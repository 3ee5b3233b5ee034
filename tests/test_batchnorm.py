"""The conv nets' BatchNorm (``repro.models.resnet._bn``): one-pass float32
statistics and a hand-written backward, checked against plain autodiff of
the centred two-pass formula."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.resnet import _bn

EPS = 1e-5   # the configs' BatchNorm epsilon


def _two_pass(x, s, b):
    """BatchNorm as plain autodiff sees it: mean, then centred variance."""
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * s + b


def _rel(a, ref):
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def _inputs(seed, shape, mean=0.0):
    kx, ks, kb, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
    c = shape[-1]
    x = mean + jax.random.normal(kx, shape, jnp.float32)
    s = 1.0 + 0.1 * jax.random.normal(ks, (c,), jnp.float32)
    b = 0.1 * jax.random.normal(kb, (c,), jnp.float32)
    dy = jax.random.normal(kd, shape, jnp.float32)
    return x, s, b, dy


def _inception_concat(seed):
    """The four branches of an Inception-BN mixed block at width 64
    (16 + 32 + 8 + 8 channels), each with its own offset, concatenated
    as ``inception_forward`` feeds them to one BatchNorm."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    parts = [off + jax.random.normal(k, (2, 8, 8, c), jnp.float32)
             for k, c, off in zip(ks, (16, 32, 8, 8), (0.0, 1.0, -0.5, 2.0))]
    return jnp.concatenate(parts, -1)


CASES = {
    "stem": dict(shape=(4, 32, 32, 64)),
    "stage3": dict(shape=(8, 4, 4, 2048)),
    "nonzero_mean": dict(shape=(4, 32, 32, 64), mean=2.0),
}


@pytest.mark.parametrize("case", [*CASES, "inception_concat"])
def test_bn_matches_two_pass_autodiff(case):
    if case == "inception_concat":
        x = _inception_concat(1)
        _, s, b, dy = _inputs(2, x.shape)
    else:
        x, s, b, dy = _inputs(0, **CASES[case])
    y, vjp = jax.vjp(_bn, x, s, b)
    y_ref, vjp_ref = jax.vjp(_two_pass, x, s, b)
    assert _rel(y, y_ref) <= 1e-5
    for got, want in zip(vjp(dy), vjp_ref(dy)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _rel(got, want) <= 1e-5


@pytest.mark.parametrize("ratio", [0.0, 2.0, 4.0])
def test_bn_one_pass_variance_precision(ratio):
    """E[x²] - E[x]² cancels: its float32 error grows with (mean/std)².
    At mean/std 4 (the worst channel of resnet50-cifar at its seeded
    start is about 3.8) it stays within 3e-6·(1 + ratio²) of the exact
    result, here float64 autodiff of the two-pass formula."""
    x, s, b, dy = _inputs(3, (4, 32, 32, 64), mean=ratio)
    y, vjp = jax.vjp(_bn, x, s, b)
    got = (y, *vjp(dy))
    with jax.enable_x64(True):
        f64 = [jnp.asarray(np.asarray(a), jnp.float64) for a in (x, s, b, dy)]
        y64, vjp64 = jax.vjp(_two_pass, *f64[:3])
        want = [np.asarray(a) for a in (y64, *vjp64(f64[3]))]
    tol = 3e-6 * (1.0 + ratio ** 2)
    for g, w in zip(got, want):
        assert _rel(g, w) <= tol


def test_bn_bf16_input_keeps_float32_statistics():
    x, s, b, dy = _inputs(4, (4, 8, 8, 32), mean=1.0)
    xb, dyb = x.astype(jnp.bfloat16), dy.astype(jnp.bfloat16)
    y, vjp = jax.vjp(_bn, xb, s, b)
    dx, ds, db = vjp(dyb)
    assert y.dtype == dx.dtype == jnp.bfloat16
    assert ds.dtype == db.dtype == jnp.float32
    # The same math on the float32 upcast, rounded once to bfloat16.
    y32, vjp32 = jax.vjp(_bn, xb.astype(jnp.float32), s, b)
    dx32, ds32, db32 = vjp32(dyb.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y32.astype(jnp.bfloat16),
                                             np.float32))
    np.testing.assert_array_equal(np.asarray(dx, np.float32),
                                  np.asarray(dx32.astype(jnp.bfloat16),
                                             np.float32))
    assert _rel(ds, ds32) <= 1e-6 and _rel(db, db32) <= 1e-6
    # Every reduction, forward and backward, accumulates in float32.
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(_bn, *a[:3])[1](a[3]))(
        xb, s, b, dyb)
    sums = _reduce_sums(jaxpr.jaxpr)
    assert sums and all(e.outvars[0].aval.dtype == jnp.float32 for e in sums)


def _reduce_sums(jaxpr):
    """Every ``reduce_sum`` equation of a jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "reduce_sum":
            out.append(eqn)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _reduce_sums(inner)
    return out


def test_bn_gradient_runs_four_reductions():
    """Forward Σx and Σx², backward Σdy and Σdy·x̂: four in all, where
    autodiff of the two-pass formula runs more than twice as many."""
    x, s, b, dy = _inputs(5, (2, 4, 4, 8))

    def grad_jaxpr(bn):
        return jax.make_jaxpr(
            lambda *a: jax.vjp(bn, *a[:3])[1](a[3]))(x, s, b, dy).jaxpr

    assert len(_reduce_sums(grad_jaxpr(_bn))) <= 4
    assert len(_reduce_sums(grad_jaxpr(_two_pass))) > 8
