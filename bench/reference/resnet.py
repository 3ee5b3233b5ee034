"""Plain float32 ResNet-50 (He et al. 2015, arXiv:1512.03385), CIFAR form.

Straightforward ``jax.numpy``: no kernels, no sharding, no bucketing.  It
imports nothing of the system under test.  Departures from the paper,
each the deployment's own (see ``bench/configs/resnet50-cifar.json``):

- the CIFAR stem is one 3x3 stride-1 convolution with no max-pool, so
  stage 0 runs at 32x32;
- convolutions carry no bias (BatchNorm follows each);
- BatchNorm uses the statistics of each worker's share of the batch
  (``bn_groups`` workers, as in the paper's MXNET runs) and keeps no
  running averages; epsilon 1e-5;
- the stride of a down-sampling bottleneck sits on its 3x3 convolution
  and on the 1x1 projection.

The module also keeps the benchmark's own input generator, its weight
maker, and the analytic operation count of one training sample.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that uses every bit of a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def layer_plan(sizes: dict) -> list[tuple[str, tuple[int, ...], int]]:
    """Every weight as (path, shape, fan_in), in the layout the served
    parameter tree uses: ``stem``, ``stage<i>`` lists of bottlenecks,
    ``head``."""
    stem, k0 = sizes["stem_width"], sizes["stem_kernel"]
    plan = [("stem/conv", (k0, k0, 3, stem), k0 * k0 * 3),
            ("stem/bn_s", (stem,), 0), ("stem/bn_b", (stem,), 0)]
    cin = stem
    for si, (n, w) in enumerate(zip(sizes["stages"], sizes["widths"])):
        mid = w // sizes["bottleneck_ratio"]
        for bi in range(n):
            pre = f"stage{si}/{bi}/"
            plan += [(pre + "c1", (1, 1, cin, mid), cin),
                     (pre + "bn1s", (mid,), 0), (pre + "bn1b", (mid,), 0),
                     (pre + "c2", (3, 3, mid, mid), 9 * mid),
                     (pre + "bn2s", (mid,), 0), (pre + "bn2b", (mid,), 0),
                     (pre + "c3", (1, 1, mid, w), mid),
                     (pre + "bn3s", (w,), 0), (pre + "bn3b", (w,), 0)]
            if cin != w:
                plan.append((pre + "proj", (1, 1, cin, w), cin))
            cin = w
    plan.append(("head", (cin, sizes["num_classes"]), cin))
    return plan


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    for k, v in list(out.items()):
        if k.startswith("stage"):
            out[k] = [v[str(i)] for i in range(len(v))]
    return out


def init_weights(seed: int, sizes: dict, dtype=jnp.float32) -> dict:
    """Seeded weights in one jitted call: He-style normal / sqrt(fan_in)
    for convolutions and the head, BatchNorm scale 1 + 0.1 normal and
    shift 0.1 normal (so a dropped scale or shift shows)."""
    plan = layer_plan(sizes)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape, fan_in) in enumerate(plan):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if fan_in:
                v = z / np.sqrt(fan_in)
            elif path.endswith("s"):               # BatchNorm scale
                v = 1.0 + 0.1 * z
            else:
                v = 0.1 * z
            flat[path] = v.astype(dtype)
        return _nest(flat)

    return make(seed_key(seed))


def batch(seed: int, step: int, global_batch: int, img: int,
          classes: int) -> dict:
    """The benchmark's input for ``step``: standard-normal images and
    uniform labels from ``numpy.random.default_rng((seed, step))`` (the
    (seed, step) contract of the system's synthetic image source)."""
    rng = np.random.default_rng((int(seed), int(step)))
    images = rng.standard_normal(
        (global_batch, img, img, 3)).astype(np.float32)
    labels = rng.integers(0, classes, (global_batch,), dtype=np.int32)
    return {"images": images, "labels": labels}


# ------------------------------------------------------------- forward
def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, s, b, groups: int):
    """BatchNorm over each group's rows (the batch is split in order)."""
    n = x.shape[0]
    g = x.reshape(groups, n // groups, *x.shape[1:])
    mean = jnp.mean(g, axis=(1, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 3), keepdims=True)
    y = (g - mean) / jnp.sqrt(var + BN_EPS)
    return y.reshape(x.shape) * s.astype(x.dtype) + b.astype(x.dtype)


def forward(params, images, sizes: dict, groups: int):
    relu = jax.nn.relu
    st = params["stem"]
    x = relu(_bn(_conv(images, st["conv"]), st["bn_s"], st["bn_b"], groups))
    for si, n in enumerate(sizes["stages"]):
        for bi in range(n):
            p = params[f"stage{si}"][bi]
            stride = 2 if (bi == 0 and si > 0) else 1
            h = relu(_bn(_conv(x, p["c1"]), p["bn1s"], p["bn1b"], groups))
            h = relu(_bn(_conv(h, p["c2"], stride), p["bn2s"], p["bn2b"],
                         groups))
            h = _bn(_conv(h, p["c3"]), p["bn3s"], p["bn3b"], groups)
            if "proj" in p:
                sc = _conv(x, p["proj"], stride)
            else:
                sc = x[:, ::stride, ::stride]
            x = relu(h + sc)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"].astype(x.dtype)


def loss_fn(params, images, labels, sizes: dict, groups: int):
    """Mean cross-entropy over the global batch."""
    logits = forward(params, images, sizes, groups).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.mean(nll)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def sgd_run(params, batches, sizes: dict, *, groups: int, lr: float,
            momentum: float, clip_norm: float, dtype=jnp.float32,
            grad_fn=None):
    """Train ``len(batches)`` steps of SGD with momentum after clipping
    the gradient to ``clip_norm`` (global L2), at "highest" matmul
    precision.  Returns (losses, momentum after step 1, params after the
    last step, the first gradient's global norm before clipping), on the
    host in float32.  ``dtype`` lower than float32 computes the whole
    step in that type (the control).  ``grad_fn`` replaces
    ``jax.value_and_grad(loss_fn)`` (planted faults)."""
    vg = grad_fn or jax.value_and_grad(
        functools.partial(loss_fn, sizes=sizes, groups=groups))

    @jax.jit
    def step(p, mom, images, labels):
        loss, g = vg(p, images.astype(dtype), labels)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        norm = _global_norm(g)
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip_norm
                                                   / (norm + 1e-9)), g)
        mom = jax.tree.map(lambda m, x: momentum * m + x, mom, g)
        p = jax.tree.map(lambda w, m: (w.astype(jnp.float32) - lr * m
                                       ).astype(dtype), p, mom)
        return p, mom, loss, norm

    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        mom = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), params)
        losses, mom1, norm0 = [], None, None
        for b in batches:
            p, mom, loss, norm = step(p, mom, jnp.asarray(b["images"]),
                                      jnp.asarray(b["labels"]))
            losses.append(float(loss))
            if mom1 is None:
                mom1, norm0 = jax.tree.map(np.asarray, mom), float(norm)
    return losses, mom1, jax.tree.map(
        lambda w: np.asarray(w, np.float32), p), norm0


# ----------------------------------------------------- analytic counts
def forward_flops_per_sample(sizes: dict) -> float:
    """Multiply-add operations (x2) of one image's forward pass:
    convolutions and the head.  BatchNorm, ReLU and pooling are left
    out, as MFU conventionally counts them."""
    img = sizes["image_size"]
    hw = img * img
    k0 = sizes["stem_kernel"]
    total = 2 * hw * k0 * k0 * 3 * sizes["stem_width"]
    cin = sizes["stem_width"]
    for si, (n, w) in enumerate(zip(sizes["stages"], sizes["widths"])):
        mid = w // sizes["bottleneck_ratio"]
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            out_hw = hw // (stride * stride)
            total += 2 * hw * cin * mid                 # c1 at input size
            total += 2 * out_hw * 9 * mid * mid         # c2 (strided)
            total += 2 * out_hw * mid * w               # c3
            if cin != w:
                total += 2 * out_hw * cin * w           # projection
            hw, cin = out_hw, w
    total += 2 * cin * sizes["num_classes"]
    return float(total)


def train_flops_per_sample(sizes: dict) -> float:
    """Forward plus backward: each layer's backward takes twice its
    forward (gradients of input and of weight), except the stem, whose
    input needs no gradient."""
    img = sizes["image_size"]
    stem = 2 * img * img * sizes["stem_kernel"] ** 2 * 3 * sizes["stem_width"]
    fwd = forward_flops_per_sample(sizes)
    return 3 * fwd - stem
