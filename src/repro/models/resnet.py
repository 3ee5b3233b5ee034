"""The paper's own evaluation models: ResNet-50 (He et al. 2015) and an
Inception-BN-style net, for the paper-figure reproductions (Figs 13-16).

Pure data-parallel (conv nets; no TP) — params replicated, gradients
reduced over the DP axes by the strategy under test, exactly the paper's
setting (one GPU per MPI process).  BatchNorm statistics are local to the
worker, as in the paper's MXNET runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, split_rngs
from repro.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str
    stages: tuple[int, ...] = (3, 4, 6, 3)      # ResNet-50
    widths: tuple[int, ...] = (256, 512, 1024, 2048)
    stem_width: int = 64
    num_classes: int = 10
    img_size: int = 32
    dtype: Any = jnp.float32
    tp: int = 1                                  # unused (DP-only); kept for API
    dp_axes: tuple[str, ...] = ("data",)
    depcha_in_scan: bool = False                 # convnets: no layer scan


def _conv(rng, k, cin, cout, dtype):
    return dense_init(rng, (k, k, cin, cout), k * k * cin, dtype)


def init_params(rng, cfg: ResNetConfig) -> dict:
    rngs = split_rngs(rng, 4 + sum(cfg.stages) * 8)
    it = iter(rngs)
    dt = cfg.dtype
    params: dict[str, Any] = {
        "stem": {
            "conv": _conv(next(it), 3, 3, cfg.stem_width, dt),
            "bn_s": jnp.ones((cfg.stem_width,), dt),
            "bn_b": jnp.zeros((cfg.stem_width,), dt),
        }
    }
    cin = cfg.stem_width
    for si, (n, w) in enumerate(zip(cfg.stages, cfg.widths)):
        blocks = []
        for bi in range(n):
            mid = w // 4
            blk = {
                "c1": _conv(next(it), 1, cin, mid, dt),
                "bn1s": jnp.ones((mid,), dt), "bn1b": jnp.zeros((mid,), dt),
                "c2": _conv(next(it), 3, mid, mid, dt),
                "bn2s": jnp.ones((mid,), dt), "bn2b": jnp.zeros((mid,), dt),
                "c3": _conv(next(it), 1, mid, w, dt),
                "bn3s": jnp.ones((w,), dt), "bn3b": jnp.zeros((w,), dt),
            }
            if cin != w:
                blk["proj"] = _conv(next(it), 1, cin, w, dt)
            blocks.append(blk)
            cin = w
        params[f"stage{si}"] = blocks
    params["head"] = dense_init(next(it), (cin, cfg.num_classes), cin, dt)
    return params


def param_rules(cfg: ResNetConfig) -> ShardingRules:
    return ShardingRules(rules=())   # everything replicated (DP only)


def in_scan_param_names(params) -> frozenset[str]:
    return frozenset()


_BN_EPS = 1e-5
_BN_AXES = (0, 1, 2)


@jax.custom_vjp
def _bn(x, s, b):
    """BatchNorm of NHWC ``x`` per channel over (N, H, W), with the
    statistics of this worker's share of the batch and no running
    averages.  Statistics and gradients are float32 whatever ``x``'s
    dtype; the result comes back in it.

    A custom VJP so that the memory passes are the ones the math needs:
    one read of ``x`` for the statistics (Σx and Σx² side by side,
    var = E[x²] - E[x]²), and in ``_bn_bwd`` one read of ``x`` and ``dy``
    for both sums.
    """
    return _bn_fwd(x, s, b)[0]


def _bn_fwd(x, s, b):
    xf = x.astype(jnp.float32)
    n = xf.size // xf.shape[-1]
    mean = jnp.sum(xf, _BN_AXES) / n
    var = jnp.maximum(jnp.sum(xf * xf, _BN_AXES) / n - mean * mean, 0.0)
    r = jax.lax.rsqrt(var + _BN_EPS)
    y = (xf - mean) * (r * s) + b
    return y.astype(x.dtype), (x, mean, r, s, b)


def _bn_bwd(res, dy):
    """x̂ = (x - mean)·r recomputed; Σdy and Σdy·x̂ (which are db and ds)
    in one fused reduction; dx = s·r·(dy - (Σdy + x̂·Σdy·x̂)/N).  ``b``
    is kept only for its dtype."""
    x, mean, r, s, b = res
    xf = x.astype(jnp.float32)
    n = xf.size // xf.shape[-1]
    xhat = (xf - mean) * r
    g = dy.astype(jnp.float32)
    db = jnp.sum(g, _BN_AXES)
    ds = jnp.sum(g * xhat, _BN_AXES)
    dx = (s * r) * (g - (db + xhat * ds) / n)
    return dx.astype(x.dtype), ds.astype(s.dtype), db.astype(b.dtype)


_bn.defvjp(_bn_fwd, _bn_bwd)


def _conv2d(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bottleneck(p, x, stride):
    h = jax.nn.relu(_bn(_conv2d(x, p["c1"]), p["bn1s"], p["bn1b"]))
    h = jax.nn.relu(_bn(_conv2d(h, p["c2"], stride), p["bn2s"], p["bn2b"]))
    h = _bn(_conv2d(h, p["c3"]), p["bn3s"], p["bn3b"])
    sc = x
    if "proj" in p:
        sc = _conv2d(x, p["proj"], stride)
    elif stride != 1:
        sc = x[:, ::stride, ::stride]
    return jax.nn.relu(h + sc)


def forward(params, images, cfg: ResNetConfig):
    """images: (B, H, W, 3) → logits (B, classes)."""
    x = jax.nn.relu(_bn(_conv2d(images, params["stem"]["conv"]),
                        params["stem"]["bn_s"], params["stem"]["bn_b"]))
    for si, n in enumerate(cfg.stages):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            x = _bottleneck(params[f"stage{si}"][bi], x, stride)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]


def train_forward(params, batch, cfg: ResNetConfig) -> jax.Array:
    logits = forward(params, batch["images"], cfg).astype(jnp.float32)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.sum(nll) / batch["global_tokens"]


# ------------------------------------------------------- Inception-BN-ish
@dataclasses.dataclass(frozen=True)
class InceptionConfig:
    name: str
    num_classes: int = 1000
    img_size: int = 224
    width_mult: float = 1.0
    dtype: Any = jnp.float32
    tp: int = 1
    dp_axes: tuple[str, ...] = ("data",)
    depcha_in_scan: bool = False


def init_inception(rng, cfg: InceptionConfig) -> dict:
    """A compact Inception-BN-style net: stem + 6 mixed blocks."""
    rngs = split_rngs(rng, 64)
    it = iter(rngs)
    dt = cfg.dtype
    w = lambda c: int(c * cfg.width_mult)
    params: dict[str, Any] = {
        "stem": _conv(next(it), 3, 3, w(64), dt),
        "stem_bn_s": jnp.ones((w(64),), dt),
        "stem_bn_b": jnp.zeros((w(64),), dt),
    }
    cin = w(64)
    for bi, cout in enumerate([64, 128, 128, 256, 256, 512]):
        c = w(cout)
        params[f"mix{bi}"] = {
            "b1": _conv(next(it), 1, cin, c // 4, dt),
            "b3a": _conv(next(it), 1, cin, c // 4, dt),
            "b3b": _conv(next(it), 3, c // 4, c // 2, dt),
            "b5a": _conv(next(it), 1, cin, c // 8, dt),
            "b5b": _conv(next(it), 3, c // 8, c // 8, dt),
            "b5c": _conv(next(it), 3, c // 8, c // 8, dt),
            "bp": _conv(next(it), 1, cin, c // 8, dt),
            "bn_s": jnp.ones((c // 4 + c // 2 + c // 8 + c // 8,), dt),
            "bn_b": jnp.zeros((c // 4 + c // 2 + c // 8 + c // 8,), dt),
        }
        cin = c // 4 + c // 2 + c // 8 + c // 8
    params["head"] = dense_init(next(it), (cin, cfg.num_classes), cin, dt)
    return params


def inception_forward(params, images, cfg: InceptionConfig):
    x = jax.nn.relu(_bn(_conv2d(images, params["stem"], 2),
                        params["stem_bn_s"], params["stem_bn_b"]))
    for bi in range(6):
        p = params[f"mix{bi}"]
        stride = 2 if bi % 2 == 0 else 1
        b1 = _conv2d(x, p["b1"], stride)
        b3 = _conv2d(jax.nn.relu(_conv2d(x, p["b3a"])), p["b3b"], stride)
        b5 = jax.nn.relu(_conv2d(x, p["b5a"]))
        b5 = jax.nn.relu(_conv2d(b5, p["b5b"]))
        b5 = _conv2d(b5, p["b5c"], stride)
        bp = _conv2d(x, p["bp"], stride)
        x = jax.nn.relu(_bn(jnp.concatenate([b1, b3, b5, bp], -1),
                            p["bn_s"], p["bn_b"]))
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]


def inception_train_forward(params, batch, cfg: InceptionConfig) -> jax.Array:
    logits = inception_forward(params, batch["images"], cfg).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)[:, 0]
    return jnp.sum(nll) / batch["global_tokens"]
