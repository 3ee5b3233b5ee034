"""Plain float32 Qwen3 decoder forward (hf:Qwen/Qwen3-1.7B family).

Straightforward ``jax.numpy`` over the whole sequence: no cache, no
paging, no bucketing, no kernels.  It imports nothing of the system
under test.  It follows the published architecture: pre-norm RMSNorm
blocks, grouped-query attention with RMSNorm on each query and key head
(qk-norm), rotary embeddings (rotate-half, base ``rope_theta``), SwiGLU
MLP, final RMSNorm, and a head tied to the embedding.  The system keeps
its head as a separate ``lm_head`` matrix; the served tree holds one,
made as the embedding's transpose, so the served model is the tied one
(see ``bench/configs/qwen3-1.7b.json``).  The reference never reads it.

The weights are served in bfloat16; the reference reads those values and
computes every product and sum in float32 at "highest" precision, layer
by layer.  The module also keeps the weight maker and the analytic
operation and byte counts of prefill and decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that uses every bit of a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def dims(sizes: dict) -> dict:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    return {"d": d, "hd": hd, "hq": sizes["num_attention_heads"],
            "hkv": sizes["num_key_value_heads"],
            "ff": sizes["intermediate_size"],
            "layers": sizes["num_hidden_layers"], "vocab": sizes["vocab_size"]}


def weight_plan(sizes: dict) -> list[tuple[str, tuple[int, ...], int]]:
    """Every weight as (path, shape, fan_in; 0 for a norm scale), in the
    served tree's layout: ``embed``, stacked ``blocks``, ``ln_f``,
    ``lm_head`` (the embedding's transpose: fan_in 0 here, never drawn)."""
    m = dims(sizes)
    d, hd, L = m["d"], m["hd"], m["layers"]
    q, kv, ff = m["hq"] * hd, m["hkv"] * hd, m["ff"]
    return [
        ("embed", (m["vocab"], d), d),
        ("blocks/ln1", (L, d), 0), ("blocks/wq", (L, d, q), d),
        ("blocks/wk", (L, d, kv), d), ("blocks/wv", (L, d, kv), d),
        ("blocks/wo", (L, q, d), q), ("blocks/ln2", (L, d), 0),
        ("blocks/qnorm", (L, hd), 0), ("blocks/knorm", (L, hd), 0),
        ("blocks/wg", (L, d, ff), d), ("blocks/wu", (L, d, ff), d),
        ("blocks/wdown", (L, ff, d), ff),
        ("ln_f", (d,), 0), ("lm_head", (d, m["vocab"]), 0),
    ]


def init_weights(seed: int, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in one jitted call, in the served type: normal /
    sqrt(fan_in) for matrices (the embedding too, so its rows have the
    scale of a hidden state), 1 + 0.1 normal for norm scales; the head
    is the embedding's transpose."""
    plan = weight_plan(sizes)

    @jax.jit
    def make(key):
        out: dict = {"blocks": {}}
        for i, (path, shape, fan_in) in enumerate(plan):
            if path == "lm_head":
                out[path] = out["embed"].T
                continue
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            v = z / np.sqrt(fan_in) if fan_in else 1.0 + 0.1 * z
            if path.startswith("blocks/"):
                out["blocks"][path[7:]] = v.astype(dtype)
            else:
                out[path] = v.astype(dtype)
        return out

    return make(seed_key(seed))


# ------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x: (S, H, D), pos: (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                          / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def read_weight(a, wdtype: str = "float32"):
    """A weight as float32.  Below float32 (the control) it is first
    quantized: scaled per output column to the type's range (per tensor
    for a vector), rounded to ``wdtype`` and scaled back."""
    a = a.astype(jnp.float32)
    if wdtype == "float32":
        return a
    top = 127.0 if wdtype == "int8" else float(jnp.finfo(wdtype).max)
    axis = -2 if a.ndim >= 2 else None
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / top
    z = a / s
    z = jnp.round(z).astype(jnp.int8) if wdtype == "int8" else z.astype(wdtype)
    return z.astype(jnp.float32) * s


def hidden_states(w, tokens, sizes: dict, wdtype: str = "float32"):
    """Final-norm hidden states (S, d) of one sequence.  Every weight is
    read by ``read_weight`` and every product computed in float32."""
    m = dims(sizes)
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    S = tokens.shape[0]
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    f = functools.partial(read_weight, wdtype=wdtype)
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = f(w["embed"])[tokens]

    def layer(x, p):
        p = jax.tree.map(f, p)
        h = _rms(x, p["ln1"], eps)
        q = (h @ p["wq"]).reshape(S, hq, hd)
        k = (h @ p["wk"]).reshape(S, hkv, hd)
        v = (h @ p["wv"]).reshape(S, hkv, hd)
        q = _rope(_rms(q, p["qnorm"], eps), pos, theta)
        k = _rope(_rms(k, p["knorm"], eps), pos, theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(S, hq * hd) @ p["wo"]
        h = _rms(x, p["ln2"], eps)
        x = x + (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wdown"]
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    return _rms(x, f(w["ln_f"]), eps)


@functools.partial(jax.jit, static_argnames=("sizes_items", "wdtype"))
def _next_logit_stats(w, tokens, follow, sizes_items, wdtype):
    sizes = dict(sizes_items)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(w, tokens, sizes, wdtype)
        logits = h @ read_weight(w["embed"].T, wdtype)
    best = jnp.max(logits, -1)
    pick = jnp.take_along_axis(logits, follow[:, None], -1)[:, 0]
    return best - pick, jnp.argmax(logits, -1).astype(jnp.int32)


def next_token_gaps(w, tokens: np.ndarray, follow: np.ndarray,
                    sizes: dict, wdtype: str = "float32"):
    """For each position p of ``tokens`` (one sequence, padded at the
    end as the caller likes: attention is causal), the gap by which the
    logit of ``follow[p]`` lies below the best logit at p, and the argmax
    at p.  ``wdtype`` below float32 reads the weights in that type (the
    control: ``"float8_e4m3fn"`` or ``"int8"``)."""
    items = tuple(sorted((k, v) for k, v in sizes.items()
                         if isinstance(v, (int, float))))
    gaps, top = _next_logit_stats(w, jnp.asarray(tokens, jnp.int32),
                                  jnp.asarray(follow, jnp.int32), items,
                                  wdtype)
    return np.asarray(gaps), np.asarray(top)


# ----------------------------------------------------- analytic counts
def _per_token_matmul_flops(sizes: dict) -> float:
    m = dims(sizes)
    d, hd = m["d"], m["hd"]
    per_layer = 2 * d * (m["hq"] + 2 * m["hkv"]) * hd     # q, k, v
    per_layer += 2 * m["hq"] * hd * d                      # o
    per_layer += 2 * 3 * d * m["ff"]                       # gate, up, down
    return float(m["layers"] * per_layer)


def _attn_flops(sizes: dict, keys: float) -> float:
    """QK^T and PV for one query attending to ``keys`` positions."""
    m = dims(sizes)
    return float(m["layers"] * 4 * m["hq"] * m["hd"] * keys)


def head_flops(sizes: dict) -> float:
    return float(2 * sizes["hidden_size"] * sizes["vocab_size"])


def prefill_flops(sizes: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` real tokens: every token's layers,
    causal attention (token i attends to i + 1 keys) and one head row
    (the first output token)."""
    L = int(prompt_len)
    return (L * _per_token_matmul_flops(sizes)
            + _attn_flops(sizes, L * (L + 1) / 2) + head_flops(sizes))


def decode_flops(sizes: dict, kv_len: int) -> float:
    """One decode token that attends to ``kv_len`` positions (itself
    included)."""
    return (_per_token_matmul_flops(sizes) + _attn_flops(sizes, kv_len)
            + head_flops(sizes))


def kv_bytes_per_position(sizes: dict, itemsize: int = 2) -> int:
    m = dims(sizes)
    return m["layers"] * 2 * m["hkv"] * m["hd"] * itemsize


def decode_weight_bytes(sizes: dict, rows: int, itemsize: int = 2) -> int:
    """Weights one decode step must read: every layer, the final norm and
    the head once, and one embedding row per active slot."""
    total = 0
    for path, shape, _ in weight_plan(sizes):
        n = int(np.prod(shape))
        if path == "embed":
            n = rows * shape[1]
        total += n * itemsize
    return total


def decode_step_bytes(sizes: dict, kv_lens, itemsize: int = 2) -> int:
    """Least bytes of one decode step of the active slots whose cache
    lengths (the new token included) are ``kv_lens``: the weights above,
    each slot's earlier K/V (``kv_len - 1`` positions) read and its new
    K/V row written."""
    kv = kv_bytes_per_position(sizes, itemsize)
    return (decode_weight_bytes(sizes, len(kv_lens), itemsize)
            + kv * int(sum(kv_lens)))
