"""Pure-jnp oracles for the collectives kernels.

``leafwise_pack``/``leafwise_unpack`` mirror the seed's per-leaf staging
(``repro.core.buckets.pack``/``unpack`` semantics plus the optional
loss-scale): per-leaf ravel + cast, one concatenate, per-leaf slice +
cast back.  They are both the parity oracle for the fused kernels and
the runtime fallback for buckets the fused path cannot take (odd
dtypes).

``ring_reduce_scatter_ref``/``ring_all_gather_ref`` are the chunked,
``ppermute``-based rings: g-1 neighbor hops over one mesh axis, each hop
one ``lax.ppermute`` (on TPU, an ICI DMA) plus an accumulate.
``bidirectional=True`` splits every chunk in half and runs a clockwise and a counter-clockwise ring at once
— two messages in flight per hop (the double-buffering), using both link
directions.  Device ``r`` ends owning chunk ``r``, matching tiled
``psum_scatter``/``all_gather`` exactly.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp


# -------------------------------------------------------------- staging

def leafwise_pack(leaves: Sequence[jax.Array], comm_dtype, *,
                  scale: float = 1.0) -> jax.Array:
    """Per-leaf cast + concatenate (the seed emission, paper's CopyFromTo)."""
    parts = []
    for x in leaves:
        x = jnp.ravel(x)
        if scale != 1.0:
            x = x.astype(jnp.float32) * scale
        parts.append(x.astype(comm_dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def leafwise_unpack(buf: jax.Array, sizes: Sequence[int], dtypes, *,
                    scale: float = 1.0) -> list[jax.Array]:
    """Static per-leaf slice + cast back (1-D pieces, caller reshapes)."""
    out = []
    off = 0
    for n, dt in zip(sizes, dtypes):
        x = jax.lax.slice(buf, (off,), (off + n,))
        if scale != 1.0:
            x = x.astype(jnp.float32) * scale
        out.append(x.astype(dt))
        off += n
    return out


# ---------------------------------------------------------- ring (1 axis)

def _fwd_perm(g: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % g) for i in range(g)]


def _bwd_perm(g: int) -> list[tuple[int, int]]:
    return [(i, (i - 1) % g) for i in range(g)]


def _chunk(x2d: jax.Array, idx) -> jax.Array:
    """Row ``idx`` (traced device-dependent index) of the (g, c) view."""
    return jax.lax.dynamic_slice_in_dim(x2d, idx, 1, 0)[0]


def _ring_rs_one_way(x2d: jax.Array, axis: str, g: int, forward: bool,
                     accum: Callable) -> jax.Array:
    """One directional ring: g-1 hops, device r ends owning chunk r."""
    r = jax.lax.axis_index(axis)
    perm = _fwd_perm(g) if forward else _bwd_perm(g)
    sgn = 1 if forward else -1
    # hop 0's payload: our own value of chunk r ∓ 1
    msg = _chunk(x2d, (r - sgn) % g)
    for s in range(1, g):
        msg = jax.lax.ppermute(msg, axis, perm)
        # received the partial of chunk r ∓ (s+1); add our contribution
        msg = accum(msg, _chunk(x2d, (r - sgn * (s + 1)) % g))
    return msg


def ring_reduce_scatter_ref(
    x: jax.Array, axis: str, g: int, *,
    bidirectional: bool = True,
    accum: Callable[[jax.Array, jax.Array], jax.Array] = jnp.add,
) -> jax.Array:
    """(n,) per-device buffer (n % g == 0) → (n/g,) reduced shard.

    ``accum`` is the per-hop combine — ``jnp.add`` here, the Pallas
    ``ring_accum_kernel`` when driven from ``ops``.
    """
    if g == 1:
        return x
    c = x.shape[0] // g
    x2d = x.reshape(g, c)
    h = c // 2
    if not bidirectional or h == 0:
        return _ring_rs_one_way(x2d, axis, g, True, accum)
    # two half-width rings in flight per hop: cw on [:h], ccw on [h:]
    lo = _ring_rs_one_way(x2d[:, :h], axis, g, True, accum)
    hi = _ring_rs_one_way(x2d[:, h:], axis, g, False, accum)
    return jnp.concatenate([lo, hi])


def _ring_ag_one_way(shard: jax.Array, axis: str, g: int,
                     forward: bool) -> jax.Array:
    """(c,) owned chunk → (g, c): g-1 hops circulate every chunk."""
    r = jax.lax.axis_index(axis)
    perm = _fwd_perm(g) if forward else _bwd_perm(g)
    sgn = 1 if forward else -1
    out = jnp.zeros((g,) + shard.shape, shard.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, shard[None], r, 0)
    msg = shard
    for s in range(1, g):
        msg = jax.lax.ppermute(msg, axis, perm)
        # hop s delivers chunk r ∓ s
        out = jax.lax.dynamic_update_slice_in_dim(
            out, msg[None], (r - sgn * s) % g, 0)
    return out


def ring_all_gather_ref(
    shard: jax.Array, axis: str, g: int, *, bidirectional: bool = True,
) -> jax.Array:
    """(c,) owned shard (device r owns chunk r) → (g*c,) full buffer."""
    if g == 1:
        return shard
    c = shard.shape[0]
    h = c // 2
    if not bidirectional or h == 0:
        return _ring_ag_one_way(shard, axis, g, True).reshape(-1)
    lo = _ring_ag_one_way(shard[:h], axis, g, True)
    hi = _ring_ag_one_way(shard[h:], axis, g, False)
    return jnp.concatenate([lo, hi], axis=1).reshape(-1)
