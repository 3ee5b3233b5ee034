"""Fused comm-staging + ring collectives: the public API.

Three implementation tiers, selected per call (``impl=``):

  xla      — the default on every backend: a fused XLA emission.  Pack
             concatenates in the source dtype and runs ONE
             cast(+loss-scale) pass over the whole buffer; unpack is
             static ``lax.slice`` + cast (fusion-friendly — no dynamic
             offsets).
  kernel   — the Pallas kernels (``kernel.py``), only when asked for.
             They do not compile for the TPU at real bucket sizes
             (unaligned VMEM slices, whole bucket in VMEM), so they run
             in interpret mode off the chip and are tested there.
  leafwise — the seed's per-leaf emission (``ref.py``), kept as the
             oracle and the fallback for buckets the fused path cannot
             take (non-float dtypes).

The ring collectives run the chunked, bidirectional (double-buffered)
``ppermute`` rings from ``ref.py`` — on TPU each hop lowers to an ICI
DMA — with the per-hop accumulate optionally routed through the Pallas
``ring_accum_kernel``.  Device
``r`` owns chunk ``r`` after reduce-scatter, so they are drop-in for
``psum_scatter``/``all_gather`` (tiled) anywhere in the repo: the
``ring`` reducer, rsag's two-phase ops, the hierarchical fast-tier
stages and compressed's gather phase.
"""
from __future__ import annotations

import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.collectives import ref
from repro.kernels.collectives.kernel import (
    pack_bucket_kernel,
    ring_accum_kernel,
    unpack_bucket_kernel,
)

_FLOATS = (jnp.float32, jnp.bfloat16, jnp.float16, jnp.float64)

# the staging tier every bucket takes unless a caller names another
DEFAULT_STAGING = "xla"


def staging_supported(leaf_dtypes, comm_dtype) -> bool:
    """Fused staging handles float↔float casts; anything else (int grads,
    complex) falls back to the leafwise ref path."""
    dts = tuple(leaf_dtypes) + (comm_dtype,)
    return all(jnp.dtype(d) in [jnp.dtype(f) for f in _FLOATS] for d in dts)


# -------------------------------------------------------------- staging

def fused_pack(bucket, flat_leaves: Sequence[jax.Array], comm_dtype, *,
               scale: float = 1.0, impl: str = DEFAULT_STAGING,
               interpret: bool = False) -> jax.Array:
    """CopyFromTo(g, comm_buf), fused: one staging pass over the bucket.

    ``bucket``: a ``repro.core.buckets.Bucket``; ``flat_leaves``: the flat
    gradient list it indexes into.  ``scale`` is the optional loss-scale
    folded into the cast.
    """
    leaves = [jnp.ravel(flat_leaves[l.index]) for l in bucket.leaves]
    if impl == "kernel":
        return pack_bucket_kernel(
            leaves, comm_dtype, scale=scale,
            interpret=interpret or jax.default_backend() != "tpu")
    if impl == "xla":
        if len({l.dtype for l in leaves}) == 1:
            buf = leaves[0] if len(leaves) == 1 else jnp.concatenate(leaves)
            if scale != 1.0:
                buf = buf.astype(jnp.float32) * scale
            return buf.astype(comm_dtype)
        # mixed-dtype bucket: per-leaf cast keeps rounding identical to
        # the leafwise oracle (concat would promote first)
        return ref.leafwise_pack(leaves, comm_dtype, scale=scale)
    if impl == "leafwise":
        return ref.leafwise_pack(leaves, comm_dtype, scale=scale)
    raise ValueError(f"unknown staging impl {impl!r}")


def fused_unpack(bucket, buf: jax.Array, flat_out: list, *,
                 scale: float = 1.0, impl: str = DEFAULT_STAGING,
                 interpret: bool = False) -> None:
    """CopyFromTo(recv_buf, g), fused: scatter the reduced buffer back
    into ``flat_out`` (cast-back + inverse loss-scale in the same pass)."""
    sizes = [l.size for l in bucket.leaves]
    dtypes = [l.dtype for l in bucket.leaves]
    if impl == "kernel":
        pieces = unpack_bucket_kernel(
            buf, sizes, dtypes, scale=scale,
            interpret=interpret or jax.default_backend() != "tpu")
    elif impl in ("xla", "leafwise"):
        pieces = ref.leafwise_unpack(buf, sizes, dtypes, scale=scale)
    else:
        raise ValueError(f"unknown staging impl {impl!r}")
    for l, piece in zip(bucket.leaves, pieces):
        flat_out[l.index] = piece.reshape(l.shape)


# ---------------------------------------------------------------- rings

def _ring_axes(axes: Sequence[str],
               mesh_shape: Mapping[str, int]) -> list[tuple[str, int]]:
    return [(a, int(mesh_shape[a])) for a in axes
            if int(mesh_shape.get(a, 1)) > 1]


def group_size(axes: Sequence[str], mesh_shape: Mapping[str, int]) -> int:
    g = 1
    for _, s in _ring_axes(axes, mesh_shape):
        g *= s
    return g


def _accum(use_kernel: bool, interpret: bool):
    if not use_kernel:
        return jnp.add
    return functools.partial(ring_accum_kernel, interpret=interpret)


def ring_reduce_scatter(
    buf: jax.Array, axes: tuple[str, ...],
    mesh_shape: Mapping[str, int], *,
    bidirectional: bool = True, use_accum_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """(n,) buffer, n divisible by the group size → (n/g,) shard.

    Multi-axis groups decompose axis-by-axis in the given order (shards
    shrink per tier); ``ring_all_gather`` reverses the same order, so the
    pair composes to a ring allreduce over the product group.
    """
    interpret = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    accum = _accum(use_accum_kernel, interpret)
    for a, g in _ring_axes(axes, mesh_shape):
        buf = ref.ring_reduce_scatter_ref(
            buf, a, g, bidirectional=bidirectional, accum=accum)
    return buf


def ring_all_gather(
    shard: jax.Array, axes: tuple[str, ...],
    mesh_shape: Mapping[str, int], *, bidirectional: bool = True,
) -> jax.Array:
    """(n/g,) owned shard → (n,) full buffer (reverse of the RS order)."""
    for a, g in reversed(_ring_axes(axes, mesh_shape)):
        shard = ref.ring_all_gather_ref(
            shard, a, g, bidirectional=bidirectional)
    return shard


def ring_allreduce(
    buf: jax.Array, axes: tuple[str, ...],
    mesh_shape: Mapping[str, int], *,
    bidirectional: bool = True, use_accum_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked ring allreduce = ring RS → ring AG (pads internally)."""
    g = group_size(axes, mesh_shape)
    if g == 1:
        return buf
    n = buf.shape[0]
    pad = (-n) % g
    if pad:
        buf = jnp.pad(buf, (0, pad))
    shard = ring_reduce_scatter(
        buf, axes, mesh_shape, bidirectional=bidirectional,
        use_accum_kernel=use_accum_kernel, interpret=interpret)
    full = ring_all_gather(shard, axes, mesh_shape,
                           bidirectional=bidirectional)
    return full[:n] if pad else full
