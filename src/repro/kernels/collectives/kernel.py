"""Pallas TPU kernels for the CommSchedule staging + collective hot path.

The two per-step costs every embedding design pays (paper Figs 6, 9, 11)
are the ``CopyFromTo(g, comm_buf)`` staging and the allreduce itself.
This module owns both at the kernel level:

  pack / unpack     — ONE grid gathers all of a bucket's leaves into the
                      1-D comm buffer, fusing the ``comm_dtype`` cast and
                      the optional loss-scale (one HBM pass, one kernel
                      launch, instead of per-leaf ravel+cast+concatenate
                      and per-leaf slice+cast on the way back).
  ring accumulate   — the per-hop combine of the chunked ring
                      reduce-scatter (received shard += local chunk, in
                      the comm dtype), chunked to VREG-aligned blocks so
                      large buckets stream through VMEM.

The ring transport itself is the ``ppermute``-based rings in
``repro.kernels.collectives.ref``: XLA lowers each hop to an ICI DMA.

pack / unpack hold the whole bucket in VMEM and write unaligned 1-D
slices, which the TPU compiler refuses at real bucket sizes; they are
reached only through an explicit ``impl="kernel"`` and are verified in
interpret mode (tests/test_collectives.py).  ``ring_accum_kernel``
compiles for the chip (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VREG-aligned block (8 sublanes × 128 lanes) for chunked ring grids
RING_CHUNK = 8 * 128


# -------------------------------------------------------- fused staging

def _pack_kernel(*refs, sizes, scale):
    """Gather every leaf into its slice of the 1-D comm buffer.

    One grid step owns the whole bucket: offsets are compile-time
    constants, so each leaf is a single contiguous VMEM write with the
    dtype cast (and loss-scale) fused in — no intermediate per-leaf
    buffers, no concatenate.
    """
    out_ref = refs[-1]
    off = 0
    for ref, n in zip(refs[:-1], sizes):
        x = ref[...]
        if scale != 1.0:
            x = (x.astype(jnp.float32) * scale)
        out_ref[off:off + n] = x.astype(out_ref.dtype)
        off += n


def pack_bucket_kernel(leaves, comm_dtype, *, scale: float = 1.0,
                       interpret: bool = False) -> jax.Array:
    """leaves: list of 1-D arrays → (sum(sizes),) ``comm_dtype`` buffer."""
    sizes = tuple(int(l.shape[0]) for l in leaves)
    return pl.pallas_call(
        functools.partial(_pack_kernel, sizes=sizes, scale=scale),
        out_shape=jax.ShapeDtypeStruct((sum(sizes),), comm_dtype),
        interpret=interpret,
    )(*leaves)


def _unpack_kernel(buf_ref, *out_refs, sizes, scale):
    """Scatter the reduced buffer back into per-leaf outputs (cast-back
    and inverse loss-scale fused into the single read of each slice)."""
    off = 0
    for ref, n in zip(out_refs, sizes):
        x = buf_ref[off:off + n]
        if scale != 1.0:
            x = x.astype(jnp.float32) * scale
        ref[...] = x.astype(ref.dtype)
        off += n


def unpack_bucket_kernel(buf, sizes, dtypes, *, scale: float = 1.0,
                         interpret: bool = False):
    """buf: (n,) comm buffer → list of 1-D leaf arrays (given dtypes)."""
    sizes = tuple(int(s) for s in sizes)
    return pl.pallas_call(
        functools.partial(_unpack_kernel, sizes=sizes, scale=scale),
        out_shape=[jax.ShapeDtypeStruct((s,), d)
                   for s, d in zip(sizes, dtypes)],
        interpret=interpret,
    )(buf)


# ----------------------------------------------------- ring accumulate

def _accum_kernel(msg_ref, chunk_ref, out_ref):
    out_ref[...] = msg_ref[...] + chunk_ref[...]


def ring_accum_kernel(msg: jax.Array, chunk: jax.Array, *,
                      interpret: bool = False) -> jax.Array:
    """One ring hop's combine: received partial shard += local chunk.

    Chunked over ``RING_CHUNK`` blocks when the shard is block-aligned so
    arbitrarily large buckets stream through VMEM; falls back to a single
    whole-shard block otherwise (small tails).
    """
    n = msg.shape[0]
    if n > RING_CHUNK and n % RING_CHUNK == 0:
        grid = (n // RING_CHUNK,)
        spec = pl.BlockSpec((RING_CHUNK,), lambda i: (i,))
        return pl.pallas_call(
            _accum_kernel, grid=grid, in_specs=[spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((n,), msg.dtype),
            interpret=interpret,
        )(msg, chunk)
    return pl.pallas_call(
        _accum_kernel,
        out_shape=jax.ShapeDtypeStruct((n,), msg.dtype),
        interpret=interpret,
    )(msg, chunk)
