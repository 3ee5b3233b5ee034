"""Deterministic synthetic data pipeline with host sharding + prefetch.

Determinism is load-bearing for fault tolerance: batches are a pure
function of (seed, step), so a restarted worker resumes mid-epoch by
skipping to the right step — no data-state checkpointing needed (the
restore path in ``runtime.train_loop`` relies on this).

Real deployments swap ``_synth_*`` for a file-backed source keeping the
same (seed, step) → batch contract (e.g. deterministic shard shuffling).
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import span
from repro.parallel.sharding import batch_spec


def place(batch: dict[str, Any], mesh: Mesh | None) -> dict[str, Any]:
    """Put a host batch on the devices, recorded as the span
    ``data.place`` with its ``bytes``: split along the batch axes of
    ``mesh`` (scalars replicated), or onto the default device without
    one."""
    with span("data.place", bytes=sum(v.nbytes for v in batch.values())):
        if mesh is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        bspec = batch_spec(mesh)
        return {
            k: jax.device_put(
                v, NamedSharding(mesh, P() if np.ndim(v) == 0 else bspec))
            for k, v in batch.items()
        }


class TokenPipeline:
    """Synthetic LM token stream: (tokens, labels) of (B, S) int32."""

    def __init__(self, vocab: int, seq_len: int, global_batch: int,
                 *, seed: int = 0, mesh: Mesh | None = None,
                 extra_specs: dict[str, tuple[tuple[int, ...], Any]] | None = None):
        self.vocab = vocab
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.seed = seed
        self.mesh = mesh
        self.extra = extra_specs or {}

    def batch_at(self, step: int) -> dict[str, Any]:
        with span("data.synth"):
            rng = np.random.default_rng((self.seed, step))
            toks = rng.integers(
                0, self.vocab, (self.global_batch, self.seq_len + 1),
                dtype=np.int32)
            batch = {
                "tokens": toks[:, :-1],
                "labels": toks[:, 1:],
                "global_tokens": np.float32(
                    self.global_batch * self.seq_len),
            }
            for name, (shape, dtype) in self.extra.items():
                batch[name] = rng.standard_normal(
                    (self.global_batch, *shape)).astype(dtype)
        return place(batch, self.mesh)

    def __iter__(self) -> Iterator[dict[str, Any]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class ImagePipeline:
    """Synthetic image classification stream (paper's CIFAR/ImageNet)."""

    def __init__(self, img_size: int, num_classes: int, global_batch: int,
                 *, seed: int = 0, mesh: Mesh | None = None):
        self.img_size = img_size
        self.num_classes = num_classes
        self.global_batch = global_batch
        self.seed = seed
        self.mesh = mesh

    def batch_at(self, step: int) -> dict[str, Any]:
        with span("data.synth"):
            rng = np.random.default_rng((self.seed, step))
            batch = {
                "images": rng.standard_normal(
                    (self.global_batch, self.img_size, self.img_size, 3)
                ).astype(np.float32),
                "labels": rng.integers(
                    0, self.num_classes, (self.global_batch,),
                    dtype=np.int32),
                "global_tokens": np.float32(self.global_batch),
            }
        return place(batch, self.mesh)

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class Prefetcher:
    """Background-thread prefetch (the MXNET IO thread-pool analogue)."""

    _DONE = object()

    def __init__(self, it: Iterator[Any], depth: int = 2):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.err: list[BaseException] = []

        def worker():
            try:
                for item in it:
                    self.q.put(item)
            except BaseException as e:   # surfaced on next()
                self.err.append(e)
            finally:
                self.q.put(self._DONE)

        self.t = threading.Thread(target=worker, daemon=True)
        self.t.start()

    def __iter__(self):
        return self

    def __next__(self):
        item = self.q.get()
        if item is self._DONE:
            if self.err:
                raise self.err[0]
            raise StopIteration
        return item
