"""Subprocess worker for the ring-vs-psum microbenchmark rows.

Runs under 8 fake CPU devices (jax fixes the device count at first init,
so the parent benchmark process — which must see the real single device —
spawns this).  Prints ``name,us`` CSV lines parsed by benchmarks/run.py.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings

warnings.filterwarnings("ignore")
import time
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.kernels.collectives.ops import ring_allreduce


def _t(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def main():
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    shape = {"data": 8}
    for n in (1 << 16, 1 << 20):
        x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)

        def run(body):
            return jax.jit(lambda v: jax.shard_map(
                body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False)(v))

        psum = run(lambda v: jax.lax.psum(v, ("data",)))
        ring = run(lambda v: ring_allreduce(v, ("data",), shape))
        ring_uni = run(lambda v: ring_allreduce(
            v, ("data",), shape, bidirectional=False))
        kb = n * 4 >> 10
        print(f"allreduce_psum_{kb}kb,{_t(psum, x):.1f}")
        print(f"allreduce_ring_{kb}kb,{_t(ring, x):.1f}")
        print(f"allreduce_ring_uni_{kb}kb,{_t(ring_uni, x):.1f}")


if __name__ == "__main__":
    main()
