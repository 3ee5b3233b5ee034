"""Compiles for a described TPU v5e (no chip attached): the main path's
kernels and collectives at real sizes go through the chip's compiler,
which refuses what interpret mode accepts (unaligned VMEM slices, VMEM
overruns, programs that do not partition).

The topology is described in a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import every test file.  Nothing here runs; these are compiles.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.core import GradSync, GradSyncConfig
from repro.kernels.collectives.kernel import ring_accum_kernel
from repro.launch.mesh import make_local_mesh
from repro.models.registry import family_of


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return make_local_mesh(1, devices=topo.devices[:1])


@pytest.fixture(scope="module")
def four_chips(topo):
    return make_local_mesh(1, devices=topo.devices)


def _sds(tree, mesh, specs):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_accum_kernel_compiles_at_a_real_ring_shard(one_chip, dtype):
    # one 4 MB bucket's shard on a ring of 4
    n = (4 << 20) // jnp.dtype(dtype).itemsize // 4
    x = jax.ShapeDtypeStruct((n,), dtype,
                             sharding=NamedSharding(one_chip, P()))
    hlo = jax.jit(ring_accum_kernel).lower(x, x).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.fixture(scope="module")
def resnet_grads():
    cfg = get_arch("resnet50-cifar").make_config()
    api = family_of(cfg)
    grads = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    return grads, api.param_rules(cfg).tree_specs(grads)


@pytest.mark.parametrize("strategy", ["funnel", "concom", "depcha"])
def test_gradsync_compiles_on_v5e_2x2(four_chips, resnet_grads, strategy):
    grads, specs = resnet_grads
    gs = GradSync(GradSyncConfig(strategy=strategy), four_chips, specs,
                  grads)
    assert len(gs.plan.buckets) > 1          # 4 MB buckets of ~94 MB
    f = jax.jit(jax.shard_map(gs, mesh=four_chips, in_specs=(specs,),
                              out_specs=specs, check_vma=False))
    hlo = f.lower(_sds(grads, four_chips, specs)).compile().as_text()
    assert "all-reduce" in hlo


def test_qwen3_decoder_layer_forward_compiles_on_one_chip(one_chip):
    # published widths, depth cut to one layer
    cfg = get_arch("qwen3-1.7b").make_config(tp=1, n_layers=1)
    api = family_of(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    specs = api.param_rules(cfg).tree_specs(params)
    cspecs = api.decode_state_specs(cfg, "data")

    def prefill(p, tokens):
        return api.prefill(p, tokens, cfg)

    f = jax.jit(jax.shard_map(prefill, mesh=one_chip,
                              in_specs=(specs, P("data")),
                              out_specs=(P("data"), cspecs),
                              check_vma=False))
    tokens = jax.ShapeDtypeStruct((1, 320), jnp.int32,
                                  sharding=NamedSharding(one_chip, P("data")))
    compiled = f.lower(_sds(params, one_chip, specs), tokens).compile()
    # every weight is an argument on the one chip (nothing sharded away)
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes >= param_bytes
