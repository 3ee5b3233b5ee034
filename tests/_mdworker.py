"""Multi-device worker: runs under XLA_FLAGS=8 fake devices in a
subprocess (jax device count is fixed at first init, so these checks
can't live in the main pytest process).  Prints PASS/FAIL lines parsed by
tests/test_multidevice.py."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings

warnings.filterwarnings("ignore")
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import GradSync, GradSyncConfig
from repro.models import transformer as tf
from repro.models.registry import family_of
from repro.utils.trees import named_leaves

mesh8 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2,
                      devices=jax.devices()[:1])

B, S = 4, 32
_rng = jax.random.PRNGKey(0)
BATCH = {
    "tokens": jax.random.randint(_rng, (B, S), 0, 96),
    "labels": jax.random.randint(_rng, (B, S), 0, 96),
    "global_tokens": jnp.float32(B * S),
}


def loss_and_grads(cfg, mesh, params, strategy="concom", reducer="flat"):
    api = family_of(cfg)
    params = params  # global tree; sharded below
    rules = api.param_rules(cfg)
    pspecs = rules.tree_specs(params)
    bspecs = {k: (P() if np.ndim(v) == 0 else P("data"))
              for k, v in BATCH.items()}
    tp = cfg.tp
    sync = GradSyncConfig(strategy=strategy, reducer=reducer,
                          bucket_bytes=1 << 12, num_channels=3)

    in_scan = (api.in_scan_names(params)
               if getattr(cfg, "depcha_in_scan", False) else frozenset())

    def step(p, b):
        loss, grads = jax.value_and_grad(
            lambda pp: api.train_forward(pp, b, cfg))(p)
        if tp > 1:
            grads = jax.tree.map(lambda g: g / tp, grads)
        gs = GradSync(sync, mesh, pspecs, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads),
            in_scan_names=in_scan)
        grads = gs(grads)
        return jax.lax.psum(loss, ("data",)), grads

    f = jax.jit(lambda p, b: jax.shard_map(
        step, mesh=mesh, in_specs=(pspecs, bspecs),
        out_specs=(P(), pspecs), check_vma=False)(p, b))
    ps = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs))
    bs = jax.device_put(BATCH, {k: NamedSharding(mesh, s)
                                for k, s in bspecs.items()})
    return f(ps, bs)


def check(name, cond):
    print(("PASS " if cond else "FAIL ") + name, flush=True)


def compare_tp(name, mk_cfg, strategy="concom", reducer="flat", tol=3e-4,
               grad_tol=2e-3):
    cfg1, cfg4 = mk_cfg(1), mk_cfg(4)
    api = family_of(cfg1)
    params = api.init(jax.random.PRNGKey(1), cfg1)
    l1, g1 = loss_and_grads(cfg1, mesh1, params, strategy, reducer)
    l4, g4 = loss_and_grads(cfg4, mesh8, params, strategy, reducer)
    dl = abs(float(l1) - float(l4))
    worst = 0.0
    for (n, a), (_, b) in zip(named_leaves(g1), named_leaves(g4)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.shape != b.shape:
            continue
        rel = float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-8))
        worst = max(worst, rel)
    check(f"{name} dloss<{tol}", dl < tol)
    check(f"{name} grads<{grad_tol}", worst < grad_tol)


mk_dense = lambda tp: tf.TransformerConfig(
    name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=2, d_ff=128,
    vocab=96, tp=tp, attn_chunk=16, dtype=jnp.float32)

# 1. TP=4 x DP=2 == TP=1 for each registered strategy (the paper's
#    correctness claim across real process groups; priority/rsag ride
#    the same check for free via the registry)
from repro.core import get_strategy, strategy_names

for strat in strategy_names():
    compare_tp(f"tp-equiv[{strat}]",
               lambda tp: dataclasses.replace(
                   mk_dense(tp),
                   depcha_in_scan=(get_strategy(strat).uses_in_scan
                                   and tp > 1)),
               strategy=strat)

# 2. hierarchical + compressed + ring reducers on real groups
compare_tp("tp-equiv[hierarchical]", mk_dense, reducer="hierarchical")
compare_tp("tp-equiv[compressed]", mk_dense, reducer="compressed",
           tol=5e-2, grad_tol=0.35)   # int8 wire: lossy by design
compare_tp("tp-equiv[ring]", mk_dense, reducer="ring",
           tol=3e-4, grad_tol=5e-3)   # ring hop order ≠ psum tree order

# 3. cross-strategy equality on the multi-device mesh
outs = {}
params8 = family_of(mk_dense(4)).init(jax.random.PRNGKey(1), mk_dense(1))
for strat in strategy_names():
    cfg = dataclasses.replace(
        mk_dense(4), depcha_in_scan=get_strategy(strat).uses_in_scan)
    _, g = loss_and_grads(cfg, mesh8, params8, strat)
    outs[strat] = g
ok = True
for strat in [s for s in strategy_names() if s != "funnel"]:
    for a, b in zip(jax.tree.leaves(outs["funnel"]),
                    jax.tree.leaves(outs[strat])):
        if np.max(np.abs(np.asarray(a, np.float32)
                         - np.asarray(b, np.float32))) > 1e-4:
            ok = False
check("strategies-identical-grads-8dev", ok)

# 4. ZeRO-1 on the StepProgram (DESIGN.md §9) at dp=2 × tp=4: the
#    scheduled per-bucket RS→UPDATE→AG program is bit-exact with the
#    monolithic zero1 optimizer, matches flat allreduce+update on the
#    SAME mesh, matches plain adamw at dp=1, rides the ring transport,
#    and clips via the scheduled NORM op exactly like
#    clip_by_global_norm does on the flat path.
from repro.optim import adamw, sgd, zero1
from repro.runtime import make_train_step
from repro.data import TokenPipeline


def one_step(mesh, cfg, *, mode, dp_size=1, clip_norm=0.0,
             strategy="concom", reducer="flat", verify=True):
    pipe = TokenPipeline(96, 32, 4, seed=3, mesh=mesh)
    params = family_of(cfg).init(jax.random.PRNGKey(2), mk_dense(1))
    b = pipe.batch_at(0)
    if mode == "flat":
        opt = adamw(1e-3)
        sync = GradSyncConfig(strategy=strategy, reducer=reducer,
                              bucket_bytes=1 << 12, verify=verify)
        ts = make_train_step(cfg, mesh, sync, opt, batch_like=b,
                             params_like=params, clip_norm=clip_norm)
    else:
        opt = zero1(adamw(1e-3), ("data",), dp_size)
        sync = GradSyncConfig(strategy=strategy, reducer=reducer,
                              bucket_bytes=1 << 12,
                              exclude_axes=("data",), verify=verify)
        ts = make_train_step(cfg, mesh, sync, opt, batch_like=b,
                             params_like=params, zero1_mode=True,
                             zero1_plan=mode, clip_norm=clip_norm)
    ps = jax.device_put(params, ts.shardings(ts.param_specs))
    p2, _, m = ts.fn(ps, ts.init_opt(), b, jnp.int32(0))
    return float(m["loss"]), p2, ts


def worst_diff(pa, pb):
    worst = 0.0
    for (n, a), (_, b) in zip(named_leaves(pa), named_leaves(pb)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.shape != b.shape:
            continue
        worst = max(worst, float(np.max(np.abs(a - b))))
    return worst


l_ref, p_ref, _ = one_step(mesh1, mk_dense(1), mode="flat")
l_s, p_s, ts_s = one_step(mesh8, mk_dense(4), mode="scheduled", dp_size=2)
l_m, p_m, _ = one_step(mesh8, mk_dense(4), mode="monolithic", dp_size=2)
l_f8, p_f8, _ = one_step(mesh8, mk_dense(4), mode="flat")

kinds = ts_s.gradsync.schedule.stats()["kinds"]
check("zero1-sched-ir-update-ops",
      kinds.get("update", 0) > 1
      and kinds.get("update") == kinds.get("all_gather"))
check("zero1-sched-multidev-loss", abs(l_ref - l_s) < 3e-4)
check("zero1-sched-multidev-params", worst_diff(p_ref, p_s) < 5e-4)
check("zero1-sched-equals-monolithic-bitexact",
      worst_diff(p_s, p_m) == 0.0)
check("zero1-sched-equals-flat-allreduce-update",
      worst_diff(p_s, p_f8) < 1e-5)

# rsag's two-phase base plan rewrites to the same triples: bit-exact
_, p_rsag, _ = one_step(mesh8, mk_dense(4), mode="scheduled", dp_size=2,
                        strategy="rsag")
check("zero1-sched-rsag-equals-concom", worst_diff(p_s, p_rsag) == 0.0)

# ring-family reducer: the zero1 RS/AG ops ride the chunked ring kernels
_, p_ring, _ = one_step(mesh8, mk_dense(4), mode="scheduled", dp_size=2,
                        reducer="ring")
check("zero1-sched-ring-transport", worst_diff(p_s, p_ring) < 5e-5)

# scheduled NORM clip ≡ clip_by_global_norm on the flat path (same mesh)
_, p_sc, _ = one_step(mesh8, mk_dense(4), mode="scheduled", dp_size=2,
                      clip_norm=0.05)
_, p_fc, _ = one_step(mesh8, mk_dense(4), mode="flat", clip_norm=0.05)
check("zero1-sched-clip-matches-flat-clip",
      worst_diff(p_sc, p_fc) < 1e-5)

# 5. FSDP (ZeRO-3 storage) one train step == plain, params compared
#    globally (device_get gathers the data-sharded weights)
def one_step_cfg(mesh, cfg):
    pipe = TokenPipeline(96, 32, 4, seed=4, mesh=mesh)
    params = family_of(cfg).init(jax.random.PRNGKey(2), mk_dense(1))
    b = pipe.batch_at(0)
    opt = adamw(1e-3)
    ts = make_train_step(cfg, mesh, GradSyncConfig(strategy="concom"),
                         opt, batch_like=b, params_like=params,
                         clip_norm=0)
    ps = jax.device_put(params, ts.shardings(ts.param_specs))
    p2, _, m = ts.fn(ps, ts.init_opt(), b, jnp.int32(0))
    return float(m["loss"]), jax.device_get(p2)


l_ref, p_ref = one_step_cfg(mesh1, mk_dense(1))
l_f, p_f = one_step_cfg(mesh8, dataclasses.replace(mk_dense(4), fsdp=True))
worst = 0.0
for (n, a), (_, b) in zip(named_leaves(p_ref), named_leaves(p_f)):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    worst = max(worst, float(np.max(np.abs(a - b))))
check("fsdp-onestep-loss", abs(l_ref - l_f) < 3e-4)
check("fsdp-onestep-params", worst < 5e-4)

# 6. hierarchical ≡ flat over REAL process groups: the 3-stage
#    RS(data)→AR(pod)→AG(data) path needs a pod axis, so re-mesh the 8
#    fake devices as 2×2×2 (pod, data, model) and compare both reducers
#    on rank-varying data (every rank contributes a different value).
from repro.core.buckets import Bucket, LeafInfo
from repro.core.strategies import make_reducer

mesh_pod = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
pod_shape = {"pod": 2, "data": 2, "model": 2}
N = 1024
base = jax.random.normal(jax.random.PRNGKey(7), (N,), jnp.float32)
bucket_pd = Bucket(
    leaves=(LeafInfo(name="x", index=0, shape=(N,), dtype=jnp.float32,
                     size=N),),
    reduce_axes=("pod", "data"), channel=0, bucket_id=0)


def _reduce_with(reducer_name):
    red = make_reducer(reducer_name, pod_shape, mean_axes=("pod", "data"))

    def body(x):
        rank = (jax.lax.axis_index("pod") * 2
                + jax.lax.axis_index("data")).astype(jnp.float32)
        return red(x * (1.0 + rank), bucket_pd)

    return jax.jit(lambda x: jax.shard_map(
        body, mesh=mesh_pod, in_specs=(P(),), out_specs=P(),
        check_vma=False)(x))(base)


flat_out = np.asarray(_reduce_with("flat"))
hier_out = np.asarray(_reduce_with("hierarchical"))
# mean over 4 DP ranks of (1+rank)·x = 2.5·x / ... both paths must agree
check("hier-matches-analytic",
      float(np.max(np.abs(flat_out - np.asarray(base) * 2.5))) < 1e-5)
check("hier-equals-flat-podmesh",
      float(np.max(np.abs(flat_out - hier_out))) < 1e-5)

ring_out = np.asarray(_reduce_with("ring"))
check("ring-equals-flat-podmesh",
      float(np.max(np.abs(flat_out - ring_out))) < 1e-5)
hier_ring_out = np.asarray(_reduce_with("hierarchical_ring"))
check("hier-ring-reducer-equals-flat-podmesh",
      float(np.max(np.abs(flat_out - hier_ring_out))) < 1e-5)

# 7. ring collectives ≡ psum_scatter / all_gather over a REAL 8-way ring
#    (rank-varying data; device r must own chunk r after RS, and the
#    bidirectional double-buffered variant must match the plain ring)
from repro.kernels.collectives.ops import (
    ring_all_gather,
    ring_allreduce,
    ring_reduce_scatter,
)

mesh_ring = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
ring_shape = {"data": 8}
M = 8 * 192
base_r = jax.random.normal(jax.random.PRNGKey(11), (M,), jnp.float32)


def _ring_vs_psum(bidirectional):
    def body(x):
        rank = jax.lax.axis_index("data").astype(jnp.float32)
        local = x * (1.0 + rank)
        rs_ring = ring_reduce_scatter(local, ("data",), ring_shape,
                                      bidirectional=bidirectional)
        rs_ref = jax.lax.psum_scatter(local, "data",
                                      scatter_dimension=0, tiled=True)
        ag_ring = ring_all_gather(rs_ref, ("data",), ring_shape,
                                  bidirectional=bidirectional)
        ag_ref = jax.lax.all_gather(rs_ref, "data", axis=0, tiled=True)
        ar_ring = ring_allreduce(local, ("data",), ring_shape,
                                 bidirectional=bidirectional)
        ar_ref = jax.lax.psum(local, ("data",))
        return rs_ring, rs_ref, ag_ring, ag_ref, ar_ring, ar_ref

    # per-device shard in, per-device shard out: compare on global views
    return jax.jit(lambda x: jax.shard_map(
        body, mesh=mesh_ring, in_specs=(P("data"),),
        out_specs=(P("data"),) * 6, check_vma=False)(x))(base_r)


for bidi in (False, True):
    tag = "bidi" if bidi else "uni"
    rs_ring, rs_ref, ag_ring, ag_ref, ar_ring, ar_ref = (
        np.asarray(v) for v in _ring_vs_psum(bidi))
    scale = float(np.max(np.abs(rs_ref))) + 1e-8
    check(f"ring-rs-equals-psum-scatter[{tag}]",
          float(np.max(np.abs(rs_ring - rs_ref))) / scale < 1e-6)
    check(f"ring-ag-equals-all-gather[{tag}]",
          float(np.max(np.abs(ag_ring - ag_ref))) < 1e-6 * scale)
    check(f"ring-allreduce-equals-psum[{tag}]",
          float(np.max(np.abs(ar_ring - ar_ref))) / scale < 1e-6)

# 8. hierarchical reducer with its fast-tier bulk bytes routed through
#    the ring kernels (use_ring) ≡ the psum_scatter/all_gather stages
from repro.core.hierarchical import hierarchical_allreduce


def _hier(use_ring):
    def body(x):
        rank = (jax.lax.axis_index("pod") * 2
                + jax.lax.axis_index("data")).astype(jnp.float32)
        return hierarchical_allreduce(
            x * (1.0 + rank), intra_axis="data", inter_axis="pod",
            intra_size=2, use_ring=use_ring)

    return jax.jit(lambda x: jax.shard_map(
        body, mesh=mesh_pod, in_specs=(P(),), out_specs=P(),
        check_vma=False)(x))(base)


check("hier-ring-equals-psum-stages",
      float(np.max(np.abs(np.asarray(_hier(True))
                          - np.asarray(_hier(False))))) < 1e-5)

# 9. compressed_ring ≡ compressed on a single-axis 8-ring: the int8
#    gather phase rides the ring all-gather — pure transport, so the
#    (lossy) values must match the lax.all_gather path bit-for-bit
from repro.core.strategies import make_reducer as _mk_red

big = jax.random.normal(jax.random.PRNGKey(13), (4096,), jnp.float32)
bucket_d8 = Bucket(
    leaves=(LeafInfo(name="c", index=0, shape=(4096,), dtype=jnp.float32,
                     size=4096),),
    reduce_axes=("data",), channel=0, bucket_id=0)


def _comp_with(name):
    red = _mk_red(name, {"data": 8}, mean_axes=("data",))

    def body(x):
        rank = jax.lax.axis_index("data").astype(jnp.float32)
        return red(x * (1.0 + rank), bucket_d8)

    return jax.jit(lambda x: jax.shard_map(
        body, mesh=mesh_ring, in_specs=(P(),), out_specs=P(),
        check_vma=False)(x))(big)


comp_out = np.asarray(_comp_with("compressed"))
comp_ring_out = np.asarray(_comp_with("compressed_ring"))
check("compressed-ring-equals-compressed",
      float(np.max(np.abs(comp_out - comp_ring_out))) == 0.0)

# 10. pipelined StepProgram (DESIGN.md §10) at dp=2 × tp=4: the
#     deferred plan (AGs detached into the next step's top, update
#     shards carried in opt_state["pending"]) is BIT-exact with the
#     scheduled plan over consecutive steps — the real all-gather
#     materializes the shards identically on both paths, so the carried
#     state (the easy thing to get wrong) is fully checked — and the
#     peeled-final-microbatch accumulation is bit-exact with the plain
#     scan while microbatch count leaves the trajectory unchanged.
pipe8 = TokenPipeline(96, 32, 8, seed=5, mesh=mesh8)


def run_steps(mode, n, *, clip_norm=0.0, microbatch=1,
              accum_overlap=True):
    cfg = mk_dense(4)
    params = family_of(cfg).init(jax.random.PRNGKey(2), mk_dense(1))
    b0 = pipe8.batch_at(0)
    if mode == "flat":
        # SGD: a microbatch count that scaled the gradient would scale
        # the update (Adam normalizes it away), and round-off from the
        # accumulation order is not amplified by lr/eps at near-zero
        # gradients as it is in Adam's first step
        opt = sgd(0.1)
        sync = GradSyncConfig(strategy="concom", bucket_bytes=1 << 12)
        ts = make_train_step(cfg, mesh8, sync, opt, batch_like=b0,
                             params_like=params, clip_norm=clip_norm,
                             microbatch=microbatch,
                             accum_overlap=accum_overlap)
    else:
        opt = zero1(adamw(1e-3), ("data",), 2)
        sync = GradSyncConfig(strategy="concom", bucket_bytes=1 << 12,
                              exclude_axes=("data",))
        ts = make_train_step(cfg, mesh8, sync, opt, batch_like=b0,
                             params_like=params, zero1_mode=True,
                             zero1_plan=mode, clip_norm=clip_norm,
                             microbatch=microbatch,
                             accum_overlap=accum_overlap)
    ps = jax.device_put(params, ts.shardings(ts.param_specs))
    st = ts.init_opt()
    m = None
    for k in range(n):
        ps, st, m = ts.fn(ps, st, pipe8.batch_at(k), jnp.int32(k))
    return ts, ps, st, m


ts_ds, p_ds, s_ds, m_ds = run_steps("deferred", 2)
_, p_ss, _, m_ss = run_steps("scheduled", 2)
check("pipelined-deferred-ir-phases",
      ts_ds.gradsync.schedule.phase_counts().get("pre", 0) > 1
      and ts_ds.gradsync.program.defer_ag)
check("pipelined-deferred-equals-scheduled-2steps-bitexact",
      worst_diff(ts_ds.finalize(p_ds, s_ds), p_ss) == 0.0)
ts_d3, p_d3, s_d3, _ = run_steps("deferred", 3)
_, p_s3, _, _ = run_steps("scheduled", 3)
check("pipelined-deferred-equals-scheduled-3steps-bitexact",
      worst_diff(ts_d3.finalize(p_d3, s_d3), p_s3) == 0.0)

# clipped: the NORM op stays in the POST program; the grad-norm metric
# and the clipped trajectory both survive the phase split
ts_dc, p_dc, s_dc, m_dc = run_steps("deferred", 2, clip_norm=0.05)
_, p_sc2, _, m_sc2 = run_steps("scheduled", 2, clip_norm=0.05)
check("pipelined-deferred-clip-bitexact",
      worst_diff(ts_dc.finalize(p_dc, s_dc), p_sc2) == 0.0
      and float(m_dc["grad_norm"]) == float(m_sc2["grad_norm"]))

# accumulation-overlapped (peeled final microbatch) ≡ plain scan, and
# microbatch count ≡ unsplit batch (normalization), on real dp groups.
# The peel preserves the exact accumulation order, but the inlined
# final backward compiles outside the scan body — under tp=4 XLA fuses
# its matmul/psum chain differently, so parity is float round-off
# (~1e-7 after 2 steps), not bit-level (it IS bit-exact at dp=1, see
# tests/test_pipelined.py).
_, p_ov, _, m_ov = run_steps("flat", 2, microbatch=4, accum_overlap=True)
_, p_pl, _, m_pl = run_steps("flat", 2, microbatch=4,
                             accum_overlap=False)
check("accum-overlap-equals-plain-scan",
      worst_diff(p_ov, p_pl) < 1e-5
      and float(m_ov["loss"]) == float(m_pl["loss"]))
_, p_m1, _, m_m1 = run_steps("flat", 2, microbatch=1)
check("accum-m4-equals-m1-trajectory",
      worst_diff(p_ov, p_m1) < 1e-5
      and abs(float(m_ov["loss"]) - float(m_m1["loss"])) < 1e-5)

# 11. static analyzer (DESIGN.md §11): the verify=True planning hook is
#     pure analysis over the IR — planning the dp=2 × tp=4 deferred
#     StepProgram with verification on is bit-exact with verification
#     off (every other GradSync in this file already planned with
#     verify=True, the default, so the analyzer blessed all of them)
_, p_von, _ = one_step(mesh8, mk_dense(4), mode="deferred", dp_size=2,
                       verify=True)
_, p_voff, _ = one_step(mesh8, mk_dense(4), mode="deferred", dp_size=2,
                        verify=False)
check("analysis-verify-planning-bitexact",
      worst_diff(p_von, p_voff) == 0.0)

# 12. measured per-op replay (DESIGN.md §12) on the real 2×4 mesh: the
#     one-op-per-dispatch replay must be BIT-exact with the single
#     shard_map program (profile-on ≡ profile-off) and emit exactly one
#     measured OpEvent per IR op.
from repro.obs.cli import build_setup
from repro.obs.measure import measured_gradsync

for strat in ("concom", "rsag"):
    gs_o, grads_o = build_setup(strat, "flat", 64)
    pspecs_o = gs_o.param_specs
    flat_g, gdef = jax.tree_util.tree_flatten(grads_o)
    flat_s = jax.tree_util.tree_leaves(
        pspecs_o, is_leaf=lambda x: isinstance(x, P))
    gput = jax.tree_util.tree_unflatten(gdef, [
        jax.device_put(g, NamedSharding(gs_o.mesh, s))
        for g, s in zip(flat_g, flat_s)])
    ref = jax.jit(lambda g, _gs=gs_o, _ps=pspecs_o: jax.shard_map(
        _gs, mesh=_gs.mesh, in_specs=(_ps,), out_specs=_ps,
        check_vma=False)(g))(gput)
    out_m, tl_m, _ = measured_gradsync(gs_o, grads_o, reps=1)
    check(f"obs-measured-opcount[{strat}]",
          len(tl_m.events) == len(gs_o.schedule.ops) > 0)
    check(f"obs-measured-equals-execute-bitexact[{strat}]",
          worst_diff(out_m, ref) == 0.0)
    check(f"obs-measured-serial-clock[{strat}]",
          abs(tl_m.step_time - sum(e.duration for e in tl_m.events))
          < 1e-9)

# 13. continuous-batching serving (DESIGN.md §14) at dp=2 × tp=4: the
#     paged engine must match the static path bit-for-bit under greedy
#     on real process groups (vocab sharded over tp=4, slots over dp=2),
#     and the vocab-sharded samplers must keep their tie-break and
#     per-request seed contracts across shards.
from repro.runtime import (ContinuousScheduler, SamplingParams, Server,
                           sharded_argmax, sharded_sample)

mk_serve = lambda: tf.TransformerConfig(
    name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=4, d_ff=128,
    vocab=96, tp=4, attn_chunk=16, dtype=jnp.float32)

# sharded_argmax tie-break: equal maxima on shards 1 and 3 → the LOWEST
# shard (and lowest index within it) must win, deterministically
_v_local = 96 // 4
_tie = np.full((2, 96), -5.0, np.float32)
_tie[:, 1 * _v_local + 3] = 7.0          # shard 1, local index 3
_tie[:, 3 * _v_local + 0] = 7.0          # shard 3, local index 0
_tie[0, 1 * _v_local + 5] = 7.0          # row 0: another tie inside shard 1


def _run_argmax(logits):
    return jax.jit(lambda l: jax.shard_map(
        lambda x: sharded_argmax(x, 4), mesh=mesh8,
        in_specs=(P(None, "model"),), out_specs=P(),
        check_vma=False)(l))(jnp.asarray(logits))


_am = np.asarray(_run_argmax(_tie))
check("serve-argmax-tiebreak-lowest-shard",
      _am[0] == 1 * _v_local + 3 and _am[1] == 1 * _v_local + 3)

# sharded_sample at temperature 0 ≡ sharded_argmax (ties included)
_rng_s = np.random.default_rng(3)
_rand = _rng_s.normal(size=(4, 96)).astype(np.float32)
_rand[2] = _tie[0, :]                      # one all-tied row in the batch


def _run_sample(logits, temps, topks, topps, seeds):
    def body(l, t, k, p, s):
        keys = jax.vmap(jax.random.PRNGKey)(s)
        return sharded_sample(l, 4, keys, t, k, p)
    return jax.jit(lambda *a: jax.shard_map(
        body, mesh=mesh8, in_specs=(P(None, "model"),) + (P(),) * 4,
        out_specs=P(), check_vma=False)(*a))(
        jnp.asarray(logits), jnp.asarray(temps), jnp.asarray(topks),
        jnp.asarray(topps), jnp.asarray(seeds))


_z4 = np.zeros(4, np.float32)
_s0 = _run_sample(_rand, _z4, np.zeros(4, np.int32), np.ones(4, np.float32),
                  np.arange(4, dtype=np.uint32))
check("serve-sample-temp0-equals-argmax",
      np.array_equal(np.asarray(_s0), np.asarray(_run_argmax(_rand))))

# the paged continuous-batching engine vs the static Server, end to end
scfg = mk_serve()
sparams = family_of(scfg).init(jax.random.PRNGKey(7), scfg)
srv8 = Server(scfg, mesh8, sparams, max_len=64)
eng8 = ContinuousScheduler(srv8, slots=8, block_size=16, chunk=4)

_rng_p = np.random.default_rng(11)
sprompts = [_rng_p.integers(1, 96, size=int(L)).astype(np.int32)
            for L in (5, 12, 17, 3, 30, 9)]
souts = eng8.generate_batch(sprompts, 10)
_exact = all(
    np.array_equal(srv8.generate(np.tile(p[None], (2, 1)), 10)[0], o)
    for p, o in zip(sprompts, souts))
check("serve-paged-greedy-bitexact-vs-static", _exact)

ssp = SamplingParams(temperature=0.8, top_k=8, seed=42)
sa = eng8.generate_batch(sprompts[:3], 10, ssp)
sb = eng8.generate_batch(sprompts[:3], 10, ssp)
check("serve-sample-seed-reproducible",
      all(np.array_equal(x, y) for x, y in zip(sa, sb)))
sc = eng8.generate_batch(sprompts[:3], 10,
                         SamplingParams(temperature=0.8, top_k=8, seed=9))
check("serve-sample-seed-differs",
      any(not np.array_equal(x, y) for x, y in zip(sa, sc)))
sk1 = eng8.generate_batch(sprompts[:3], 10,
                          SamplingParams(temperature=0.9, top_k=1, seed=3))
check("serve-sample-topk1-equals-greedy",
      all(np.array_equal(x, y) for x, y in zip(sk1, souts[:3])))

# 14. pipeline parallelism (DESIGN.md §15) at dp=2 × stage=2 × tp=2:
#     the staged wave pipeline over real stage process groups must match
#     the stage=1 reference BIT-exactly — GPipe at any M (same reverse-
#     wave accumulation order; warmup/drain garbage dies in exact-zero
#     where-mask cotangents), 1F1B at M == S (its single chunk IS the
#     GPipe wave).  Chunked 1F1B at M > S re-associates the chunk sum
#     (float round-off, like the §10 accum peel), as does the clip
#     norm's interaction with adamw's compiled update — both held to
#     loose tolerance instead.
from repro.launch.mesh import make_smoke_mesh
from repro.sim.autotune import choose_pp_schedule

mesh_pp2 = make_smoke_mesh(2, 2, stage=2)   # dp2 × stage2 × tp2
mesh_pp1 = make_smoke_mesh(2, 2, stage=1)   # the staged S=1 reference

mk_pp = lambda: tf.TransformerConfig(
    name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=2,
    d_ff=128, vocab=96, tp=2, attn_chunk=16, dtype=jnp.float32)


def pp_steps(mesh, stage, schedule, microbatch, n=2, clip=0.0):
    cfg = mk_pp()
    params = family_of(cfg).init(jax.random.PRNGKey(2), cfg)
    pipe = TokenPipeline(96, 32, 8, seed=5, mesh=mesh)
    sync = GradSyncConfig(strategy="concom", bucket_bytes=1 << 12)
    ts = make_train_step(cfg, mesh, sync, adamw(1e-3),
                         batch_like=pipe.batch_at(0), params_like=params,
                         clip_norm=clip, microbatch=microbatch,
                         pp_stages=stage, pp_schedule=schedule)
    ps = jax.device_put(params, ts.shardings(ts.param_specs))
    st = ts.init_opt()
    ms = []
    for k in range(n):
        ps, st, m = ts.fn(ps, st, pipe.batch_at(k), jnp.int32(k))
        ms.append(m)
    return ps, ms


pg2, (*_, mg2) = pp_steps(mesh_pp2, 2, "gpipe", 4)
pg1, (*_, mg1) = pp_steps(mesh_pp1, 1, "gpipe", 4)
check("pp-gpipe-bitexact-vs-stage1",
      worst_diff(pg2, pg1) == 0.0
      and float(mg2["loss"]) == float(mg1["loss"]))

# 1f1b at M == S: one chunk of S microbatches == the GPipe wave program
pf2, _ = pp_steps(mesh_pp2, 2, "1f1b", 2)
pw1, _ = pp_steps(mesh_pp1, 1, "gpipe", 2)
check("pp-1f1b-m-eq-s-bitexact-vs-stage1", worst_diff(pf2, pw1) == 0.0)

# chunked 1f1b at M > S: chunk-sum re-association only (round-off)
pf4, _ = pp_steps(mesh_pp2, 2, "1f1b", 4)
pf4r, _ = pp_steps(mesh_pp1, 1, "1f1b", 4)
check("pp-1f1b-m4-close-vs-stage1", worst_diff(pf4, pf4r) < 1e-5)

# clipped: the first step's gnorm (same params on both stagings) is
# bit-identical (per-leaf psum in the same layer order); the clip×adamw
# fusion is float round-off, so later steps' gnorms are held only to the
# params' closeness
pc2, (mc2, _) = pp_steps(mesh_pp2, 2, "gpipe", 4, clip=0.05)
pc1, (mc1, _) = pp_steps(mesh_pp1, 1, "gpipe", 4, clip=0.05)
check("pp-clip-gnorm-bitexact",
      float(mc2["grad_norm"]) == float(mc1["grad_norm"]))
check("pp-clip-close-vs-stage1", worst_diff(pc2, pc1) < 1e-5)

# staged S=1 vs the plain (no stage axis) accumulation path: same math,
# different program shape — float round-off closeness
mesh_pp0 = make_smoke_mesh(2, 2)
cfg_pl = mk_pp()
params_pl = family_of(cfg_pl).init(jax.random.PRNGKey(2), cfg_pl)
pipe_pl = TokenPipeline(96, 32, 8, seed=5, mesh=mesh_pp0)
ts_pl = make_train_step(
    cfg_pl, mesh_pp0, GradSyncConfig(strategy="concom",
                                     bucket_bytes=1 << 12),
    adamw(1e-3), batch_like=pipe_pl.batch_at(0), params_like=params_pl,
    clip_norm=0.0, microbatch=4)
pp_pl = jax.device_put(params_pl, ts_pl.shardings(ts_pl.param_specs))
st_pl = ts_pl.init_opt()
for k in range(2):
    pp_pl, st_pl, _ = ts_pl.fn(pp_pl, st_pl, pipe_pl.batch_at(k),
                               jnp.int32(k))
check("pp-staged-ref-close-vs-plain-accum",
      worst_diff(pg1, pp_pl) < 1e-4)

# auto resolves to a fixed schedule before compile and matches that
# fixed schedule's trajectory bit-for-bit
pick = choose_pp_schedule(2, 4)
pa2, _ = pp_steps(mesh_pp2, 2, "auto", 4)
pfix, _ = pp_steps(mesh_pp2, 2, pick, 4)
check("pp-auto-equals-resolved-fixed-bitexact",
      worst_diff(pa2, pfix) == 0.0)

print("DONE", flush=True)
