"""Pipelined StepProgram (DESIGN.md §10): deferred all-gathers crossing
into the next step + sync overlapping the accumulation tail.

Three layers of checks, mirroring tests/test_stepprogram.py:
  - pure-IR phase-split properties (microseconds, no devices);
  - simulator semantics: the steady-state pipelined timeline hides the
    PRE gathers under the next forward (deferred exposed comm strictly
    below the same-step zero1 plan), and the accumulation compute model
    places releases only in the final microbatch's backward;
  - executable parity on the smoke mesh (dp=1): deferred ≡ scheduled
    across consecutive steps (tight tolerance — with dp=1 the elided
    all-gather lets XLA contract the update math into the apply-add,
    a 1-ulp artifact; tests/_mdworker.py asserts BIT-exactness on real
    dp=2 × tp=4 groups where the gather materializes the shards), and
    microbatch=1 ≡ microbatch=M training (the grad-accumulation
    normalization fix) with the peeled final microbatch bit-exact
    against the plain scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.sim  # noqa: F401  (registers the "auto" strategy)
from repro.core.buckets import Bucket, BucketPlan, LeafInfo
from repro.core.registry import fixed_strategy_names, get_strategy
from repro.core.schedule import ALL_GATHER, POST, PRE
from repro.core.stepprogram import zero1_schedule
from repro.sim import (
    ComputeModel,
    PipelinedTimeline,
    rank_step_plans,
    simulate_pipelined,
)

MESH = {"data": 8, "model": 1}
COMPUTE = ComputeModel(t_fwd=1e-4, t_bwd=2e-4, n_stages=8)


def _plan(n_buckets=8, num_channels=4, elems=1 << 20):
    buckets = []
    for bid in range(n_buckets):
        leaves = (LeafInfo(name=f"g{bid}", index=bid, shape=(elems,),
                           dtype=jnp.float32, size=elems),)
        buckets.append(Bucket(leaves=leaves, reduce_axes=("data",),
                              channel=bid % num_channels, bucket_id=bid,
                              comm_dtype=jnp.float32))
    return BucketPlan(buckets=tuple(buckets), treedef=None,
                      num_leaves=n_buckets, comm_dtype=jnp.float32)


# ---------------------------------------------------------- IR phases

def test_defer_ag_tags_only_all_gathers_pre():
    plan = _plan()
    for name in fixed_strategy_names():
        base = get_strategy(name).plan(plan)
        zd = zero1_schedule(base, dp_axes=("data",), clip=True,
                            defer_ag=True)
        assert zd.validate() is zd
        for op in zd.ops:
            want = PRE if op.kind == ALL_GATHER else POST
            assert op.phase == want, (name, op.kind)
        n = len(plan.buckets)
        assert zd.phase_counts() == {POST: 2 * n + 1, PRE: n}, name
        # every dp bucket's payload crosses the boundary, at f32 wire
        assert zd.deferred_bytes() == sum(
            b.size * 4 for b in plan.buckets), name
        # without the flag nothing is deferred
        zs = zero1_schedule(base, dp_axes=("data",), clip=True)
        assert zs.phase_counts() == {POST: 3 * n + 1}, name
        assert zs.deferred_bytes() == 0, name


def test_split_phases_reroots_pre_ops():
    plan = _plan()
    zd = zero1_schedule(get_strategy("concom").plan(plan),
                        dp_axes=("data",), defer_ag=True)
    post, pre = zd.split_phases()
    assert post.validate() is post and pre.validate() is pre
    n = len(plan.buckets)
    assert len(post.ops) == 2 * n and len(pre.ops) == n
    assert all(op.kind != ALL_GATHER for op in post.ops)
    # the PRE gathers lost their UPDATE deps (those ran LAST step —
    # the shards arrive as carried state) and free-fly
    assert all(op.kind == ALL_GATHER and op.depends_on == ()
               for op in pre.ops)
    # op ids survive the split: the two halves partition the program
    assert ({op.op_id for op in post.ops} | {op.op_id for op in pre.ops}
            == {op.op_id for op in zd.ops})


def test_build_step_program_deferred_keeps_sync_post(smoke_mesh):
    from jax.sharding import PartitionSpec as P

    from repro.core import GradSync, GradSyncConfig

    grads = {"w": jnp.ones((64, 8)), "b": jnp.ones((8,))}
    specs = jax.tree.map(lambda _: P(), grads)
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), grads)
    gs = GradSync(
        GradSyncConfig(strategy="concom", bucket_bytes=256,
                       exclude_axes=("data",), zero1_dp_axes=("data",),
                       zero1_defer_ag=True),
        smoke_mesh, specs, sds)
    assert gs.program is not None and gs.program.defer_ag
    pre = gs.program.pre_schedule()
    post = gs.program.post_schedule()
    assert len(pre.ops) == len(gs.dp_plan.buckets)
    assert all(op.kind == ALL_GATHER for op in pre.ops)
    # the model-axis sync ops all stay in the POST half
    assert {op.op_id for op in post.ops} >= set(
        range(gs.program.num_sync_ops))


# ------------------------------------------------------------ simulator

def test_simulate_pipelined_deterministic_and_complete():
    plan = _plan()
    zd = zero1_schedule(get_strategy("concom").plan(plan),
                        dp_axes=("data",), defer_ag=True)
    post, pre = zd.split_phases()
    a = simulate_pipelined(post, pre, MESH, compute=COMPUTE)
    b = simulate_pipelined(post, pre, MESH, compute=COMPUTE)
    assert a == b
    assert isinstance(a, PipelinedTimeline)
    assert len(a.events) == len(zd.ops)
    # PRE gathers are released at t=0 — the step's head, not its tail
    ag_starts = [e.start for e in a.events if e.kind == ALL_GATHER]
    assert min(ag_starts) == 0.0


def test_deferred_exposes_strictly_less_than_zero1():
    """The acceptance bar: per strategy, the pipelined plan's exposed
    comm is strictly below the same-step zero1 plan's (the AG tail
    moved under the next forward), and the best plan overall is a
    deferred one on this comm-heavy cell."""
    plan = _plan(n_buckets=8, num_channels=4)
    ranked = rank_step_plans(plan, MESH, dp_axes=("data",),
                             compute=COMPUTE)
    by = dict(ranked)
    names = {n.split(":")[0] for n in by}
    assert names == {"deferred", "zero1", "flat"}
    best_z = min(v.exposed_comm for k, v in by.items()
                 if k.startswith("zero1:"))
    best_d = min(v.exposed_comm for k, v in by.items()
                 if k.startswith("deferred:"))
    assert best_d < best_z
    for s in ("concom", "rsag", "depcha"):
        assert by[f"deferred:{s}"].exposed_comm \
            < by[f"zero1:{s}"].exposed_comm, s
        assert by[f"deferred:{s}"].step_time \
            <= by[f"zero1:{s}"].step_time, s


def test_pre_gathers_outrunning_the_forward_push_the_step():
    plan = _plan(n_buckets=8, num_channels=4)
    zd = zero1_schedule(get_strategy("concom").plan(plan),
                        dp_axes=("data",), defer_ag=True)
    post, pre = zd.split_phases()
    wide = simulate_pipelined(post, pre, MESH, compute=COMPUTE,
                              pre_window=1.0)      # fully hidden
    tight = simulate_pipelined(post, pre, MESH, compute=COMPUTE,
                               pre_window=0.0)     # fully exposed
    assert tight.step_time > wide.step_time
    # the push is exactly the un-hidden PRE makespan
    pre_end = max(e.end for e in tight.events if e.kind == ALL_GATHER
                  and e.release == 0.0)
    assert tight.t_fwd == pytest.approx(COMPUTE.t_fwd + pre_end)


def test_with_accum_places_releases_in_final_microbatch():
    micro = ComputeModel(t_fwd=1e-4, t_bwd=2e-4, n_stages=4)
    m4 = micro.with_accum(4)
    # total compute = 4 microbatches; head = 3 full microbatches + fwd
    assert m4.end == pytest.approx(4 * micro.end)
    assert m4.t_fwd == pytest.approx(3 * micro.end + micro.t_fwd)
    assert m4.t_bwd == pytest.approx(micro.t_bwd)
    sizes = [(0, 100), (1, 100)]
    rel = m4.bucket_release_times(sizes)
    # releases live inside the FINAL microbatch's backward window
    assert all(m4.t_fwd < t <= m4.end + 1e-15 for t in rel.values())
    # plain scan: everything releases at the very end
    flat = micro.with_accum(4, overlap_tail=False)
    rel_f = flat.bucket_release_times(sizes)
    assert all(t == pytest.approx(4 * micro.end) for t in rel_f.values())
    assert micro.with_accum(1) is micro


def test_rank_step_plans_accum_scales_step_time():
    plan = _plan(n_buckets=4)
    r1 = dict(rank_step_plans(plan, MESH, dp_axes=("data",),
                              compute=COMPUTE))
    r4 = dict(rank_step_plans(plan, MESH, dp_axes=("data",),
                              compute=COMPUTE, accum=4))
    for k in r1:
        assert r4[k].step_time > r1[k].step_time, k
        # the extra time is compute (the 3 head microbatches), not comm
        assert r4[k].total_comm == pytest.approx(r1[k].total_comm), k


# ------------------------------------------------- executable parity

@pytest.fixture(scope="module")
def pipe_setup(smoke_mesh):
    from repro.data import TokenPipeline
    from repro.models import transformer as tf

    cfg = tf.TransformerConfig(
        name="pipelined", n_layers=2, d_model=32, n_heads=4, kv_heads=2,
        d_ff=64, vocab=64, tp=1, attn_chunk=16, dtype=jnp.float32)
    pipe = TokenPipeline(64, 16, 4, seed=7, mesh=smoke_mesh)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, pipe, params


def _make_step(cfg, pipe, params, mesh, *, mode=None, microbatch=1,
               accum_overlap=True, clip_norm=0.0, inner=None):
    from repro.core import GradSyncConfig
    from repro.optim import adamw, zero1
    from repro.runtime import make_train_step

    batch = pipe.batch_at(0)
    if mode is None:        # plain adamw (no zero1)
        return make_train_step(
            cfg, mesh,
            GradSyncConfig(strategy="concom", bucket_bytes=1 << 14),
            adamw(1e-3), batch_like=batch, params_like=params,
            microbatch=microbatch, accum_overlap=accum_overlap,
            clip_norm=clip_norm)
    opt = zero1(inner or adamw(1e-3), ("data",), 1)
    return make_train_step(
        cfg, mesh,
        GradSyncConfig(strategy="concom", bucket_bytes=1 << 14,
                       exclude_axes=("data",)),
        opt, batch_like=batch, params_like=params, zero1_mode=True,
        zero1_plan=mode, microbatch=microbatch,
        accum_overlap=accum_overlap, clip_norm=clip_norm)


def _run(ts, pipe, params, n_steps):
    p, s = params, ts.init_opt()
    m = None
    for k in range(n_steps):
        p, s, m = ts.fn(p, s, pipe.batch_at(k), jnp.int32(k))
    return p, s, m


def _max_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_deferred_carries_pending_state(pipe_setup, smoke_mesh):
    cfg, pipe, params = pipe_setup
    ts = _make_step(cfg, pipe, params, smoke_mesh, mode="deferred")
    state = ts.init_opt()
    assert "pending" in state
    assert set(state["pending"]) == set(state["inner"])
    # zero-initialized carry: gathering it is the identity update
    assert all(float(jnp.max(jnp.abs(v))) == 0.0
               for v in jax.tree.leaves(state["pending"]))
    p, s, _ = _run(ts, pipe, params, 1)
    # after one step the carry holds real (nonzero) update shards and
    # the params are still untouched-by-step-0's update until finalize
    assert any(float(jnp.max(jnp.abs(v))) > 0.0
               for v in jax.tree.leaves(s["pending"]))
    assert ts.finalize is not None
    assert _max_diff(ts.finalize(p, s), p) > 0.0


def test_deferred_matches_scheduled_across_steps(pipe_setup, smoke_mesh):
    cfg, pipe, params = pipe_setup
    ts_s = _make_step(cfg, pipe, params, smoke_mesh, mode="scheduled")
    ts_d = _make_step(cfg, pipe, params, smoke_mesh, mode="deferred")
    p_s, s_s = params, ts_s.init_opt()
    p_d, s_d = params, ts_d.init_opt()
    for k in range(3):
        p_s, s_s, m_s = ts_s.fn(p_s, s_s, pipe.batch_at(k), jnp.int32(k))
        p_d, s_d, m_d = ts_d.fn(p_d, s_d, pipe.batch_at(k), jnp.int32(k))
        # the optimizer moments track the same trajectory: the carried
        # shards feed the SAME update math one boundary later (the tiny
        # dp=1 drift is the same contraction artifact as below)
        assert _max_diff(s_s["inner"], s_d["inner"]) < 1e-6, k
        # params agree once the pending gathers are flushed (dp=1 ulp
        # tolerance: the elided AG lets XLA contract update into apply;
        # _mdworker asserts == 0.0 on real dp=2 groups)
        assert _max_diff(p_s, ts_d.finalize(p_d, s_d)) < 1e-6, k
        assert abs(float(m_s["grad_norm"])
                   - float(m_d["grad_norm"])) < 1e-6, k


def test_deferred_clip_matches_scheduled_clip(pipe_setup, smoke_mesh):
    from repro.optim import sgd

    cfg, pipe, params = pipe_setup
    clip = 0.05                              # small enough to bind
    # SGD, not Adam: the clipped update is then proportional to the clip
    # scale (Adam normalizes the scale away), and a 1-ulp param
    # difference is not amplified by lr/eps at near-zero gradients
    ts_s = _make_step(cfg, pipe, params, smoke_mesh, mode="scheduled",
                      clip_norm=clip, inner=sgd(0.1))
    ts_d = _make_step(cfg, pipe, params, smoke_mesh, mode="deferred",
                      clip_norm=clip, inner=sgd(0.1))
    p_s, _, m_s = _run(ts_s, pipe, params, 2)
    p_d, s_d, m_d = _run(ts_d, pipe, params, 2)
    assert float(m_s["grad_norm"]) > clip    # the clip actually engaged
    assert abs(float(m_s["grad_norm"]) - float(m_d["grad_norm"])) < 1e-6
    assert _max_diff(p_s, ts_d.finalize(p_d, s_d)) < 1e-6


def test_microbatch_count_does_not_scale_training(pipe_setup, smoke_mesh):
    """The grad-accumulation normalization: same global batch split
    M ways trains the same trajectory (loss and params), to float
    round-off — the scan accumulates means, not sums."""
    cfg, pipe, params = pipe_setup
    ts1 = _make_step(cfg, pipe, params, smoke_mesh, microbatch=1)
    ts4 = _make_step(cfg, pipe, params, smoke_mesh, microbatch=4)
    p1, s1 = params, ts1.init_opt()
    p4, s4 = params, ts4.init_opt()
    for k in range(2):
        p1, s1, m1 = ts1.fn(p1, s1, pipe.batch_at(k), jnp.int32(k))
        p4, s4, m4 = ts4.fn(p4, s4, pipe.batch_at(k), jnp.int32(k))
        assert float(m1["loss"]) == pytest.approx(
            float(m4["loss"]), rel=1e-6), k
        assert float(m1["grad_norm"]) == pytest.approx(
            float(m4["grad_norm"]), rel=1e-5), k
        assert _max_diff(p1, p4) < 1e-6, k


def test_peeled_final_microbatch_is_bit_exact(pipe_setup, smoke_mesh):
    """Peeling the last microbatch out of the scan keeps the exact
    accumulation order — overlapped and plain paths are bit-identical."""
    cfg, pipe, params = pipe_setup
    ts_o = _make_step(cfg, pipe, params, smoke_mesh, microbatch=4,
                      accum_overlap=True)
    ts_p = _make_step(cfg, pipe, params, smoke_mesh, microbatch=4,
                      accum_overlap=False)
    p_o, _, m_o = _run(ts_o, pipe, params, 2)
    p_p, _, m_p = _run(ts_p, pipe, params, 2)
    assert float(m_o["loss"]) == float(m_p["loss"])
    assert _max_diff(p_o, p_p) == 0.0


def test_deferred_with_accumulation(pipe_setup, smoke_mesh):
    """Both boundaries crossed at once: deferred AGs + peeled
    accumulation tail still track the scheduled plain-scan step."""
    cfg, pipe, params = pipe_setup
    ts_s = _make_step(cfg, pipe, params, smoke_mesh, mode="scheduled",
                      microbatch=2, accum_overlap=False)
    ts_d = _make_step(cfg, pipe, params, smoke_mesh, mode="deferred",
                      microbatch=2, accum_overlap=True)
    p_s, _, _ = _run(ts_s, pipe, params, 2)
    p_d, s_d, _ = _run(ts_d, pipe, params, 2)
    assert _max_diff(p_s, ts_d.finalize(p_d, s_d)) < 1e-6
