"""Pipeline schedules as CommSchedule programs (DESIGN.md §15).

Plan-shape and costing tests run in-process (pure IR, no devices); the
SEND/RECV emitter's executed semantics need >1 device and run in a
subprocess.  Property tests ride Hypothesis when it is installed.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.pipeline_program import (
    PipelinePlan,
    SCHEDULES,
    Slot,
    bucket_stage_map,
    compose_step,
    max_in_flight,
    plan_pipeline,
)
from repro.core.schedule import RECV, SEND
from repro.sim.autotune import choose_pp_schedule
from repro.sim.compute import ComputeModel, pipeline_timeline

CM = ComputeModel(t_fwd=1.0, t_bwd=2.0)


def n_boundary_ops(S_tot, M):
    # per phase: (S_tot - 1) crossings per microbatch, SEND + RECV each
    return 2 * 2 * (S_tot - 1) * M


@pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
@pytest.mark.parametrize("S,M", [(2, 2), (2, 4), (4, 8)])
def test_plan_shape(kind, S, M):
    plan = plan_pipeline(S, M, kind=kind, activation_bytes=1 << 10)
    ops = plan.schedule.ops
    assert len(ops) == n_boundary_ops(S, M)
    sends = [o for o in ops if o.kind == SEND]
    recvs = [o for o in ops if o.kind == RECV]
    assert len(sends) == len(recvs) == len(ops) // 2
    # every RECV pairs with exactly one SEND: same bucket, SEND in deps
    by_bucket = {o.bucket.bucket_id: o for o in sends}
    for r in recvs:
        s = by_bucket[r.bucket.bucket_id]
        assert s.op_id in r.depends_on
        assert r.shift == s.shift
    # activations ride +1, cotangents -1
    shifts = {plan.op_slot[o.op_id][1].phase: o.shift for o in ops}
    assert shifts["F"] == 1 and shifts["B"] == -1


def test_interleaved_plan():
    plan = plan_pipeline(2, 8, kind="interleaved", virtual=2,
                         activation_bytes=1 << 10)
    assert plan.total_stages == 4
    assert len(plan.schedule.ops) == n_boundary_ops(4, 8)
    # device of global stage g is g % S: stage 2 lives on device 0
    devs = {s.stage: d for d, s in plan.commits}
    assert devs[0] == devs[2] == 0 and devs[1] == devs[3] == 1


def test_plan_rejects_bad_args():
    with pytest.raises(ValueError):
        plan_pipeline(0, 4, activation_bytes=0)
    with pytest.raises(ValueError):
        plan_pipeline(2, 0, activation_bytes=0)
    with pytest.raises(ValueError):
        plan_pipeline(2, 4, kind="gpipe", virtual=2, activation_bytes=0)
    with pytest.raises(ValueError):
        plan_pipeline(2, 4, kind="wavefront", activation_bytes=0)
    with pytest.raises(ValueError):
        plan_pipeline(2, 4, kind="1f1b", virtual=2, activation_bytes=0)


def test_1f1b_in_flight_bound():
    for S, M in [(2, 4), (4, 8), (3, 9)]:
        plan = plan_pipeline(S, M, kind="1f1b", activation_bytes=1 << 10)
        assert max_in_flight(plan) <= S
        gp = plan_pipeline(S, M, kind="gpipe", activation_bytes=1 << 10)
        assert max_in_flight(gp) == M   # gpipe flushes everything


def test_gpipe_bubble_closed_form():
    for S, M in [(2, 2), (2, 8), (4, 4), (4, 16)]:
        plan = plan_pipeline(S, M, kind="gpipe", activation_bytes=1 << 10)
        tl = pipeline_timeline(plan, CM, wire_time=0.0)
        assert tl.bubble_fraction == pytest.approx((S - 1) / (M + S - 1))


def test_1f1b_beats_gpipe_wall():
    for S, M in [(2, 2), (2, 8), (4, 8)]:
        walls = {}
        for kind in ("gpipe", "1f1b"):
            plan = plan_pipeline(S, M, kind=kind,
                                 activation_bytes=1 << 20)
            walls[kind] = pipeline_timeline(plan, CM, wire_time=0.3).wall
        assert walls["1f1b"] < walls["gpipe"]


def test_choose_pp_schedule_never_worse_than_fixed():
    for S, M in [(2, 2), (2, 8), (4, 8)]:
        pick = choose_pp_schedule(S, M, activation_bytes=1 << 20)
        assert pick in SCHEDULES

        def wall(kind):
            plan = plan_pipeline(S, M, kind=kind,
                                 activation_bytes=1 << 20)
            return pipeline_timeline(plan, CM, wire_time=0.0).wall

        # at wire 0 the analytic walls rank the same way the chooser
        # saw them (same cost model): the pick's wall is the min
        walls = {k: wall(k) for k in ("gpipe", "1f1b")}
        assert walls[pick] == min(walls.values())


def test_compose_step_releases_buckets_by_stage():
    from repro.core.buckets import Bucket, LeafInfo
    from repro.core.schedule import CollectiveOp, CommSchedule, ALLREDUCE

    pp = plan_pipeline(2, 4, kind="1f1b", activation_bytes=1 << 10)
    mk = lambda bid, oid, deps: CollectiveOp(
        op_id=oid, bucket=Bucket(
            leaves=(LeafInfo(name=f"b{bid}", index=0, shape=(8,),
                             dtype=np.float32, size=8),),
            reduce_axes=("data",), channel=0, bucket_id=bid),
        chain=0, depends_on=deps, kind=ALLREDUCE)
    sync = CommSchedule((mk(0, 0, ()), mk(1, 1, (0,))))
    joint, id_map = compose_step(pp, sync)
    off = len(pp.schedule.ops)
    assert id_map == {0: off, 1: off + 1}
    smap = bucket_stage_map(pp, sync)
    # bucket 0 = output-side = LAST stage (first to drain under 1f1b)
    assert smap[0] == 1 and smap[1] == 0
    for op in joint.ops[off:]:
        rel = pp.final_backward_op(smap[op.bucket.bucket_id])
        assert rel in op.depends_on


def test_timeline_release_times_cover_all_ops():
    plan = plan_pipeline(2, 4, kind="1f1b", activation_bytes=1 << 10)
    tl = pipeline_timeline(plan, CM, wire_time=0.1)
    assert set(tl.op_release) == {o.op_id for o in plan.schedule.ops}
    assert tl.wall >= tl.fwd_wall > 0
    assert len(tl.stage_grad_release) == plan.total_stages
    # gradients drain in reverse stage order under 1f1b: stage 1's last
    # backward retires before stage 0's
    assert tl.stage_grad_release[1] < tl.stage_grad_release[0]


# --- executed SEND/RECV semantics (subprocess: needs 2 devices) -------

WORKER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.core.buckets import Bucket, BucketPlan, LeafInfo
from repro.core.schedule import (CollectiveOp, CommSchedule, RECV, SEND,
                                 execute)
from repro.core.strategies import make_reducer

mesh = jax.make_mesh((2,), ("stage",), axis_types=(AxisType.Auto,))
N = 8
x = jnp.arange(2 * N, dtype=jnp.float32)     # rank r holds [rN, rN+N)
bucket = Bucket(
    leaves=(LeafInfo(name="act", index=0, shape=(N,), dtype=jnp.float32,
                     size=N),),
    reduce_axes=("stage",), channel=0, bucket_id=0)
sched = CommSchedule((
    CollectiveOp(op_id=0, bucket=bucket, chain=0, depends_on=(),
                 kind=SEND, shift=1),
    CollectiveOp(op_id=1, bucket=bucket, chain=0, depends_on=(0,),
                 kind=RECV, shift=1),
)).validate()
treedef = jax.tree_util.tree_structure([0])
plan = BucketPlan(buckets=(bucket,), treedef=treedef, num_leaves=1,
                  comm_dtype=jnp.float32)

def f(xs):
    out = execute(sched, [xs], plan,
                  reducer=make_reducer("flat", {"stage": 2},
                                       mean_axes=()),
                  mesh_shape={"stage": 2}, mean_axes=())
    return out[0]

out = jax.jit(lambda v: jax.shard_map(
    f, mesh=mesh, in_specs=(P("stage"),), out_specs=P("stage"))(v))(x)
got = np.asarray(out)
want = np.concatenate([np.arange(N, 2 * N), np.arange(0, N)])
print("SENDRECV_OK" if np.array_equal(got, want)
      else f"SENDRECV_FAIL {got}")
'''


def test_send_recv_moves_payload_subprocess(tmp_path):
    script = tmp_path / "sr_worker.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SENDRECV_OK" in proc.stdout, proc.stdout


# --- Hypothesis properties (skipped when hypothesis is absent) --------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:   # pragma: no cover — optional dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(S=st.integers(2, 4), M=st.integers(1, 12))
    def test_prop_1f1b_in_flight_le_stages(S, M):
        plan = plan_pipeline(S, M, kind="1f1b",
                             activation_bytes=1 << 10)
        assert max_in_flight(plan) <= S

    @settings(max_examples=25, deadline=None)
    @given(S=st.integers(1, 4), M=st.integers(1, 12))
    def test_prop_gpipe_bubble_formula(S, M):
        plan = plan_pipeline(S, M, kind="gpipe",
                             activation_bytes=1 << 10)
        tl = pipeline_timeline(plan, CM, wire_time=0.0)
        assert tl.bubble_fraction == pytest.approx(
            (S - 1) / (M + S - 1))

    @settings(max_examples=25, deadline=None)
    @given(S=st.integers(2, 4), M=st.integers(2, 12),
           wire=st.floats(0.01, 1.0))
    def test_prop_1f1b_wall_beats_gpipe(S, M, wire):
        if M < S:
            return   # the claim is for M >= S
        walls = {}
        for kind in ("gpipe", "1f1b"):
            plan = plan_pipeline(S, M, kind=kind,
                                 activation_bytes=1 << 20)
            walls[kind] = pipeline_timeline(
                plan, CM, wire_time=wire).wall
        assert walls["1f1b"] < walls["gpipe"]
else:   # keep a visible skip marker in the test report
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_prop_pipeline_properties():
        pass
