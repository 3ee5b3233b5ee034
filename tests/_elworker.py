"""Elastic-training worker: runs under XLA_FLAGS=8 fake devices in a
subprocess (jax device count is fixed at first init).  Prints PASS/FAIL
lines parsed by tests/test_elastic.py.

Covers DESIGN.md §13 end-to-end on real device groups:
  - StateCodec encode∘decode bit-exactness (scheduled + deferred)
  - zero-step 8→4→8 reshard round-trip identity
  - plan_reshard static facts + sim costing + seeded-mutation rejection
  - Supervisor fault cycles (rank loss, transient steps, checkpoint-I/O
    faults) with bit-exact faulty ≡ clean-scripted-replay parity, for
    scheduled AND deferred ZeRO-1 plans
  - deferred-plan exact resume through the PLAIN checkpoint path (tp=1)
    and the pending-manifest restore guard
  - straggler-driven shrink (opt-in remesh hook) with parity
  - measured per-op replay of the codec's RESHARD programs
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings

warnings.filterwarnings("ignore")
import dataclasses
import shutil
import tempfile
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.core import GradSyncConfig
from repro.data import TokenPipeline
from repro.elastic import (
    FaultPlan,
    StateCodec,
    Supervisor,
    plan_reshard,
    reshard_state,
)
from repro.models import transformer as tf
from repro.models.registry import family_of
from repro.optim import adamw, zero1
from repro.runtime import make_train_step
from repro.utils.trees import named_leaves


def check(name, cond):
    print(("PASS " if cond else "FAIL ") + name, flush=True)


def tree_maxdiff(a, b):
    worst = 0.0
    for (n, x), (_, y) in zip(named_leaves(a), named_leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        if x.shape != y.shape:
            return float("inf")
        if x.size:
            worst = max(worst, float(np.max(np.abs(x - y))))
    return worst


mk_dense = lambda tp: tf.TransformerConfig(
    name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=2, d_ff=128,
    vocab=96, tp=tp, attn_chunk=16, dtype=jnp.float32)

MESHES = {
    "tp4": ((2, 4), 8, 4),      # (mesh dims, device count, tp)
    "tp2": ((2, 2), 4, 2),
    "tp1": ((2, 1), 2, 1),
}
_BUILT: dict = {}


def build_for(mode, key):
    """Memoized (train_step, pipeline, placed_params) per (plan, mesh).

    Builder contract (Supervisor docstring): the batch schedule is
    mesh-independent — same seed, same global batch, dp extent 2 on
    every rung — so a replayed trajectory sees identical data.
    """
    if (mode, key) not in _BUILT:
        dims, ndev, tp = MESHES[key]
        mesh = jax.make_mesh(dims, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:ndev])
        cfg = mk_dense(tp)
        pipe = TokenPipeline(96, 32, 8, seed=5, mesh=mesh)
        params = family_of(cfg).init(jax.random.PRNGKey(2), mk_dense(1))
        # 1<<12 buckets: the config the pipelined-plan tests prove
        # bit-exact (deferred ≡ scheduled); larger buckets shift the
        # deferred AG's float fusion by ~1e-6 (pre-existing, see
        # tests/_mdworker.py check 10) and would turn the cross-plan
        # parity checks below into tolerance checks
        sync = GradSyncConfig(strategy="concom", bucket_bytes=1 << 12,
                              exclude_axes=("data",))
        ts = make_train_step(
            cfg, mesh, sync, zero1(adamw(1e-3), ("data",), 2),
            batch_like=pipe.batch_at(0), params_like=params,
            zero1_mode=True, zero1_plan=mode, clip_norm=0.0)
        ps = jax.device_put(params, ts.shardings(ts.param_specs))
        _BUILT[(mode, key)] = (ts, pipe, ps)
    return _BUILT[(mode, key)]


def run_plain(mode, key, n):
    ts, pipe, ps = build_for(mode, key)
    st = ts.init_opt()
    for k in range(n):
        ps, st, _ = ts.fn(ps, st, pipe.batch_at(k), jnp.int32(k))
    return ts, ps, st


# 1. StateCodec round-trip on the SAME mesh is bit-exact — scheduled
#    state (m, v) and deferred state (m, v, pending carry)
ts_s, p_s, o_s = run_plain("scheduled", "tp4", 2)
codec_s = StateCodec(ts_s)
enc = jax.device_get(codec_s.encode(p_s, o_s))
p_rt, o_rt = codec_s.decode(enc)
check("codec-roundtrip-scheduled-params", tree_maxdiff(p_s, p_rt) == 0.0)
check("codec-roundtrip-scheduled-opt", tree_maxdiff(o_s, o_rt) == 0.0)

ts_d, p_d, o_d = run_plain("deferred", "tp4", 2)
codec_d = StateCodec(ts_d)
enc_d = jax.device_get(codec_d.encode(p_d, o_d, include_pending=True))
check("codec-encodes-pending-stream", "pending" in enc_d["stats"])
p_drt, o_drt = codec_d.decode(enc_d)
check("codec-roundtrip-deferred-opt+pending",
      tree_maxdiff(o_d, o_drt) == 0.0
      and tree_maxdiff(p_d, p_drt) == 0.0)

# 2. zero-step 8→4→8 reshard round-trip is the identity (the tp-honest
#    global view survives a tp=4 → tp=2 → tp=4 relayout bit-for-bit)
ts_s2, _, _ = build_for("scheduled", "tp2")
p_4, o_4 = reshard_state(ts_s, ts_s2, p_s, o_s,
                         old_codec=codec_s, new_codec=StateCodec(ts_s2))
p_8, o_8 = reshard_state(ts_s2, ts_s, p_4, o_4,
                         old_codec=StateCodec(ts_s2), new_codec=codec_s)
check("reshard-8-4-8-roundtrip-params", tree_maxdiff(p_s, p_8) == 0.0)
check("reshard-8-4-8-roundtrip-opt", tree_maxdiff(o_s, o_8) == 0.0)

# 3. plan_reshard: verified transition IR with byte accounting, costable
#    by the simulator, and the analysis pass rejects a PRE op crossing
#    the REGROUP (the seeded mutation of the acceptance criteria)
rp = plan_reshard(ts_s, ts_s2, codec_s._params_like())
n_param = sum(int(np.prod(l.shape))
              for l in jax.tree.leaves(codec_s._params_like()))
check("plan-reshard-bytes-cover-streams",
      rp.reshard_bytes >= 3 * n_param * 4 and rp.streams[0] == "param")

from repro.sim.engine import SimConfig, simulate

merged = {"data": 2, "model": 4}
tl = simulate(rp.transition, merged, sim=SimConfig())
check("plan-reshard-sim-costable",
      tl.step_time > 0 and len(tl.events) == len(rp.transition.ops))

from repro.analysis import ScheduleError, verify_schedule
from repro.core.schedule import CommSchedule

mut_ops = list(rp.transition.ops)
mut_ops[0] = dataclasses.replace(mut_ops[0], phase="pre")
caught = False
try:
    verify_schedule(CommSchedule(tuple(mut_ops)), mesh_shape=None,
                    old_mesh_shape=rp.old_mesh_shape,
                    new_mesh_shape=rp.new_mesh_shape,
                    leaf_divisibility=rp.leaf_divisibility)
except ScheduleError as e:
    caught = "pre-crosses-regroup" in str(e)
check("plan-reshard-rejects-pre-crossing-regroup", caught)

# 4. measured per-op replay (repro.obs) of the codec's RESHARD programs:
#    gather side is bit-exact with the jitted gather, scatter side emits
#    one event per op
from repro.obs.measure import measured_timeline

gs = ts_s.gradsync
m_shards = {bid: o_s["inner"][k]["m"] for bid, k in codec_s.keys}
zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), p_s)
out_m, tl_m, _ = measured_timeline(
    codec_s._sched, zeros, codec_s.dp_plan, mesh=ts_s.mesh,
    param_specs=ts_s.param_specs, reducer=lambda b, _bk: b,
    mesh_shape=gs.mesh_shape, two_phase_impl=gs._two_phase_impl(),
    pending=m_shards)
ref_m = codec_s._gather(p_s, m_shards)
check("obs-replays-reshard-gather-bitexact",
      tree_maxdiff(out_m, ref_m) == 0.0
      and len(tl_m.events) == len(codec_s._sched.ops))

# 5. supervisor fault cycle, scheduled plan: transient step (absorbed by
#    rung-1 retry), checkpoint-I/O faults (absorbed by the manager's
#    backoff), rank loss at step 5 → shrink tp4→tp2, grow back at 10 —
#    and the clean scripted replay of the SAME mesh trajectory is
#    bit-exact with the faulty run
PLAN = FaultPlan(rank_loss=frozenset({5}), transient=frozenset({2}),
                 step_retries=1, ckpt_io_faults=2, ckpt_retries=3)
TOTAL, EVERY, GROW = 16, 4, 5


def run_super(mode, plan=None, script=None, **kw):
    root = tempfile.mkdtemp(prefix="elastic_")
    sup = Supervisor(lambda key: build_for(mode, key), ("tp4", "tp2"),
                     root, plan=plan, script=script, every=EVERY,
                     grow_back_after=GROW, printer=lambda s: None, **kw)
    p, o, rep = sup.run(TOTAL)
    shutil.rmtree(root, ignore_errors=True)
    return p, o, rep


pF, oF, repF = run_super("scheduled", plan=PLAN)
kindsF = [e["kind"] for e in repF["events"]]
check("supervisor-sched-cycle-script",
      repF["script"] == ((5, "tp2"), (10, "tp4"))
      and repF["final_mesh"] == "tp4")
check("supervisor-sched-events",
      "retry" in kindsF and "rank_lost" in kindsF
      and kindsF.count("transition") == 2)
check("supervisor-sched-metrics",
      repF["metrics"]["recovery_latency_s"]["count"] == 2
      and repF["metrics"]["reshard_bytes_total"] > 0)

pC, oC, repC = run_super("scheduled", script=repF["script"])
check("supervisor-sched-faulty-equals-clean-params",
      tree_maxdiff(pF, pC) == 0.0)
check("supervisor-sched-faulty-equals-clean-opt",
      tree_maxdiff(oF, oC) == 0.0)

# an uninterrupted tp4-only run is NOT bit-comparable (different tp →
# different reduction order on the middle segment) but must stay close
_, p_un, _ = run_plain("scheduled", "tp4", TOTAL)
check("supervisor-sched-close-to-uninterrupted",
      tree_maxdiff(pF, p_un) < 5e-2)

# 6. the same cycle under the DEFERRED plan: the pending carry is
#    flushed at each transition (finalize), decodes to the identity
#    carry on the new mesh, and the whole faulty run stays bit-exact
#    with its clean replay AND with the scheduled plan's trajectory
pFd, oFd, repFd = run_super("deferred", plan=PLAN)
check("supervisor-deferred-cycle-script",
      repFd["script"] == repF["script"])
pCd, oCd, repCd = run_super("deferred", script=repFd["script"])
check("supervisor-deferred-faulty-equals-clean",
      tree_maxdiff(pFd, pCd) == 0.0 and tree_maxdiff(oFd, oCd) == 0.0)
ts_d8, _, _ = build_for("deferred", "tp4")
check("supervisor-deferred-equals-scheduled-bitexact",
      tree_maxdiff(ts_d8.finalize(pFd, oFd), pF) == 0.0)

# 7. straggler-driven shrink (opt-in): two consecutive injected slow
#    steps trip the patience window, the remesh hook answers "shrink",
#    the supervisor transitions with the HEALTHY post-step state — and
#    the decision lands in the event stream
SPLAN = FaultPlan(straggler=frozenset({7, 8}), straggler_s=3.0,
                  straggler_shrink=True)
pS, oS, repS = run_super("scheduled", plan=SPLAN, straggler_factor=6.0,
                         straggler_patience=2)
remesh = [e for e in repS["events"] if e["kind"] == "remesh_requested"]
trans = repS["transitions"]
check("straggler-shrink-decision-event",
      bool(remesh) and remesh[0]["decision"] == "shrink")
check("straggler-shrink-transition",
      len(trans) == 2 and trans[0]["reason"] == "straggler_shrink"
      and trans[0]["resume_step"] == 9)
pSc, _, _ = run_super("scheduled", script=repS["script"])
check("straggler-shrink-faulty-equals-clean",
      tree_maxdiff(pS, pSc) == 0.0)

# 8. deferred-plan exact resume through the PLAIN checkpoint path: at
#    tp=1 the global view is honest, so CheckpointManager round-trips
#    the pending carry — a killed-and-recovered run matches the
#    uninterrupted one bit-for-bit; and the restore guard refuses a
#    checkpoint WITHOUT the carry
from repro.checkpoint.manager import CheckpointManager
from repro.runtime.train_loop import Trainer

ts1, pipe1, ps1 = build_for("deferred", "tp1")


def run_trainer(root, fail_at=frozenset(), every=2):
    ck = CheckpointManager(root, every=every, keep=0, blocking=True)
    tr = Trainer(ts1, pipe1, ck, fail_at=frozenset(fail_at),
                 printer=lambda s: None, log_every=10_000)
    return tr.run(ps1, ts1.init_opt(), 8)


root_a = tempfile.mkdtemp(prefix="elastic_")
root_b = tempfile.mkdtemp(prefix="elastic_")
p_kill, o_kill, rep_kill = run_trainer(root_a, fail_at={5})
p_ok, o_ok, _ = run_trainer(root_b)
kinds_k = [e["kind"] for e in rep_kill["events"]]
check("deferred-plain-ckpt-exact-resume",
      "recover" in kinds_k
      and tree_maxdiff(ts1.finalize(p_kill, o_kill),
                       ts1.finalize(p_ok, o_ok)) == 0.0)

# guard: a checkpoint saved WITHOUT the pending carry must be refused
no_pending = {"params": ps1,
              "opt": {k: v for k, v in ts1.init_opt().items()
                      if k != "pending"}}
root_c = tempfile.mkdtemp(prefix="elastic_")
CheckpointManager(root_c, every=1, blocking=True).maybe_save(
    1, no_pending)
guard_hit = False
try:
    run_trainer(root_c)
except RuntimeError as e:
    guard_hit = "pending" in str(e)
check("deferred-restore-guard-refuses-carry-less-ckpt", guard_hit)
for r in (root_a, root_b, root_c):
    shutil.rmtree(r, ignore_errors=True)

print("DONE", flush=True)
