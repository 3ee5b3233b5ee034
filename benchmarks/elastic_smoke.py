"""Elastic-training smoke (CI gate + BENCH_elastic.json artifact).

Runs the full DESIGN.md §13 cycle on 8 fake CPU devices: a fault-ridden
supervisor run (transient step, checkpoint-I/O faults, rank loss at
step 5 → shrink tp4→tp2 → grow back) must produce BIT-EXACT final state
against a clean scripted replay of the same mesh trajectory, for the
scheduled AND the deferred ZeRO-1 plan; the reshard analysis pass must
reject a seeded PRE-op-crosses-REGROUP mutation.  Exits nonzero on any
failure.  Writes BENCH_elastic.json with the provenance header
(`obs.bench_metadata`), per-transition recovery latency, and reshard
byte counts.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import warnings

warnings.filterwarnings("ignore")
import dataclasses
import json
import shutil
import sys
import tempfile
import time
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.analysis import ScheduleError, verify_schedule
from repro.core import GradSyncConfig
from repro.core.schedule import CommSchedule
from repro.data import TokenPipeline
from repro.elastic import FaultPlan, StateCodec, Supervisor, plan_reshard
from repro.models import transformer as tf
from repro.models.registry import family_of
from repro.optim import adamw, zero1
from repro.runtime import make_train_step
from repro.utils.trees import named_leaves

FAILURES: list[str] = []


def check(name, cond):
    print(("PASS " if cond else "FAIL ") + name, flush=True)
    if not cond:
        FAILURES.append(name)


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


def mk_dense(tp):
    return tf.TransformerConfig(
        name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=2,
        d_ff=128, vocab=96, tp=tp, attn_chunk=16, dtype=jnp.float32)


MESHES = {"tp4": ((2, 4), 8, 4), "tp2": ((2, 2), 4, 2)}
_BUILT: dict = {}


def build_for(mode, key):
    if (mode, key) not in _BUILT:
        dims, ndev, tp = MESHES[key]
        mesh = jax.make_mesh(dims, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:ndev])
        cfg = mk_dense(tp)
        pipe = TokenPipeline(96, 32, 8, seed=5, mesh=mesh)
        params = family_of(cfg).init(jax.random.PRNGKey(2), mk_dense(1))
        # 1<<12 buckets keep deferred ≡ scheduled bit-exact (see
        # tests/_mdworker.py check 10)
        sync = GradSyncConfig(strategy="concom", bucket_bytes=1 << 12,
                              exclude_axes=("data",))
        ts = make_train_step(
            cfg, mesh, sync, zero1(adamw(1e-3), ("data",), 2),
            batch_like=pipe.batch_at(0), params_like=params,
            zero1_mode=True, zero1_plan=mode, clip_norm=0.0)
        ps = jax.device_put(params, ts.shardings(ts.param_specs))
        _BUILT[(mode, key)] = (ts, pipe, ps)
    return _BUILT[(mode, key)]


def main():
    t_start = time.time()
    PLAN = FaultPlan(rank_loss=frozenset({5}), transient=frozenset({2}),
                     step_retries=1, ckpt_io_faults=2, ckpt_retries=3)
    TOTAL, EVERY, GROW = 12, 4, 5

    def run_super(mode, plan=None, script=None):
        root = tempfile.mkdtemp(prefix="elastic_smoke_")
        sup = Supervisor(lambda key: build_for(mode, key),
                         ("tp4", "tp2"), root, plan=plan, script=script,
                         every=EVERY, grow_back_after=GROW,
                         printer=lambda s: None)
        try:
            return sup.run(TOTAL)
        finally:
            shutil.rmtree(root, ignore_errors=True)

    rows = []
    reports = {}
    for mode in ("scheduled", "deferred"):
        t0 = time.time()
        pF, oF, repF = run_super(mode, plan=PLAN)
        check(f"{mode}-cycle-script",
              repF["script"] == ((5, "tp2"), (10, "tp4"))
              and repF["final_mesh"] == "tp4")
        kinds = [e["kind"] for e in repF["events"]]
        check(f"{mode}-survives-faults",
              "retry" in kinds and "rank_lost" in kinds
              and kinds.count("transition") == 2)
        pC, oC, _ = run_super(mode, script=repF["script"])
        check(f"{mode}-faulty-equals-clean-bitexact",
              tree_maxdiff(pF, pC) == 0.0
              and tree_maxdiff(oF, oC) == 0.0)
        reports[mode] = repF
        lat = repF["metrics"]["recovery_latency_s"]
        rows.append({
            "mode": mode,
            "steps": TOTAL,
            "transitions": len(repF["transitions"]),
            "recovery_latency_s_mean": round(lat["mean"], 4),
            "recovery_latency_s_max": round(lat["max"], 4),
            "reshard_bytes_total": int(
                repF["metrics"]["reshard_bytes_total"]),
            "reshard_bytes_per_transition": [
                t["reshard_bytes"] for t in repF["transitions"]],
            "wall_s": round(time.time() - t0, 2),
        })

    # the static reshard pass catches the seeded mutation: a PRE-phase
    # op smuggled across the REGROUP barrier
    ts_s, _, _ = build_for("scheduled", "tp4")
    ts_s2, _, _ = build_for("scheduled", "tp2")
    codec = StateCodec(ts_s)
    rp = plan_reshard(ts_s, ts_s2, codec._params_like())
    mut = list(rp.transition.ops)
    mut[0] = dataclasses.replace(mut[0], phase="pre")
    caught = False
    try:
        verify_schedule(CommSchedule(tuple(mut)), mesh_shape=None,
                        old_mesh_shape=rp.old_mesh_shape,
                        new_mesh_shape=rp.new_mesh_shape,
                        leaf_divisibility=rp.leaf_divisibility)
    except ScheduleError as e:
        caught = "pre-crosses-regroup" in str(e)
    check("reshard-pass-catches-seeded-mutation", caught)

    from repro.obs import bench_metadata

    out = {
        "bench": "elastic",
        "meta": bench_metadata(),
        "plan": {"rank_loss": sorted(PLAN.rank_loss),
                 "transient": sorted(PLAN.transient),
                 "ckpt_io_faults": PLAN.ckpt_io_faults,
                 "steps": TOTAL, "ladder": ["tp4", "tp2"]},
        "rows": rows,
        "checks": {"failed": FAILURES,
                   "wall_s": round(time.time() - t_start, 2)},
    }
    with open("BENCH_elastic.json", "w") as f:
        json.dump(out, f, indent=1)
    print(f"[bench] wrote BENCH_elastic.json ({len(rows)} rows)")
    if FAILURES:
        print(f"FAILED: {len(FAILURES)} check(s): {FAILURES}")
        return 1
    print("DONE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
