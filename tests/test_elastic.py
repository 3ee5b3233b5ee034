"""repro.elastic: IR-level regroup/reshard semantics (single device)
plus the full multi-device elastic cycle in a subprocess worker
(tests/_elworker.py — jax fixes the device count at first init)."""
import os
import subprocess
import sys
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.analysis import ScheduleError, run_passes, verify_schedule
from repro.analysis.mutations import (
    NEW_MESH_RS,
    OLD_MESH_RS,
    synthetic_reshard_schedule,
)
from repro.core.schedule import REGROUP, RESHARD, CommSchedule

CTX = dict(old_mesh_shape=OLD_MESH_RS, new_mesh_shape=NEW_MESH_RS)


# ----------------------------------------------------- transition IR

def test_synthetic_transition_verifies_clean():
    s = synthetic_reshard_schedule()
    verify_schedule(s, **CTX)
    report = run_passes(s, **CTX)
    assert report.ok, report.render()


def test_split_regroup_sides():
    s = synthetic_reshard_schedule(streams=("param", "inner/m"))
    old, new = s.split_regroup()
    assert old.ops[-1].kind == REGROUP
    assert all(op.kind == RESHARD for op in new.ops)
    # cross-side deps were dropped: the new side is self-contained
    new_ids = {op.op_id for op in new.ops}
    for op in new.ops:
        assert set(op.depends_on) <= new_ids
    # each side verifies standalone (old on the old mesh, new on the new)
    run_passes(old, mesh_shape=OLD_MESH_RS)
    run_passes(new, mesh_shape=NEW_MESH_RS)


def test_split_regroup_requires_regroup():
    s = synthetic_reshard_schedule()
    plain = CommSchedule(tuple(op for op in s.ops
                               if op.kind != REGROUP))
    with pytest.raises(ValueError, match="no REGROUP"):
        plain.split_regroup()


def test_reshard_pass_leaf_divisibility():
    # the static divisibility facts fail loud even with no RESHARD ops
    s = synthetic_reshard_schedule()
    with pytest.raises(ScheduleError, match="leaf-indivisible"):
        verify_schedule(s, **CTX,
                        leaf_divisibility={"w0@dim0": (10, 4)})
    verify_schedule(s, **CTX, leaf_divisibility={"w0@dim0": (12, 4)})


def test_reshard_pass_byte_conservation():
    s = synthetic_reshard_schedule()
    # drop one scatter: the new side loses a stream's bytes
    pruned = CommSchedule(s.ops[:-1])
    report = run_passes(pruned, **CTX)
    assert not report.ok
    assert any(f.code in ("leaf-lost", "leaf-size-drift")
               for f in report.findings)


# ------------------------------------------------- sim costing

def test_sim_costs_transition_ops():
    from repro.sim.engine import SimConfig, simulate

    s = synthetic_reshard_schedule()
    merged = {a: max(OLD_MESH_RS.get(a, 1), NEW_MESH_RS.get(a, 1))
              for a in {*OLD_MESH_RS, *NEW_MESH_RS}}
    tl = simulate(s, merged, sim=SimConfig())
    assert len(tl.events) == len(s.ops)
    by_id = {e.op_id: e for e in tl.events}
    for op in s.ops:
        assert by_id[op.op_id].duration > 0
    # the REGROUP barrier starts only after every gather finished
    rg = next(op for op in s.ops if op.kind == REGROUP)
    gather_ends = [by_id[op.op_id].end for op in s.ops
                   if op.kind == RESHARD and op.op_id < rg.op_id]
    assert by_id[rg.op_id].start >= max(gather_ends) - 1e-12


# ------------------------------------------------- KVStore.regroup

def test_kvstore_regroup_records_barrier_ir():
    from repro.core.kvstore import KVStore

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))

    kv = KVStore("concom", reduce_axes=("data",), num_channels=2,
                 mesh_shape={"data": 1})
    traced = {}

    def body(x):
        kv.init(0, x)
        kv.init(1, x)
        kv.push(0, x)
        kv.push(1, x * 2)
        traced["size"] = kv.regroup()
        kv.push(0, x * 3)
        return kv.pull(0)

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                out_specs=P(), check_vma=False))(
        jnp.ones((8,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 3.0)

    s = kv.schedule()
    kinds = [op.kind for op in s.ops]
    assert kinds.count(REGROUP) == 1
    rg = next(op for op in s.ops if op.kind == REGROUP)
    pre = [op.op_id for op in s.ops
           if op.op_id < rg.op_id and op.kind != REGROUP]
    # the barrier joins every outstanding chain tail...
    assert set(rg.depends_on) == set(pre[-2:]) or \
        set(rg.depends_on) <= set(pre)
    # ...and every post-regroup op is anchored on it
    post = [op for op in s.ops if op.op_id > rg.op_id]
    assert post and all(rg.op_id in op.depends_on for op in post)
    assert run_passes(s, mesh_shape={"data": 1}).ok


def test_kvstore_regroup_switches_communicator():
    from repro.core.kvstore import KVStore

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    kv = KVStore("concom", reduce_axes=("data", "model"), num_channels=1,
                 mesh_shape={"data": 1, "model": 1})

    def body(x):
        kv.init(0, x)
        kv.push(0, x)
        kv.regroup(reduce_axes=("data",), mesh_shape={"data": 1})
        kv.push(0, x)
        return kv.pull(0)

    jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                          out_specs=P(), check_vma=False))(
        jnp.ones((4,), jnp.float32))
    assert kv.reduce_axes == ("data",)
    # the trace spans TWO communicators — no single mesh_shape verifies
    # it, so read the IR unverified and check the recorded switch
    s = kv.schedule(verify=False)
    rg = next(op for op in s.ops if op.kind == REGROUP)
    # the barrier itself runs on the OLD communicator's axes
    assert rg.bucket.reduce_axes == ("data", "model")
    # ops after the regroup reduce over the NEW group only
    post = [op for op in s.ops if op.op_id > rg.op_id]
    assert post and all(op.bucket.reduce_axes == ("data",)
                       for op in post)


# ------------------------------------------------- multi-device worker

@pytest.fixture(scope="module")
def worker_output():
    script = os.path.join(os.path.dirname(__file__), "_elworker.py")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run(
        [sys.executable, script], env=env,
        capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_worker_completed(worker_output):
    assert "DONE" in worker_output


def test_all_elastic_checks_pass(worker_output):
    fails = [l for l in worker_output.splitlines() if l.startswith("FAIL")]
    passes = [l for l in worker_output.splitlines() if l.startswith("PASS")]
    assert not fails, fails
    assert len(passes) >= 18, worker_output
