"""A whole benchmark run of a training cell at dp=4 (global batch 16, four
virtual CPU devices, in a child process), sound and with the gradient
exchange left out: each worker steps on its own gradient, the fault
that only a cell of several workers can have.

The sound run is ``correct`` under the real cell's limits.  The missing
exchange shows in every reading of the comparison, most of all in the
first gradient's norm (``grad_norm_gap``, logged, not compared): after
clipping, each worker's update has the reference's norm, so the
norm-based ``update_gap`` and ``loss_gap`` catch it on some seeds only,
which is why the dp=4 cell waits for a limit that separates it.

    python -m bench.tests.test_bench_faults_dp4 <dir>   # the child
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).parent / "fixture"
CELL = "tiny.train-dp4"
SEED = 2**40 + 3


def write_cell(root: Path) -> None:
    """The fixture's tiny training cell over a ``data = 4`` mesh, with
    the real cell's limits."""
    m = json.loads((FIX / "BENCHMARK.json").read_text())
    m["configs"] = [c for c in m["configs"] if c["name"] == "resnet-tiny"]
    m["workloads"] = [{"name": CELL, "config": "resnet-tiny",
                       "traffic": "tiny-train-dp4", "chips": 4,
                       "why": "test"}]
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = [CELL]
    cfg = json.loads((FIX / "configs" / "resnet-tiny.json").read_text())
    cfg["limits"] = json.loads(
        (ROOT / "bench" / "configs" / "resnet50-cifar.json").read_text()
    )["limits"]
    tr = json.loads(
        (FIX / "bench" / "traffic" / "tiny-train.json").read_text())
    tr.update(global_batch=16, mesh={"data": 4})
    (root / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    (root / "configs" / "resnet-tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-train-dp4.json").write_text(
        json.dumps(tr))


def child(root: Path) -> None:
    """Run the cell sound, then without the exchange; one JSON line
    each, with every reading of the comparison."""
    import jax

    from bench.harness import core
    from bench.jobs import train_image
    from repro.core import GradSync

    got: dict = {}
    readings = train_image.readings
    train_image.readings = lambda *a: got.update(readings(*a)) or got
    sync = GradSync.__call__
    for fault in (False, True):
        if fault:
            # the plain sync (no update_fn) hands the gradients back as
            # each worker computed them
            GradSync.__call__ = (
                lambda self, g, **kw: sync(self, g, **kw) if kw else g)
        got.clear()
        res = core.execute(root, CELL, SEED, 0.5, False, time.perf_counter(),
                           device_check=lambda n: jax.devices()[:n])
        print(json.dumps({"fault": fault, "correct": res["correct"],
                          "checks": sorted(res["checks"]),
                          "readings": got}), flush=True)


@pytest.fixture(scope="module")
def runs(tmp_path_factory) -> dict:
    root = tmp_path_factory.mktemp("dp4")
    write_cell(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.test_bench_faults_dp4",
         str(root)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    return {r["fault"]: r for r in rows}


def test_sound_run_at_dp4_is_correct(runs):
    sound = runs[False]
    assert sound["correct"], sound["readings"]
    assert sound["checks"] == ["loss_gap", "update_gap"]
    assert sound["readings"]["grad_norm_gap"] < 1e-3


def test_missing_exchange_at_dp4_shows_in_every_reading(runs):
    sound, fault = runs[False]["readings"], runs[True]["readings"]
    for name in ("loss_gap", "grad_norm_gap", "grad_gap", "update_gap"):
        assert fault[name] > sound[name] + 1e-3, name
    # the gradient norm tells the fault from the sound run by far
    assert fault["grad_norm_gap"] > 0.3


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    child(Path(sys.argv[1]))
