"""A whole benchmark run of a training cell, at a small size on the CPU
with the look for a chip skipped, and with the timed path broken
underneath: ``correct`` has to come out false for each fault the cell can
have (a state left unchanged; half of the batch left out, the mean taken
over the rest), and true for the sound program.  The limits are the real
cell's.
"""
import json
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).parent / "fixture"
LIMITS = json.loads(
    (ROOT / "bench" / "configs" / "resnet50-cifar.json").read_text())["limits"]


def run_tiny(workload: str, seed: int, trace: bool = False) -> dict:
    import jax

    from bench.harness import core, manifest

    orig = manifest.Cell.limits
    manifest.Cell.limits = lambda self: dict(LIMITS)
    try:
        return core.execute(FIX, workload, seed, 0.5, trace,
                            time.perf_counter(),
                            device_check=lambda n: jax.devices()[:n])
    finally:
        manifest.Cell.limits = orig


def test_sound_run_is_correct():
    res = run_tiny("tiny.train", 2**40 + 3)
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_samples_per_s"]["value"] > 0
    assert set(res["checks"]) == set(LIMITS)
    assert list(res)[-1] == "checks"


def test_traced_run_reads_the_host_metrics():
    """The traced path end to end: profiler on, trace reduced, readers
    run; on the CPU there is no device plane, so device metrics and the
    peak-based shares read nothing and are left out."""
    res = run_tiny("tiny.train", 7, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"input_wait_ms.train"}
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_state_left_unchanged_is_caught(monkeypatch):
    import repro.runtime.train_loop as tl

    monkeypatch.setattr(tl, "apply_updates", lambda params, updates: params)
    res = run_tiny("tiny.train", 11)
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch):
    import dataclasses

    import repro.runtime.train_loop as tl

    family_of = tl.family_of

    def halved(cfg):
        api = family_of(cfg)

        def fwd(params, batch, cfg):
            n = batch["labels"].shape[0] // 2
            half = {"images": batch["images"][:n],
                    "labels": batch["labels"][:n],
                    "global_tokens": batch["global_tokens"] / 2}
            return api.train_forward(params, half, cfg)

        return dataclasses.replace(api, train_forward=fwd)

    monkeypatch.setattr(tl, "family_of", halved)
    res = run_tiny("tiny.train", 12)
    assert not res["correct"], res["checks"]
