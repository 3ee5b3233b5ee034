"""A whole benchmark run of a serving cell, at a small size on the CPU
with the look for a chip skipped, and with the timed path broken
underneath: ``correct`` has to come out false for each fault the cell can
have (a token altered where it is produced; the prompt's cache never
written, so decode runs on a state left unchanged), and true for the
sound program.  The limits are the real cell's."""
import json
import time
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).parent / "fixture"
LIMITS = json.loads(
    (ROOT / "bench" / "configs" / "qwen3-1.7b.json").read_text())["limits"]


def run_tiny(seed: int, trace: bool = False) -> dict:
    from bench.harness import core, manifest

    orig = manifest.Cell.limits
    manifest.Cell.limits = lambda self: dict(LIMITS)
    try:
        return core.execute(FIX, "tiny.serve", seed, 0.5, trace,
                            time.perf_counter(),
                            device_check=lambda n: jax.devices()[:n])
    finally:
        manifest.Cell.limits = orig


def test_sound_run_is_correct():
    res = run_tiny(2**40 + 5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"ttft_p95_s", "tpot_p95_ms", "serve_tokens_per_s",
            "setup_s"} <= set(res["metrics"])


def test_traced_run_reads_the_host_metrics():
    """The traced path end to end; on the CPU only the engine's counter
    has something to read."""
    res = run_tiny(8, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"batch_occupancy.serve"}
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_altered_token_is_caught(monkeypatch):
    import repro.runtime.serve_loop as sl

    sample = sl.sharded_sample

    def altered(logits_local, tp, *a, **kw):
        tok = sample(logits_local, tp, *a, **kw)
        return (tok + 1) % (logits_local.shape[-1] * tp)

    monkeypatch.setattr(sl, "sharded_sample", altered)
    res = run_tiny(31)
    assert not res["correct"], res["checks"]


def test_unwritten_cache_is_caught(monkeypatch):
    import repro.runtime.serve_loop as sl

    build = sl.ContinuousScheduler._build_prefill

    def no_insert(self, sb):
        pf, _ = build(self, sb)
        return pf, jax.jit(lambda pk, pv, ck, cv, dest, owner: (pk, pv))

    monkeypatch.setattr(sl.ContinuousScheduler, "_build_prefill", no_insert)
    res = run_tiny(32)
    assert not res["correct"], res["checks"]
