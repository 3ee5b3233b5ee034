"""The readers of the program's spans against values counted by hand.

Window 0-1000 ms on the trace's clock.  Device "a" runs ops 100-300 and
500-700, so it is idle 0-100, 300-500 and 700-1000 (600 ms).  The ring
holds two steps:

- step 1: ``train.step`` 0-500; ``train.input`` 0-150 with ``data.synth``
  10-60 and ``data.place`` 60-140 inside it; ``train.dispatch`` 150-160,
  ``train.wait`` 160-320, ``train.account`` 320-420;
- step 2: ``train.step`` 500-900; ``train.input`` 500-550,
  ``train.dispatch`` 550-560, ``train.wait`` 560-720, ``train.account``
  720-800;
- nothing 900-1000.

The idle gap 0-100 is split by overlap: 10 under ``train.input``'s own
time, 50 under ``data.synth``, 40 under ``data.place``.  ``train.wait``
holds 20 + 20, ``train.account`` 100 + 80, the step spans' own time
(420-500, 800-900) 180, and no span 100: 280 of 600 unspanned.  Device
"busy" runs an op over the whole window; averaged with it, every idle
time halves and the unspanned share stays.
"""
import time

import numpy as np
import pytest

from bench.harness import manifest, spans as hs
from bench.harness.core import ReaderContext
from bench.harness.trace import Trace
from repro.obs.spans import Span, SpanRecorder

MS = 1_000_000
ORIGIN = 7 * 10**18          # a stand-in for time_ns - perf_counter_ns
PERF_LO = 1234.5             # perf_counter seconds at the window's start
SHIFT = ORIGIN + round(PERF_LO * 1e9)      # ring clock - trace clock

STEPS = [   # (name, start ms, end ms, parent, step)
    ("data.synth", 10, 60, "train.input", 1),
    ("data.place", 60, 140, "train.input", 1),
    ("train.input", 0, 150, "train.step", 1),
    ("train.dispatch", 150, 160, "train.step", 1),
    ("train.wait", 160, 320, "train.step", 1),
    ("train.account", 320, 420, "train.step", 1),
    ("train.step", 0, 500, None, 1),
    ("train.input", 500, 550, "train.step", 2),
    ("train.dispatch", 550, 560, "train.step", 2),
    ("train.wait", 560, 720, "train.step", 2),
    ("train.account", 720, 800, "train.step", 2),
    ("train.step", 500, 900, None, 2),
]
DEV_A = {"ops": [[100 * MS, 300 * MS, "%fusion.1 = f32[8] fusion(%p)"],
                 [500 * MS, 700 * MS, "%fusion.2 = f32[8] fusion(%q)"]]}
DEV_BUSY = {"ops": [[0, 1000 * MS, "%fusion.3 = f32[8] fusion(%r)"]]}


def _ring(rows) -> SpanRecorder:
    rec = SpanRecorder()
    for name, a, b, parent, step in rows:
        rec._append(Span(name, a * MS + SHIFT, b * MS + SHIFT, parent, step,
                         {}))
    return rec


def _ctx(devices, rows, monkeypatch):
    import repro.obs.spans

    monkeypatch.setattr(hs, "clock_origin_ns", lambda: ORIGIN)
    monkeypatch.setattr(repro.obs.spans, "RECORDER", _ring(rows))
    tr = Trace.from_dict({"devices": devices,
                          "host": [[0, 1000 * MS, "bench.traced"]]})
    return ReaderContext(trace=tr, counters={"trace_window": (PERF_LO, 1e9)},
                         peaks={}, chips=len(devices), cell=None,
                         reference=None)


def _read(name, ctx):
    return manifest.reader(name).read(ctx)


ONE = {"/device:TPU:0": DEV_A}
FOUR = {"/device:TPU:0": DEV_A, "/device:TPU:1": DEV_BUSY,
        "/device:TPU:2": DEV_A, "/device:TPU:3": DEV_BUSY}


@pytest.mark.parametrize("devices,scale", [(ONE, 1.0), (FOUR, 0.5)],
                         ids=["one-device", "four-devices"])
def test_idle_is_split_by_overlap_with_own_time(devices, scale,
                                                monkeypatch):
    ctx = _ctx(devices, STEPS, monkeypatch)
    # per step: two steps start in the window
    assert _read("idle_input_ms.train", ctx) == pytest.approx(
        scale * (10 + 50 + 40) / 2)
    assert _read("idle_dispatch_ms.train", ctx) == pytest.approx(
        scale * (20 + 20) / 2)
    assert _read("idle_account_ms.train", ctx) == pytest.approx(
        scale * (100 + 80) / 2)
    assert _read("idle_unspanned_share.train", ctx) == pytest.approx(
        100 * 280 / 600)
    assert _read("compiles_in_window.train", ctx) == 0


def test_own_time_leaves_out_children(monkeypatch):
    # idle 0-100 under a parent and its child, which covers 20-100: the
    # child's 80 counts once, under the child
    rows = [("train.step", 0, 100, None, 1),
            ("train.input", 0, 100, "train.step", 1),
            ("data.synth", 20, 100, "train.input", 1),
            ("train.wait", 100, 1000, "train.step", 1)]
    ctx = _ctx({"/device:TPU:0": {"ops": [[100 * MS, 1000 * MS, "fusion"]]}},
               rows, monkeypatch)
    assert _read("idle_input_ms.train", ctx) == pytest.approx(100)
    assert _read("idle_dispatch_ms.train", ctx) == pytest.approx(0)
    assert _read("idle_unspanned_share.train", ctx) == pytest.approx(0)


def test_a_gap_shared_by_two_spans_is_split(monkeypatch):
    # one idle gap 0-100 on the device, half under each of two spans
    rows = [("train.step", 0, 100, None, 1),
            ("train.input", 0, 50, "train.step", 1),
            ("train.wait", 50, 100, "train.step", 1)]
    ctx = _ctx({"/device:TPU:0": {"ops": [[100 * MS, 1000 * MS, "fusion"]]}},
               rows, monkeypatch)
    assert _read("idle_input_ms.train", ctx) == pytest.approx(50)
    assert _read("idle_dispatch_ms.train", ctx) == pytest.approx(50)
    assert _read("idle_unspanned_share.train", ctx) == pytest.approx(0)


READERS = ["idle_input_ms.train", "idle_dispatch_ms.train",
           "idle_account_ms.train", "idle_unspanned_share.train",
           "compiles_in_window.train"]


@pytest.mark.parametrize("name", READERS)
def test_no_span_in_the_window_reads_nothing(name, monkeypatch):
    assert _read(name, _ctx(ONE, [], monkeypatch)) is None
    # spans outside the window are not read either
    late = [("train.step", 2000, 2100, None, 9)]
    assert _read(name, _ctx(ONE, late, monkeypatch)) is None


def test_compiles_that_start_in_the_window_are_counted(monkeypatch):
    rows = STEPS + [("jax.compile", -50, 20, None, 0),
                    ("jax.compile", 170, 300, None, 1),
                    ("jax.compile", 990, 1200, None, 2)]
    assert _read("compiles_in_window.train",
                 _ctx(ONE, rows, monkeypatch)) == 2


def test_a_recompile_inside_a_window_is_counted():
    """Through the real ring and clocks: a new shape compiled between the
    window's edges is one compile in the window."""
    import jax

    f = jax.jit(lambda x: x * 2 + 1)
    f(np.ones(3, np.float32)).block_until_ready()
    x = np.ones(5, np.float32)
    lo = time.perf_counter()
    from repro.obs import step_span

    with step_span("train.step", 0):
        f(x).block_until_ready()
    hi = time.perf_counter()
    tr = Trace.from_dict({"devices": {}, "host": [
        [0, (hi - lo) * 1e9, "bench.traced"]]})
    ctx = ReaderContext(trace=tr, counters={"trace_window": (lo, hi)},
                        peaks={}, chips=1, cell=None, reference=None)
    assert _read("compiles_in_window.train", ctx) == 1
