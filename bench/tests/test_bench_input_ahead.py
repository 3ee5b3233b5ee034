"""``input_ahead_share.train`` against a ring counted by hand.

Window 0-1000 ms on the trace's clock.  The ring's ``train.input`` spans
start at -50 (before the window: left out), 0, 300, 600 and 950 (inside
it, though it ends after) and 1200 (after it): of the four that start
inside, three were built ahead, 75%.  The parent's trainer records the
same spans with no ``ahead`` attribute, which reads nothing.
"""
import pytest

from bench.harness import manifest, spans as hs
from bench.harness.core import ReaderContext
from bench.harness.trace import Trace
from bench.tests.test_bench_spans import DEV_A, MS, ORIGIN, PERF_LO, SHIFT
from repro.obs.spans import Span, SpanRecorder

INPUTS = [(-50, 20, True), (0, 100, False), (300, 350, True),
          (600, 650, True), (950, 1100, True), (1200, 1300, False)]
STEPS = [(0, 500, 1), (500, 1000, 2)]


def _ctx(monkeypatch, with_attr: bool, inputs=INPUTS):
    import repro.obs.spans

    rec = SpanRecorder()
    for a, b, step in STEPS:
        rec._append(Span("train.step", a * MS + SHIFT, b * MS + SHIFT,
                         None, step, {}))
    for a, b, ahead in inputs:
        rec._append(Span("train.input", a * MS + SHIFT, b * MS + SHIFT,
                         "train.step", None,
                         {"ahead": ahead} if with_attr else {}))
    monkeypatch.setattr(hs, "clock_origin_ns", lambda: ORIGIN)
    monkeypatch.setattr(repro.obs.spans, "RECORDER", rec)
    tr = Trace.from_dict({"devices": {"/device:TPU:0": DEV_A},
                          "host": [[0, 1000 * MS, "bench.traced"]]})
    return ReaderContext(trace=tr, counters={"trace_window": (PERF_LO, 1e9)},
                         peaks={}, chips=1, cell=None, reference=None)


def _read(ctx):
    return manifest.reader("input_ahead_share.train").read(ctx)


def test_share_of_the_window_s_builds_made_ahead(monkeypatch):
    assert _read(_ctx(monkeypatch, True)) == pytest.approx(75.0)


@pytest.mark.parametrize("with_attr,inputs", [
    (False, INPUTS),                    # the parent: no attribute
    (True, []),                         # no input built in the window
    (True, [(1200, 1300, True)]),       # only after the window
], ids=["parent", "no-input", "outside-window"])
def test_spans_without_the_attribute_read_nothing(monkeypatch, with_attr,
                                                  inputs):
    assert _read(_ctx(monkeypatch, with_attr, inputs)) is None
