"""Device idle time put down to the program's own host spans.

The program records its spans in ``repro.obs``'s process ring, stamped
with ``time.time_ns()``.  The profiler stamps its events with the same
clock but writes them relative to the start of its session, which the
reduced trace does not keep.  The harness's window anchors one to the
other: ``counters["trace_window"]`` holds the ``perf_counter`` seconds
at which the window span ``bench.traced`` opened, and the trace holds
the same moment as ``Trace.lo``.

Each span's own time is its interval less its children's (the spans
that name it as parent).  A device's idle time is the window less the
union of its ops (``Trace._busy``: control flow left out).  Idle time is
put down to a span by overlap with the span's own time, per device, and
averaged over the devices.

Every function returns None where the ring holds no span of the window:
a program without the recorder, or a window with no step.
"""
from __future__ import annotations

import time

from bench.harness.trace import merge, minus

STEP_SPAN = "train.step"
COMPILE_SPAN = "jax.compile"


def clock_origin_ns() -> int:
    """``time.time_ns()`` less ``time.perf_counter_ns()``: the pair read
    closest together of a few."""
    best = None
    for _ in range(5):
        a = time.perf_counter_ns()
        t = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, t - (a + b) // 2)
    return best[1]


def window_spans(ctx) -> list | None:
    """The program's spans that overlap the traced window, on the
    trace's clock (not clipped); None when there are none."""
    try:
        from repro.obs import recorded_spans
    except ImportError:            # a program without the recorder
        return None
    tw = ctx.counters.get("trace_window")
    if ctx.trace is None or not tw:
        return None
    tr = ctx.trace
    shift = clock_origin_ns() + round(tw[0] * 1e9) - tr.lo
    got = recorded_spans(tr.lo + shift, tr.hi + shift)
    return [s._replace(start_ns=s.start_ns - shift, end_ns=s.end_ns - shift)
            for s in got] or None


def _clip(tr, spans, keep) -> list[tuple[float, float]]:
    return [(max(s.start_ns, tr.lo), min(s.end_ns, tr.hi))
            for s in spans if keep(s)]


def _idle(tr, intervals, exclude=()) -> float:
    """Nanoseconds of ``intervals`` in which no op ran and that
    ``exclude`` does not cover, averaged over the devices."""
    return sum(minus(merge(intervals), merge(tr._busy(d) + list(exclude)))
               for d in tr.devices) / max(tr.n_devices, 1)


def idle_ms_per_step(ctx, names) -> float | None:
    """Device idle time under the own time of the spans ``names``, in ms
    per step (the step spans that start in the window)."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    tr = ctx.trace
    steps = sum(s.name == STEP_SPAN and tr.lo <= s.start_ns < tr.hi
                for s in spans)
    if not steps or not tr.devices:
        return None
    return sum(_idle(tr, _clip(tr, spans, lambda s: s.name == n),
                     _clip(tr, spans, lambda s: s.parent == n))
               for n in names) * 1e-6 / steps


def idle_unspanned_share(ctx) -> float | None:
    """Percent of the window's device idle time under no span's own time,
    the step span's counted as no span: it frames the phases and names
    none."""
    spans = window_spans(ctx)
    tr = ctx.trace
    if spans is None or not tr.devices:
        return None
    idle = _idle(tr, [(tr.lo, tr.hi)])
    if idle <= 0:
        return 0.0
    # the own time of every span but the step spans is the union of
    # every span but theirs: a child's time is its parent's that the
    # parent's own time leaves out
    covered = _clip(tr, spans, lambda s: s.name != STEP_SPAN)
    return 100 * (idle - _idle(tr, covered)) / idle


def compiles_in_window(ctx) -> int | None:
    """Backend compiles (``jax.compile`` spans) that start in the window."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    tr = ctx.trace
    return sum(s.name == COMPILE_SPAN and tr.lo <= s.start_ns < tr.hi
               for s in spans)
