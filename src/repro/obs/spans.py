"""Host spans of the measured path (DESIGN.md §12).

``span(name, **attrs)`` times one block of host work twice over: as a
``jax.profiler.TraceAnnotation`` (so xprof and Perfetto show it beside
the device ops while a profile is being taken) and as a ``Span`` in a
bounded, always-on, process-wide ring that code in the same process
reads back with ``recorded_spans``.  ``step_span(name, step)`` does the
same with a ``StepTraceAnnotation``, which feeds xprof's step view.

A span nested in another names it as ``parent`` and inherits its
``step``.  Timestamps are ``time.time_ns()`` (CLOCK_REALTIME), the clock
the profiler stamps its host events with.  A span is recorded when its
block exits, by an exception too.

Backend compiles (and compile-cache loads) are recorded as spans
``jax.compile`` by a ``jax.monitoring`` listener, ending when it fires,
with no parent; the listener is registered once, when this module is
imported.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Iterator, NamedTuple

import jax

RING_SIZE = 1 << 16
COMPILE_SPAN = "jax.compile"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: str | None      # the enclosing span's name, on this thread
    step: int | None
    attrs: dict[str, Any]


class SpanRecorder:
    """A ring of the last ``RING_SIZE`` finished spans; each thread keeps
    its own stack of open spans, which gives parents and steps."""

    def __init__(self):
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=RING_SIZE)
        self._lock = threading.Lock()
        self._local = threading.local()

    def _open(self) -> list[tuple[str, int | None]]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, s: Span) -> None:
        with self._lock:
            self._ring.append(s)

    @contextlib.contextmanager
    def _record(self, name: str, annotation, step: int | None,
                attrs: dict) -> Iterator[None]:
        stack = self._open()
        parent, parent_step = stack[-1] if stack else (None, None)
        step = parent_step if step is None else step
        stack.append((name, step))
        with annotation:
            start = time.time_ns()
            try:
                yield
            finally:
                end = time.time_ns()
                stack.pop()
                self._append(Span(name, start, end, parent, step, attrs))

    def span(self, name: str, **attrs):
        return self._record(name, jax.profiler.TraceAnnotation(name),
                            None, attrs)

    def step_span(self, name: str, step: int):
        return self._record(
            name, jax.profiler.StepTraceAnnotation(name, step_num=step),
            int(step), {})

    def on_duration(self, event: str, duration_s: float, **kw) -> None:
        """``jax.monitoring`` duration listener: a backend compile as a
        span ``jax.compile`` that ends now."""
        if event != COMPILE_EVENT:
            return
        end = time.time_ns()
        stack = self._open()
        step = stack[-1][1] if stack else None
        self._append(Span(COMPILE_SPAN, end - int(duration_s * 1e9), end,
                          None, step, dict(kw)))

    def spans(self, lo_ns: int | None = None,
              hi_ns: int | None = None) -> list[Span]:
        """Finished spans that overlap ``[lo_ns, hi_ns)``, oldest first
        by end (all of them when no bound is given)."""
        with self._lock:
            out = list(self._ring)
        if lo_ns is not None:
            out = [s for s in out if s.end_ns > lo_ns]
        if hi_ns is not None:
            out = [s for s in out if s.start_ns < hi_ns]
        return out


RECORDER = SpanRecorder()
jax.monitoring.register_event_duration_secs_listener(RECORDER.on_duration)


def span(name: str, **attrs):
    """Context manager: record the block as a span of the process ring."""
    return RECORDER.span(name, **attrs)


def step_span(name: str, step: int):
    """``span`` for one training step, shown in xprof's step view."""
    return RECORDER.step_span(name, step)


def recorded_spans(lo_ns: int | None = None,
                   hi_ns: int | None = None) -> list[Span]:
    """The process ring's spans that overlap ``[lo_ns, hi_ns)``."""
    return RECORDER.spans(lo_ns, hi_ns)
