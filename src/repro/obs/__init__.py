"""repro.obs — measured-path telemetry (DESIGN.md §12).

The subsystem that looks *back* at what actually ran:

  metrics     — counters/gauges/histograms + the shared host timer
  events      — JSONL event stream + heartbeat line
  provenance  — the metadata header every BENCH_*/profile artifact embeds
  measure     — per-op measured replay emitting sim-compatible Timelines
  spans       — host spans on the profiler's clock + their in-process ring
  calibrate   — alpha-beta NetworkModel fits + per-mesh fitted profiles

``measure``, ``spans`` (and anything importing jax) is imported lazily so the
pure-host pieces stay usable from no-jax contexts (the analysis CLI).
"""
from repro.obs.events import EventLog, heartbeat_line, utc_now
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    host_time_us,
)
from repro.obs.provenance import SCHEMA_VERSION, bench_metadata

__all__ = [
    "Counter",
    "EventLog",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SCHEMA_VERSION",
    "Span",
    "SpanRecorder",
    "bench_metadata",
    "comm_byte_counters",
    "heartbeat_line",
    "host_time_us",
    "measured_gradsync",
    "measured_timeline",
    "recorded_spans",
    "span",
    "step_span",
    "utc_now",
]

_LAZY = {
    "measured_gradsync": "repro.obs.measure",
    "measured_timeline": "repro.obs.measure",
    "measurement_rows": "repro.obs.measure",
    "fit_network": "repro.obs.calibrate",
    "fit_staging": "repro.obs.calibrate",
    "fitted_network": "repro.obs.calibrate",
    "load_profile": "repro.obs.calibrate",
    "save_profile": "repro.obs.calibrate",
    "Span": "repro.obs.spans",
    "SpanRecorder": "repro.obs.spans",
    "recorded_spans": "repro.obs.spans",
    "span": "repro.obs.spans",
    "step_span": "repro.obs.spans",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)


def comm_byte_counters(schedule, registry: MetricsRegistry,
                       itemsize: int = 4) -> None:
    """Account one execution of ``schedule`` into byte counters keyed
    ``comm_bytes.<kind>.<reducer>.<phase>`` (RS/AG pairs each count their
    own wire pass; UPDATE/NORM move no payload)."""
    from repro.core.schedule import (
        ALL_GATHER,
        ALLREDUCE,
        REDUCE_SCATTER,
        np_itemsize,
    )

    for op in schedule.ops:
        if op.kind not in (ALLREDUCE, REDUCE_SCATTER, ALL_GATHER):
            continue
        nb = op.bucket.size * np_itemsize(op.bucket.comm_dtype, itemsize)
        tag = op.reducer or "default"
        registry.counter(
            f"comm_bytes.{op.kind}.{tag}.{op.phase}").inc(nb)
