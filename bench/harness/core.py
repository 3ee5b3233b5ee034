"""One benchmark run: device check, window, trace, checks, result line.

``execute`` runs one cell in this process and returns the result object
that ``bench/run.py`` prints.  A job module (``bench/jobs/<name>.py``)
fills a ``Run``: its set-up time, the window's end-to-end numbers, the
counters the per-layer readers need, and the checks that decide
``correct``.  With ``trace`` on, the job calls ``Run.trace_start`` and
``Run.trace_stop`` around the part of the window it traces, and the
readers of ``bench/layer_metrics`` reduce that trace after the checks.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable

from bench.harness import manifest

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    """The peak rates of one chip, keyed by JAX's ``device_kind``; an
    unknown kind is an error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


@dataclasses.dataclass
class Check:
    """One compared number and its limit; it passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    cell: manifest.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                        # process start, perf_counter
    devices: list = dataclasses.field(default_factory=list)
    peaks: dict = dataclasses.field(default_factory=dict)
    trace_dir: Path | None = None
    # filled by the job
    setup_s: float | None = None
    attempted: int = 0
    failed: int = 0
    e2e: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    checks: list[Check] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    trace_window: tuple[float, float] | None = None   # perf_counter
    _annotation: Any = None

    def log(self, msg: str) -> None:
        log(msg)

    def check(self, name: str, value: float) -> None:
        self.checks.append(Check(name, float(value),
                                 float(self.cell.limits()[name])))

    # ------------------------------------------------------------ trace
    def trace_start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench.traced")
        self._annotation.__enter__()
        self.trace_window = (time.perf_counter(), math.inf)

    def trace_stop(self) -> None:
        import jax

        if self._annotation is None:
            return
        self._annotation.__exit__(None, None, None)
        self.trace_window = (self.trace_window[0], time.perf_counter())
        self._annotation = None
        jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer reader gets: the reduced trace (None when nothing
    was traced), the job's counters, the chip's peaks, the cell."""
    trace: Any
    counters: dict
    peaks: dict
    chips: int
    cell: manifest.Cell
    reference: Any


def device_check(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, t_start: float, *,
            device_check: Callable[[int], list] = device_check) -> dict:
    """Run one cell; returns the result object (the last stdout line)."""
    cell = manifest.cell(root / "BENCHMARK.json", workload)
    devices = device_check(cell.chips)
    kind = devices[0].device_kind
    run = Run(cell=cell, seed=int(seed), seconds=float(seconds),
              trace=bool(trace), t_start=t_start, devices=devices,
              peaks=peaks_for(kind) if devices[0].platform == "tpu" else {},
              trace_dir=root / ".bench_traces" / workload)
    log(f"[bench] {workload} seed {seed} seconds {seconds} trace {trace} "
        f"on {len(devices)} x {kind}")
    cell.job().run(run)
    gc.collect()
    result: dict[str, Any] = {
        "correct": all(c.ok for c in run.checks) and bool(run.checks),
        "attempted": run.attempted, "failed": run.failed, "metrics": {},
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": int(run.memory_peak_bytes)}}
    if not trace:
        for m in cell.end_to_end:
            name = m["name"]
            value = run.setup_s if name == "setup_s" else run.e2e.get(name)
            if value is not None and math.isfinite(value):
                result["metrics"][name] = {"value": value, "unit": m["unit"]}
    else:
        from bench.harness.trace import Trace

        run.counters["trace_window"] = run.trace_window
        tr = Trace.from_dir(str(run.trace_dir))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        result["device"]["busy_s"] = tr.busy_s()
        result["device"]["window_s"] = tr.window_s
        ctx = ReaderContext(trace=tr, counters=run.counters, peaks=run.peaks,
                            chips=cell.chips, cell=cell,
                            reference=cell.reference())
        for m in cell.per_layer:
            value = manifest.reader(m["name"]).read(ctx)
            if value is not None and math.isfinite(value):
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    for c in run.checks:
        log(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    # strict JSON: a number that is not finite (a failed request's
    # infinite wait, no sample to compare) is written as null
    result["checks"] = {
        c.name: {"value": c.value if math.isfinite(c.value) else None,
                 "limit": c.limit} for c in run.checks}
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    # the persistent compile cache lives at a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        result = execute(root, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start)
    except NoChip as e:
        log(f"[bench] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
