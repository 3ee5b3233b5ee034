"""repro.obs: metrics semantics, event stream, provenance headers,
the span recorder, NetworkModel/StagingModel calibration round-trips,
fitted-profile consumption by `auto`, the Trainer's compile-time
separation and step spans, and the merged sim+measured trace
(subprocess — needs 8 fake devices)."""
import collections
import io
import json
import os
import subprocess
import sys
import time

import pytest

from repro.obs import (
    EventLog,
    MetricsRegistry,
    SCHEMA_VERSION,
    bench_metadata,
    comm_byte_counters,
    heartbeat_line,
)


# ------------------------------------------------------------- metrics

def test_counter_accumulates_and_rejects_negative():
    reg = MetricsRegistry()
    c = reg.counter("bytes")
    c.inc(3)
    c.inc(4.5)
    assert c.value == 7.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 7.5


def test_gauge_overwrites():
    g = MetricsRegistry().gauge("loss")
    assert g.value is None
    g.set(2.0)
    g.set(1.5)
    assert g.value == 1.5


def test_histogram_summary_and_percentiles():
    h = MetricsRegistry().histogram("t")
    for v in range(1, 101):            # 1..100
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(50.0, abs=1.0)
    assert s["p99"] == pytest.approx(99.0, abs=1.0)


def test_histogram_window_bounds_memory_but_keeps_exact_extremes():
    h = MetricsRegistry().histogram("t", window=8)
    h.observe(1e9)                     # falls out of the window...
    for v in range(100):
        h.observe(float(v))
    assert len(h._window) == 8
    assert h.count == 101              # ...but count/max stay exact
    assert h.max == 1e9


def test_registry_reuses_instances_and_rejects_type_conflicts():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(2.0)
    snap = reg.snapshot()
    assert snap["x"] == 0.0 and snap["g"] == 1.0
    assert snap["h"]["count"] == 1
    json.dumps(snap)                   # snapshot must be JSON-ready


# -------------------------------------------------------------- events

def test_eventlog_writes_parseable_jsonl():
    buf = io.StringIO()
    log = EventLog(buf)
    log.emit("step", step=3, loss=1.25)
    log.emit("failure", step=4)
    lines = buf.getvalue().strip().splitlines()
    rows = [json.loads(l) for l in lines]
    assert [r["kind"] for r in rows] == ["step", "failure"]
    assert rows[0]["step"] == 3 and rows[0]["loss"] == 1.25
    assert "t_utc" in rows[0] and "t_mono" in rows[0]


def test_eventlog_none_path_is_a_noop():
    log = EventLog(None)
    log.emit("step", step=0)           # must not raise
    log.close()


def test_heartbeat_line_fields():
    line = heartbeat_line(7, loss=1.5, step_ms=12.0, tokens_per_s=1234.0,
                          compile_s=3.0)
    assert line.startswith("[obs] step 7")
    assert "loss 1.5000" in line and "12.0ms" in line
    assert "1,234 tok/s" in line and "compile 3.00s excluded" in line


# ---------------------------------------------------------- provenance

def test_bench_metadata_header():
    meta = bench_metadata({"data": 2, "model": 4}, section="pack")
    assert meta["schema_version"] == SCHEMA_VERSION
    for key in ("utc", "platform", "python"):
        assert meta[key]
    assert meta["mesh_shape"] == {"data": 2, "model": 4}
    assert meta["section"] == "pack"
    json.dumps(meta)


# ------------------------------------------------- comm byte counters

def _static_gradsync(strategy, **cfg_kw):
    from repro.analysis.cli import StaticMesh, _model
    from repro.core.kvstore import GradSync, GradSyncConfig

    mesh = StaticMesh({"data": 2, "model": 4})
    grads, specs = _model("model")
    cfg = GradSyncConfig(strategy=strategy, bucket_bytes=256 * 1024,
                         verify=False, **cfg_kw)
    return GradSync(cfg, mesh, specs, grads)


def test_comm_byte_counters_account_wire_kinds_only():
    gs = _static_gradsync("concom")
    reg = MetricsRegistry()
    comm_byte_counters(gs.schedule, reg, itemsize=4)
    snap = reg.snapshot()
    expected = 4 * sum(op.bucket.size for op in gs.schedule.ops
                       if op.kind == "allreduce")
    assert snap["comm_bytes.allreduce.default.post"] == expected
    assert expected > 0

    gs2 = _static_gradsync("rsag")
    reg2 = MetricsRegistry()
    comm_byte_counters(gs2.schedule, reg2, itemsize=4)
    snap2 = reg2.snapshot()
    assert any(k.startswith("comm_bytes.reduce_scatter.") for k in snap2)
    assert any(k.startswith("comm_bytes.all_gather.") for k in snap2)
    # UPDATE/NORM ops move no payload → never counted
    assert not any("update" in k or "norm" in k for k in snap2)


# ---------------------------------------------------------------- spans

def test_spans_nest_with_parents_steps_and_attrs():
    from repro.obs import SpanRecorder

    rec = SpanRecorder()
    with rec.step_span("train.step", 7):
        with rec.span("train.input", bytes=12):
            with rec.span("data.synth"):
                pass
        with rec.span("train.wait"):
            pass
    with rec.span("free"):
        pass
    got = rec.spans()
    # recorded as each block exits
    assert [s.name for s in got] == ["data.synth", "train.input",
                                     "train.wait", "train.step", "free"]
    by = {s.name: s for s in got}
    assert by["data.synth"].parent == "train.input"
    assert by["train.input"].parent == "train.step"
    assert by["train.wait"].parent == "train.step"
    assert by["train.step"].parent is None and by["free"].parent is None
    assert [s.step for s in got] == [7, 7, 7, 7, None]
    assert by["train.input"].attrs == {"bytes": 12}
    outer = by["train.step"]
    for s in got[:3]:
        assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns


def test_span_is_recorded_when_its_block_raises():
    from repro.obs import SpanRecorder

    rec = SpanRecorder()
    with pytest.raises(KeyError):
        with rec.step_span("train.step", 3):
            with rec.span("train.input"):
                raise KeyError("window closed")
    assert [(s.name, s.step) for s in rec.spans()] == [
        ("train.input", 3), ("train.step", 3)]
    with rec.span("after"):
        pass
    assert rec.spans()[-1].parent is None      # the stack unwound


def test_span_ring_is_bounded_and_read_by_window(monkeypatch):
    import repro.obs.spans as spans

    ticks = iter(range(0, 100, 10))      # span i runs [20 i, 20 i + 10)
    monkeypatch.setattr(spans.time, "time_ns", lambda: next(ticks))
    rec = spans.SpanRecorder()
    assert rec._ring.maxlen == spans.RING_SIZE
    rec._ring = collections.deque(maxlen=3)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.spans()] == ["s2", "s3", "s4"]
    # overlap with [lo, hi): a span ending at lo or starting at hi is out
    assert [s.name for s in rec.spans(50, 80)] == ["s3"]
    assert [s.name for s in rec.spans(45, 81)] == ["s2", "s3", "s4"]
    assert rec.spans(90) == [] and rec.spans(None, 40) == []


def test_span_timestamps_come_from_time_ns(monkeypatch):
    import repro.obs.spans as spans

    ticks = iter(range(100, 200, 10))
    monkeypatch.setattr(spans.time, "time_ns", lambda: next(ticks))
    rec = spans.SpanRecorder()
    with rec.span("a"):
        with rec.span("b"):
            pass
    assert [(s.name, s.start_ns, s.end_ns) for s in rec.spans()] == [
        ("b", 110, 120), ("a", 100, 130)]
    monkeypatch.undo()
    lo = time.time_ns()
    with rec.span("c"):
        pass
    c = rec.spans()[-1]
    assert lo <= c.start_ns <= c.end_ns <= time.time_ns()


def test_backend_compiles_are_recorded_as_spans():
    import jax
    import numpy as np

    from repro.obs import recorded_spans, step_span

    lo = time.time_ns()
    with step_span("train.step", 4):
        jax.jit(lambda x: x - 3)(np.ones(7, np.float32)).block_until_ready()
    hi = time.time_ns()
    got = [s for s in recorded_spans(lo, hi) if s.name == "jax.compile"]
    assert len(got) == 1
    c = got[0]
    assert lo <= c.start_ns < c.end_ns <= hi
    assert c.parent is None and c.step == 4 and "fun_name" in c.attrs


def test_spans_show_in_the_profiler_trace_on_its_clock(tmp_path):
    """Each span is also a host event of a profile taken meanwhile; the
    profiler writes times relative to its session's start, on the clock
    the ring reads."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs import recorded_spans, span, step_span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with step_span("train.step", 11):
            with span("train.input"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    origin = next(v for p in pd.planes for k, v in p.stats
                  if k == "profile_start_time")
    events = {e.name: e for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name in ("train.step", "train.input")}
    assert set(events) == {"train.step", "train.input"}
    for s in recorded_spans(origin):
        if s.name in events:
            e = events[s.name]
            # the annotation encloses the ring's reads of the clock
            assert origin + e.start_ns <= s.start_ns
            assert s.end_ns <= origin + e.end_ns
            assert s.start_ns - (origin + e.start_ns) < 1e6


# ---------------------------------------------------------- calibration

def _true_network():
    from repro.sim.netmodel import LinkModel, NetworkModel

    # "model" deliberately FASTER than "data": the fastest-link-first
    # RS/AG ordering under the fitted model then differs from the
    # default ref's, exercising the iterative re-ordering in fit_network
    return NetworkModel(links=(
        ("data", LinkModel("data", bandwidth=8e9, latency=4e-6)),
        ("model", LinkModel("model", bandwidth=3.2e10, latency=1.5e-6)),
    ))


def _wire_rows(true, mesh_shape, *, with_staging=False):
    rows = []
    for kind in ("allreduce", "reduce_scatter", "all_gather"):
        for nbytes in (1 << 14, 1 << 16, 1 << 18, 1 << 20):
            for axes in (("data",), ("model",), ("data", "model")):
                t = true.collective_time(kind, nbytes, axes, mesh_shape)
                row = {"kind": kind, "nbytes": float(nbytes),
                       "axes": axes, "mesh_shape": mesh_shape, "t": t}
                if with_staging:
                    row["num_leaves"] = 7
                    row["t"] += true.staging_time(kind, nbytes, 7)
                rows.append(row)
    return rows


def test_fit_network_recovers_known_alpha_beta():
    from repro.obs.calibrate import fit_network

    true = _true_network()
    mesh_shape = {"data": 4, "model": 8}
    model, info = fit_network(_wire_rows(true, mesh_shape))
    assert info["rms_residual_s"] < 1e-12
    for axis in ("data", "model"):
        want, got = true.link(axis), model.link(axis)
        assert got.bandwidth == pytest.approx(want.bandwidth, rel=1e-6)
        assert got.latency == pytest.approx(want.latency, rel=1e-6)


def test_fit_network_subtracts_staging_share():
    from repro.obs.calibrate import fit_network

    true = _true_network()
    mesh_shape = {"data": 4, "model": 8}
    rows = _wire_rows(true, mesh_shape, with_staging=True)
    model, _ = fit_network(rows, staging=true.staging)
    for axis in ("data", "model"):
        assert model.link(axis).bandwidth == pytest.approx(
            true.link(axis).bandwidth, rel=1e-6)


def test_fit_network_quality_gate():
    """A clean synthetic fit is quality "ok"; the same rows with large
    multiplicative noise blow the relative-residual gate to "poor"."""
    from repro.obs.calibrate import REL_RESIDUAL_MAX, fit_network

    true = _true_network()
    mesh_shape = {"data": 4, "model": 8}
    rows = _wire_rows(true, mesh_shape)
    _, info = fit_network(rows)
    assert info["quality"] == "ok"
    assert info["rel_residual"] <= REL_RESIDUAL_MAX

    # deterministic "noise": alternate rows 4x slower / 4x faster, the
    # kind of dispatch jitter a CPU-host smoke run produces
    noisy = [dict(r, t=r["t"] * (4.0 if i % 2 else 0.25))
             for i, r in enumerate(rows)]
    _, bad = fit_network(noisy)
    assert bad["quality"] == "poor"
    assert bad["rel_residual"] > REL_RESIDUAL_MAX


def test_fit_network_needs_fittable_rows():
    from repro.obs.calibrate import fit_network

    with pytest.raises(ValueError):
        fit_network([{"kind": "allreduce", "nbytes": 1e6,
                      "axes": ("data",), "mesh_shape": {"data": 1},
                      "t": 0.0}])


def test_fit_staging_recovers_known_params():
    from repro.obs.calibrate import fit_staging
    from repro.sim.compute import StagingModel

    true = StagingModel(hbm_bw=5e11, leaf_overhead=1e-6)
    rows = []
    for nbytes in (1 << 16, 1 << 20, 1 << 22):
        for leaves in (1, 16, 128):
            for fused in (True, False):
                rows.append({
                    "nbytes": float(nbytes), "num_leaves": leaves,
                    "fused": fused,
                    "t": true.stage_time(nbytes, leaves, fused=fused)})
    model, info = fit_staging(rows)
    assert model.hbm_bw == pytest.approx(true.hbm_bw, rel=1e-6)
    assert model.leaf_overhead == pytest.approx(true.leaf_overhead,
                                                rel=1e-6)
    assert info["rms_residual_s"] < 1e-12


# -------------------------------------------------------------- profiles

def test_profile_save_load_round_trip(tmp_path):
    from repro.obs.calibrate import (
        fitted_network,
        load_profile,
        profile_path,
        save_profile,
    )

    true = _true_network()
    mesh_shape = {"data": 2, "model": 4}
    path = save_profile(true, mesh_shape, dir=str(tmp_path),
                        info={"n_rows": 3})
    assert path == profile_path(mesh_shape, str(tmp_path))
    loaded = load_profile(path)
    for axis in ("data", "model"):
        assert loaded.link(axis).bandwidth == true.link(axis).bandwidth
        assert loaded.link(axis).latency == true.link(axis).latency
    doc = json.load(open(path))
    assert doc["schema_version"] == SCHEMA_VERSION
    assert doc["meta"]["mesh_shape"] == mesh_shape
    assert doc["fit"]["n_rows"] == 3

    got, got_path = fitted_network(mesh_shape, str(tmp_path))
    assert got_path == path
    assert got.link("data").bandwidth == true.link("data").bandwidth
    # a different mesh has no profile
    assert fitted_network({"data": 16}, str(tmp_path)) == (None, None)


def test_poor_quality_profile_treated_as_absent(tmp_path):
    """A persisted profile whose recorded fit quality is "poor" must
    never reach `auto` — `fitted_network` skips it (load_profile still
    reads it for forensics)."""
    from repro.obs.calibrate import fitted_network, load_profile, save_profile

    mesh_shape = {"data": 2, "model": 4}
    path = save_profile(_true_network(), mesh_shape, dir=str(tmp_path),
                        info={"quality": "poor", "rel_residual": 1.1})
    assert fitted_network(mesh_shape, str(tmp_path)) == (None, None)
    assert load_profile(path) is not None


def test_corrupt_profile_treated_as_absent(tmp_path):
    from repro.obs.calibrate import fitted_network, profile_path

    mesh_shape = {"data": 2, "model": 4}
    path = profile_path(mesh_shape, str(tmp_path))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("{not json")
    assert fitted_network(mesh_shape, str(tmp_path)) == (None, None)


# --------------------------------------------- auto × fitted profile

def test_auto_prefers_fitted_profile(tmp_path, monkeypatch):
    """Planning with `auto` must rank under the fitted alpha/beta when a
    per-mesh profile exists — and under the defaults when none does."""
    from repro.obs.calibrate import save_profile
    from repro.sim.autotune import last_auto_report, rank_strategies
    from repro.sim.engine import SimConfig

    mesh_shape = {"data": 2, "model": 4}

    monkeypatch.setenv("REPRO_NETPROFILE_DIR", str(tmp_path / "empty"))
    gs = _static_gradsync("auto")
    default_report = last_auto_report()
    assert default_report["net"] == "default"

    fitted = _true_network()
    profile_dir = str(tmp_path / "profiles")
    path = save_profile(fitted, mesh_shape, dir=profile_dir)
    monkeypatch.setenv("REPRO_NETPROFILE_DIR", profile_dir)
    gs2 = _static_gradsync("auto")
    report = last_auto_report()
    assert report["net"] == f"fitted:{path}"

    # the reported ranking must be EXACTLY the simulation under the
    # fitted model (same plan, same sim config GradSync hands auto)
    expected = rank_strategies(
        gs2.plan, mesh_shape, net=fitted,
        sim=SimConfig(itemsize=4, reducer="flat", fused_staging=True),
        in_scan_active=False)
    assert report["ranking"] == [(n, tl.step_time) for n, tl in expected]
    # ...and differ from the default-network ranking's numbers
    assert dict(report["ranking"]) != dict(default_report["ranking"])
    assert report["winner"] == expected[0][0]
    gs.schedule.validate()
    gs2.schedule.validate()


# ------------------------------------------------ trainer integration

def test_trainer_separates_compile_time(tiny_train, tmp_path):
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    events = str(tmp_path / "events.jsonl")
    tr = Trainer(ts, pipe, None, log_every=1000, events_path=events)
    _, _, hist = tr.run(params, opt.init(params), 6)

    assert hist["compile_time"] is not None and hist["compile_time"] > 0
    snap = hist["metrics"]
    assert snap["steps_total"] == 6
    # step 0 spans jit warmup → excluded from throughput stats
    assert snap["step_time_s"]["count"] == 5
    assert len(tr.step_times) == 5
    assert snap["compile_time_s"] == hist["compile_time"]
    assert snap["tokens_total"] == 5 * 4 * 16      # 5 timed steps, B*S
    assert snap["tokens_per_s"] > 0
    assert snap["loss"] == hist["losses"][-1]
    assert snap["mem.state_bytes"] > 0
    assert any(k.startswith("comm_bytes.allreduce.") for k in snap)
    assert "sim.step_time_s" in snap
    assert [e["kind"] for e in hist["events"]].count("compile") == 1

    rows = [json.loads(l) for l in open(events)]
    steps = [r for r in rows if r["kind"] == "step"]
    assert len(steps) == 6
    assert sum(r["compile_step"] for r in steps) == 1
    assert steps[0]["compile_step"] is True
    assert {r["kind"] for r in rows} >= {"compile", "step"}


def test_trainer_bounds_loss_history(tiny_train):
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    tr = Trainer(ts, pipe, None, log_every=1000, loss_window=3)
    _, _, hist = tr.run(params, opt.init(params), 6)
    assert len(hist["losses"]) == 3


def test_trainer_records_step_spans_in_order(tiny_train):
    from repro.obs import recorded_spans
    from repro.runtime import Trainer

    ts, pipe, params, opt = tiny_train
    lo = time.time_ns()
    Trainer(ts, pipe, None, log_every=1000).run(params, opt.init(params), 3)
    got = [s for s in recorded_spans(lo, time.time_ns())
           if s.name != "jax.compile"]
    build = [("data.synth", "train.input"), ("data.place", "train.input"),
             ("train.input", "train.step")]
    dispatch = [("train.dispatch", "train.step")]
    tail = [("train.wait", "train.step"), ("train.account", "train.step"),
            ("train.step", None)]
    # step k+1's input is built between step k's dispatch and its wait
    order = (build + dispatch + build + tail + dispatch + build + tail
             + dispatch + tail)
    assert [(s.name, s.parent) for s in got] == order
    assert [s.step for s in got] == [0] * 10 + [1] * 7 + [2] * 4
    assert [s.attrs["ahead"] for s in got
            if s.name == "train.input"] == [False, True, True]
    for a, b in zip(got, got[1:]):
        if b.parent == a.parent:            # siblings follow each other
            assert a.end_ns <= b.start_ns
    place = [s for s in got if s.name == "data.place"]
    assert all(s.attrs["bytes"] == 2 * 4 * 16 * 4 + 4 for s in place)


# --------------------------------- measured replay (8 fake devices)

@pytest.fixture(scope="module")
def obs_cli_run(tmp_path_factory):
    """`python -m repro.obs --trace` in a subprocess (the main pytest
    process is pinned to 1 device; the CLI forces 8 fake devices)."""
    trace = str(tmp_path_factory.mktemp("obs") / "trace.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "--trace", trace,
         "--reps", "1", "--diff"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout, trace


def test_merged_trace_has_matching_sim_and_measured_tracks(obs_cli_run):
    stdout, trace = obs_cli_run
    assert "— match" in stdout, stdout
    doc = json.load(open(trace))
    events = doc["traceEvents"]
    names = {m["args"]["name"] for m in events
             if m.get("ph") == "M" and m.get("name") == "process_name"}
    assert any(n.startswith("measured:") for n in names), names
    assert any(n.startswith("simulated:") for n in names), names
    by_pid = {}
    for m in events:
        if m.get("ph") == "M" and m.get("name") == "process_name":
            by_pid[m["pid"]] = m["args"]["name"]
    counts = {}
    for m in events:
        if m.get("ph") == "X" and m["name"] not in ("forward", "backward"):
            counts[by_pid[m["pid"]]] = counts.get(by_pid[m["pid"]], 0) + 1
    meas = next(v for k, v in counts.items() if k.startswith("measured:"))
    sim = next(v for k, v in counts.items() if k.startswith("simulated:"))
    assert meas == sim > 0
