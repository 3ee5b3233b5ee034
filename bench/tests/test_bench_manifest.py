"""``BENCHMARK.json`` against the benchmark's contract, the data-driven
layout (a cell, mix, configuration or metric is new files plus new
entries), the traffic generator's determinism, and the entry point's
refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import manifest, traffic

ROOT = Path(__file__).resolve().parents[2]
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER = {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def _text_ok(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_paths():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"] and M["command"][1] == "bench/run.py"
    assert 1 <= M["run_seconds"] <= 51
    # a full check of 24 cells fits its time limit
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_texts():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in M[key]]
    assert all(NAME.match(n) for n in names)
    for key in ("workloads", "end_to_end", "per_layer", "configs"):
        got = [e["name"] for e in M[key]]
        assert len(got) == len(set(got))
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert set(m) <= E2E and 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) == LAYER and _text_ok(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _text_ok(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _text_ok(c["source"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("w", M["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_and_reports_enough(w):
    cell = manifest.cell(ROOT / "BENCHMARK.json", w["name"])
    assert cell.job().run and cell.reference()
    assert cell.config["name"] == w["config"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for name in cell.limits():
        assert isinstance(cell.limits()[name], (int, float))


READERS = sorted(p.stem for p in (ROOT / "bench" / "layer_metrics").glob("*.py"))


@pytest.mark.parametrize("name", READERS)
def test_every_layer_metric_has_a_reader_and_its_cells_report_moves(name):
    """Every reader loads; every metric of the manifest has one, and the
    cells it lists report the end-to-end metric it moves."""
    assert callable(manifest.reader(name).read)
    assert {m["name"] for m in M["per_layer"]} <= set(READERS)
    for m in (m for m in M["per_layer"] if m["name"] == name):
        moved = next(e for e in M["end_to_end"] if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in {w["name"] for w in M["workloads"]}
            assert cell in moved.get("workloads", [cell])


def test_layers_name_one_layer_the_same_way():
    layers = {m["layer"] for m in M["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert f"`{layer}`" in perf, layer


def test_traffic_is_deterministic_in_the_seed():
    tr = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    a = traffic.schedule(tr, 2**40 + 7, 10, 151936)
    b = traffic.schedule(tr, 2**40 + 7, 10, 151936)
    c = traffic.schedule(tr, 2**40 + 8, 10, 151936)
    key = lambda s: [(x.due, x.max_new, x.prompt.tobytes()) for x in s]
    assert key(a) == key(b) and key(a) != key(c)
    # another seed offers the same sizes and gaps, in another order
    for ph in ("lead_in", "window"):
        for f in (lambda x: len(x.prompt), lambda x: x.max_new):
            assert (sorted(f(x) for x in a if x.phase == ph)
                    == sorted(f(x) for x in c if x.phase == ph))
    win = [x for x in a if x.phase == "window"]
    assert win[0].due == 0 and win[-1].due < 10
    assert all(64 <= len(x.prompt) <= 1536 and 16 <= x.max_new <= 512
               for x in a)


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    """A serving cell, with its configuration, a new mix, its end-to-end
    metrics and a new per-layer metric, resolves without an edit to any
    file the benchmark has."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    m = json.loads(json.dumps(M))
    tr = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    (tmp_path / "bench" / "traffic" / "chat-burst.json").write_text(
        json.dumps(dict(tr, rate=0.5)))
    (tmp_path / "bench" / "layer_metrics" / "queue_s.serve.py").write_text(
        "def read(ctx):\n    return None\n")
    m["configs"].append({"name": "qwen3-1.7b",
                         "source": "https://huggingface.co/Qwen/Qwen3-1.7B",
                         "file": "bench/configs/qwen3-1.7b.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "qwen3-1.7b.serve-burst",
                           "config": "qwen3-1.7b", "traffic": "chat-burst",
                           "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "ttft_p95_s", "unit": "s",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["qwen3-1.7b.serve-burst"]})
    m["per_layer"].append({"name": "queue_s.serve", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "serving engine", "moves": "ttft_p95_s",
                           "workloads": ["qwen3-1.7b.serve-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.cell(tmp_path / "BENCHMARK.json", "qwen3-1.7b.serve-burst")
    assert cell.traffic["rate"] == 0.5
    assert [p["name"] for p in cell.per_layer] == ["queue_s.serve"]
    assert {e["name"] for e in cell.end_to_end} == {"ttft_p95_s", "setup_s"}
    assert cell.job().run and cell.limits()["served_logit_gap"] > 0


def _run(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         M["workloads"][0]["name"], "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_fails_with_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
