"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, job,
reference or per-layer metric sits in a file of its own and is found by
name, so a later cell, mix or metric is new files plus new entries:

- ``configs[].file``: the configuration as it is run (JSON), which names
  its ``job`` and its ``reference``;
- ``bench/traffic/<traffic>.json`` (beside ``BENCHMARK.json``): the
  parameters of one traffic mix;
- ``bench/jobs/<job>.py``: the general code that builds the system
  for a kind of job and drives the window (module ``bench.jobs.<job>``);
- ``bench/reference/<reference>.py``: the plain reference (module
  ``bench.reference.<reference>``);
- ``bench/layer_metrics/<metric>.py``: one per-layer metric's reader,
  loaded by path, since a metric's name may hold dots.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path, name: str | None = None):
    """Import one Python file by path (its name may hold dots and
    dashes)."""
    name = name or "bench_" + re.sub(r"\W", "_", str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict                    # the configuration file's content
    traffic: dict                   # the traffic file's content
    end_to_end: list[dict]          # metrics this cell reports, trace 0
    per_layer: list[dict]           # metrics this cell reports, trace 1
    root: Path                      # the checkout (BENCHMARK.json's dir)

    def job(self):
        return importlib.import_module(f"bench.jobs.{self.config['job']}")

    def reference(self):
        return reference(self.config["reference"])

    def limits(self) -> dict:
        """Each compared number's limit, as the configuration states it."""
        return dict(self.config["limits"])


def reference(name: str):
    """The plain reference module ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{name}")


def reader(metric: str):
    """The per-layer reader of ``metric``: a module with ``read(ctx)``."""
    return load_module(BENCH / "layer_metrics" / f"{metric}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(manifest_path: Path, workload: str) -> Cell:
    """Resolve one workload entry by name."""
    m = load(manifest_path)
    root = manifest_path.parent
    w = next((w for w in m["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in {manifest_path}")
    c = next(c for c in m["configs"] if c["name"] == w["config"])
    return Cell(
        name=w["name"], chips=int(w["chips"]), config_name=c["name"],
        traffic_name=w["traffic"], config=load(root / c["file"]),
        traffic=load(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[e for e in m["end_to_end"] if _reports(e, w["name"])],
        per_layer=[p for p in m["per_layer"] if _reports(p, w["name"])],
        root=root)
