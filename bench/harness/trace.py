"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is read once into plain interval lists (nanoseconds, on the
clock the profiler puts host and device events on):

- per device: the ops of the ``XLA Ops`` line, the asynchronous ops of
  ``Async XLA Ops`` (copies and collectives in flight), and the programs
  of ``XLA Modules``;
- on the host: the benchmark's own spans (names starting ``bench.``).

Everything is clipped to the traced window, the host span
``bench.traced``.  ``Trace.from_dict`` builds the same object from a
plain dict, which is how the tests feed it hand-made events.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW_SPAN = "bench.traced"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
# control flow whose event spans the ops it runs: its body's ops are
# listed on their own, so the container counts neither as busy (a wait
# inside a loop is idle, as one between ops of a straight program is) nor
# by name in the breakdown
CONTAINERS = ("while", "conditional", "call")


def op_name(raw: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``;
    ``jit_step(1387...)`` -> ``jit_step``."""
    s = raw.strip().lstrip("%").split(" = ", 1)[0].split("(", 1)[0]
    return re.sub(r"\.\d+$", "", s.strip())


def is_collective(name: str) -> bool:
    return op_name(name).startswith(COLLECTIVES)


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(merged) -> float:
    return sum(b - a for a, b in merged)


def minus(a, b) -> float:
    """Length of the merged set ``a`` not covered by the merged set ``b``."""
    out, j = 0.0, 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while cur < hi:
            if k >= len(b) or b[k][0] >= hi:
                out += hi - cur
                break
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
    return out


def _clip(evs, lo, hi):
    return [(max(a, lo), min(b, hi), n) for a, b, n in evs
            if b > lo and a < hi]


class Trace:
    """Device and host intervals of one traced window."""

    def __init__(self, devices: dict, host: list):
        self.host = sorted(host)
        win = [(a, b) for a, b, n in self.host if n == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        self.lo, self.hi = win[0]
        self.devices = {
            d: {k: _clip(v.get(k, []), self.lo, self.hi)
                for k in ("ops", "async", "modules")}
            for d, v in sorted(devices.items())}

    # ------------------------------------------------------------ build
    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        """``{"devices": {name: {"ops"|"async"|"modules": [[start_ns,
        end_ns, name], ...]}}, "host": [[start_ns, end_ns, name], ...]}``."""
        devs = {k: {kk: [tuple(e) for e in vv] for kk, vv in v.items()}
                for k, v in d["devices"].items()}
        return cls(devs, [tuple(e) for e in d["host"]])

    @classmethod
    def from_dir(cls, path: str) -> "Trace":
        """Read the ``.xplane.pb`` the profiler wrote under ``path``."""
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        pd = ProfileData.from_file(max(files, key=os.path.getmtime))
        lines = {"XLA Ops": "ops", "Async XLA Ops": "async",
                 "XLA Modules": "modules"}
        devices, host = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = devices.setdefault(plane.name, {})
                for line in plane.lines:
                    key = lines.get(line.name)
                    if key:
                        dev[key] = [(e.start_ns, e.end_ns, e.name)
                                    for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith("bench.")]
        return cls(devices, host)

    # --------------------------------------------------------- readings
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def _ops(self, dev) -> list:
        """The device's ops, control-flow containers left out."""
        return [(a, b, n) for a, b, n in self.devices[dev]["ops"]
                if not op_name(n).startswith(CONTAINERS)]

    def _busy(self, dev) -> list:
        return merge((a, b) for a, b, _ in self._ops(dev))

    def busy_s(self) -> float:
        """Seconds in which an op ran on a device, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(total(self._busy(d)) for d in self.devices) \
            * 1e-9 / self.n_devices

    def idle_share(self) -> float | None:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def module_runs(self, match) -> list[list[tuple[float, float]]]:
        """Per device, the [start, end) of every run of a program whose
        name ``match(name)`` accepts (names as ``op_name`` gives them)."""
        return [[(a, b) for a, b, n in self.devices[d]["modules"]
                 if match(op_name(n))] for d in self.devices]

    def module_s(self, match) -> float:
        """Device seconds of the matching programs, averaged over devices:
        the time their ops ran inside each run (not the run's span)."""
        if not self.devices:
            return 0.0
        out = 0.0
        for d, runs in zip(self.devices, self.module_runs(match)):
            busy = self._busy(d)
            out += total(busy) - minus(busy, merge(runs))
        return out * 1e-9 / self.n_devices

    def module_count(self, match) -> float:
        runs = self.module_runs(match)
        return sum(len(r) for r in runs) / max(len(runs), 1)

    def biggest_module(self) -> str | None:
        """The program with the most device time on the first device."""
        if not self.devices:
            return None
        agg = collections.Counter()
        for a, b, n in next(iter(self.devices.values()))["modules"]:
            agg[op_name(n)] += b - a
        return agg.most_common(1)[0][0] if agg else None

    def _coll(self, dev) -> list:
        return merge((a, b) for a, b, n
                     in self._ops(dev) + self.devices[dev]["async"]
                     if is_collective(n))

    def _compute(self, dev) -> list:
        return merge((a, b) for a, b, n in self._ops(dev)
                     if not is_collective(n))

    def collective_s(self) -> float:
        """Seconds in which a collective was in flight on a device
        (synchronous or asynchronous), averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(total(self._coll(d)) for d in self.devices) \
            * 1e-9 / self.n_devices

    def collective_exposed_s(self) -> float:
        """The part of ``collective_s`` during which no other op ran on
        the same device."""
        if not self.devices:
            return 0.0
        return sum(minus(self._coll(d), self._compute(d))
                   for d in self.devices) * 1e-9 / self.n_devices

    def has_collectives(self) -> bool:
        return any(self._coll(d) for d in self.devices)

    # -------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> list[list]:
        """Device seconds by op name (``op_name``), averaged over devices."""
        agg = collections.Counter()
        for d in self.devices:
            for a, b, name in self._ops(d):
                agg[op_name(name)] += (b - a) * 1e-9 / self.n_devices
        return [[k, s] for k, s in agg.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds on the devices, by what kept them idle: a gap
        between ops inside a program's run is ``in_program`` (the program
        waits, on a copy or a transfer); any other gap goes to the
        innermost benchmark span on the host that covers its middle
        (``host_other`` where none does)."""
        agg = collections.Counter()
        spans = [(a, b, s) for a, b, s in self.host if s != WINDOW_SPAN]
        for d in self.devices:
            busy = self._busy(d)
            runs = merge((a, b) for a, b, _ in self.devices[d]["modules"])
            edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) / 2
                if any(lo <= mid < hi for lo, hi in runs):
                    name = "in_program"
                else:
                    inner = [s for s in spans if s[0] <= mid < s[1]]
                    name = (max(inner, key=lambda s: s[0])[2] if inner
                            else "host_other")
                agg[name] += (b - a) * 1e-9 / self.n_devices
        return [[k, s] for k, s in agg.most_common(n)]
