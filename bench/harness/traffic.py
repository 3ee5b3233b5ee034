"""The one generator of serving traffic: a traffic file's parameters and a
seed in, an open-loop arrival schedule out.

Every seed gets the same set of sizes and gaps, in its own order: the
``n`` prompt lengths, output lengths and inter-arrival gaps of a phase
are the distribution's quantiles at ``(i + 0.5) / n``, permuted by the
seed, and the gaps are scaled to fill the phase exactly.  So two seeds
offer the same work and differ only in its order and its token ids.

A traffic file holds::

    {"kind": "serve", "rate": <requests/s>, "lead_in_s": <s>,
     "prompt": <length spec>, "output": <length spec>, ...}

with a length spec ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}`` (bounds included).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float            # seconds after the phase's start
    prompt: np.ndarray    # int32 token ids
    max_new: int
    phase: str            # "lead_in" or "window"


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / max(n, 1)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = lo + (hi - lo) * u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def quantile_gaps(rate: float, seconds: float, n: int) -> np.ndarray:
    """``n`` exponential gaps of mean 1 / rate, scaled to sum to
    ``seconds``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    return g * (seconds / g.sum())


def phase(traffic: dict, seed: int, seconds: float, vocab: int,
          name: str) -> list[Arrival]:
    n = max(1, int(round(traffic["rate"] * seconds)))
    rng = np.random.default_rng((int(seed), 0 if name == "window" else 1))
    prompts = rng.permutation(quantile_lengths(traffic["prompt"], n))
    outs = rng.permutation(quantile_lengths(traffic["output"], n))
    gaps = rng.permutation(quantile_gaps(traffic["rate"], seconds, n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(float(due[i]),
                    rng.integers(1, vocab, size=int(prompts[i]),
                                 dtype=np.int32),
                    int(outs[i]), name) for i in range(n)]


def schedule(traffic: dict, seed: int, seconds: float,
             vocab: int) -> list[Arrival]:
    """The lead-in's arrivals (due before 0, not measured) and the
    window's (due in [0, seconds)), in order of ``due``."""
    lead = float(traffic.get("lead_in_s", 0.0))
    out = []
    if lead > 0:
        for a in phase(traffic, seed, lead, vocab, "lead_in"):
            a.due -= lead
            out.append(a)
    out += phase(traffic, seed, seconds, vocab, "window")
    return sorted(out, key=lambda a: a.due)


def prompt_buckets(traffic: dict, block: int) -> list[int]:
    """Every block-aligned prompt length the mix can produce."""
    lo, hi = int(traffic["prompt"]["min"]), int(traffic["prompt"]["max"])
    return list(range(math.ceil(lo / block) * block,
                      math.ceil(hi / block) * block + 1, block))
