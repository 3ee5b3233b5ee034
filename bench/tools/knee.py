"""Sweep of offered load for a serving cell, to find its knee: the
highest Poisson rate at which the backlog does not grow over a window.

    python3 bench/tools/knee.py --workload <serve cell> --seed <n> \
        --seconds 40 --rates 0.6,0.8,1.0

One process builds the engine once; each rate gets a fresh engine state
(the compiled programs are kept), the cell's mix at that rate with its
lead-in, and one window.  Prints one JSON line per rate: tokens/s,
requests waiting at the close, and time to first token of the first and
last thirds of the window's requests (a growing backlog shows as the last
third waiting far longer).  Runs on the chip; not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--lead-in", type=float, default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench.jobs import serve_lm
    from bench.harness import core, manifest, traffic as traffic_gen
    from repro.runtime import ContinuousScheduler

    cell = manifest.cell(ROOT / "BENCHMARK.json", args.workload)
    devices = core.device_check(cell.chips)
    cfg, engine = cell.config, cell.config["engine"]
    eng, _ = serve_lm.build(cfg, cell.traffic, args.seed, devices)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(cell.traffic, rate=rate)
        if args.lead_in is not None:
            tr["lead_in_s"] = args.lead_in
        if rows:
            # a fresh engine state that keeps the compiled programs
            fns = eng._prefill_fns, eng._decode_fn
            eng.pool_k = eng.pool_v = None
            eng = ContinuousScheduler(eng.server, slots=engine["slots"],
                                      block_size=engine["block_size"],
                                      chunk=engine["chunk"])
            eng._prefill_fns, eng._decode_fn = fns
        rec = serve_lm.Recorder(eng)
        arrivals = traffic_gen.schedule(tr, args.seed, args.seconds,
                                        cfg["sizes"]["vocab_size"])
        t = time.perf_counter()
        w = serve_lm.window(eng, rec, arrivals, args.seconds,
                            float(tr.get("lead_in_s", 0.0)), drain=False)
        got = [x for x in w["ttft"]]
        third = max(1, len(got) // 3)
        admitted = [r for r in w["reqs"] if r["a"].phase == "window"
                    and r["req"] is not None and r["req"].t_first]
        row = {"rate": rate, "tokens_per_s": w["tokens_per_s"],
               "due": w["due"], "admitted": len(admitted),
               "waiting_at_end": w["waiting_at_end"],
               "ttft_first_third_s": statistics.median(got[:third]) if got else None,
               "ttft_last_third_s": statistics.median(got[-third:]) if got else None,
               "ttft_p95_admitted_s": serve_lm.p95(got) if got else None,
               "occupancy": statistics.mean(rec.occupancy) if rec.occupancy else None,
               "wall_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
