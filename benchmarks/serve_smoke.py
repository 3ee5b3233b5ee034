"""Serving smoke (CI gate + BENCH_serve.json artifact, DESIGN.md §14).

Open-loop load benchmark of the two serving paths on 8 fake CPU devices
(dp=2 × tp=4): requests with mixed prompt lengths and budgets arrive on
a fixed schedule regardless of completion (open loop), and each engine
drains them —

  static      — ``RequestQueue`` + ``Server.generate``: batches pad to
                the widest member and decode to the batch-max budget;
  continuous  — ``ContinuousScheduler``: in-flight batching over the
                paged KV pool, per-slot budgets, immediate retire.

Gates: the paged engine must be BIT-exact with the static path under
greedy, and continuous must beat static on BOTH tokens/s and p99 latency
under the mixed open-loop load.  Also reported (non-gating): the
host-sync delta row (device-side token accumulation vs the old
np.asarray-per-token loop) and the decode-plan simulated-vs-measured row
(``repro.sim.serve`` prices a v5e; the measured column is this CPU —
the row records both clocks and their ratio, like the obs trace diff).
Writes BENCH_serve.json with the provenance header (`obs.bench_metadata`).
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import warnings

warnings.filterwarnings("ignore")
import json
import queue as queue_mod
import sys
import time
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.models import transformer as tf
from repro.models.registry import family_of
from repro.runtime import ContinuousScheduler, Server
from repro.runtime.serve_loop import RequestQueue

FAILURES: list[str] = []


def check(name, cond):
    print(("PASS " if cond else "FAIL ") + name, flush=True)
    if not cond:
        FAILURES.append(name)


def mk_cfg():
    return tf.TransformerConfig(
        name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=4,
        d_ff=128, vocab=96, tp=4, attn_chunk=16, dtype=jnp.float32)


# ------------------------------------------------ open-loop load drivers
def mixed_workload(n, seed=0):
    """(prompt, max_new, arrival_s) triples: few shapes (bounds static
    recompiles), mixed budgets, fixed-rate arrivals."""
    rng = np.random.default_rng(seed)
    lens = rng.choice([8, 16, 32], size=n)
    budgets = rng.choice([4, 8, 16], size=n)
    prompts = [rng.integers(1, 96, size=int(L)).astype(np.int32)
               for L in lens]
    arrivals = np.arange(n) * 0.02
    return prompts, [int(b) for b in budgets], arrivals


def run_static(server, batch, prompts, budgets, arrivals):
    q = RequestQueue(server, batch=batch, timeout_s=0.01)
    return _drive(prompts, budgets, arrivals,
                  submit=lambda p, mn: q.submit(p, mn),
                  pump=lambda: q.serve_once())


def run_continuous(eng, prompts, budgets, arrivals):
    return _drive(prompts, budgets, arrivals,
                  submit=lambda p, mn: eng.submit(p, mn),
                  pump=lambda: eng.step())


def _drive(prompts, budgets, arrivals, *, submit, pump):
    n = len(prompts)
    handles: dict[int, tuple] = {}
    lat, toks = [], 0
    t0 = time.perf_counter()
    i = 0
    while len(lat) < n:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            handles[i] = (submit(prompts[i], budgets[i]), arrivals[i])
            i += 1
        if not handles and i < n:
            time.sleep(max(arrivals[i] - now, 0.0))
            continue
        pump()
        for j, (h, ta) in list(handles.items()):
            try:
                r = h.get_nowait()
            except queue_mod.Empty:
                continue
            if isinstance(r, Exception):
                raise r
            lat.append(time.perf_counter() - t0 - ta)
            toks += int(r.shape[0])
            del handles[j]
    wall = time.perf_counter() - t0
    return {
        "wall_s": round(wall, 3),
        "tokens": toks,
        "tokens_per_s": round(toks / wall, 2),
        "p50_latency_s": round(float(np.percentile(lat, 50)), 4),
        "p99_latency_s": round(float(np.percentile(lat, 99)), 4),
    }


def main():
    t_start = time.time()
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = mk_cfg()
    params = family_of(cfg).init(jax.random.PRNGKey(7), cfg)
    srv = Server(cfg, mesh, params, max_len=64)
    eng = ContinuousScheduler(srv, slots=8, block_size=16, chunk=4)

    # 1. bit-exactness gate: paged continuous ≡ static under greedy
    rng = np.random.default_rng(11)
    bx_prompts = [rng.integers(1, 96, size=int(L)).astype(np.int32)
                  for L in (5, 12, 17, 3, 30, 9)]
    outs = eng.generate_batch(bx_prompts, 10)
    exact = all(
        np.array_equal(srv.generate(np.tile(p[None], (2, 1)), 10)[0], o)
        for p, o in zip(bx_prompts, outs))
    check("serve-paged-greedy-bitexact", exact)

    # 2. host-sync delta (satellite: device-side token accumulation):
    #    the same static batch with and without a per-token np.asarray
    sync_prompt = np.tile(
        rng.integers(1, 96, size=16, dtype=np.int32)[None], (8, 1))
    srv.generate(sync_prompt, 32)                       # warm the shape
    t0 = time.perf_counter()
    srv.generate(sync_prompt, 32)
    t_batched = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.generate(sync_prompt, 32, sync_per_token=True)
    t_persync = time.perf_counter() - t0
    host_sync_row = {
        "batched_s": round(t_batched, 3),
        "per_token_sync_s": round(t_persync, 3),
        "speedup": round(t_persync / max(t_batched, 1e-9), 3),
    }
    print(f"[serve] host-sync delta: batched {t_batched:.3f}s vs "
          f"per-token {t_persync:.3f}s "
          f"({host_sync_row['speedup']:.2f}x)")

    # 3. decode-plan simulated vs measured: steady-state full-batch
    #    decode throughput vs the IR plan's simulated per-token latency
    from repro.sim import DecodeModel, rank_decode_plans

    warm = [rng.integers(1, 96, size=8, dtype=np.int32) for _ in range(8)]
    eng.generate_batch(warm, 31)                        # warm decode path
    for p in warm:
        eng.submit(p, 31)
    eng._admit()
    t0 = time.perf_counter()
    steady = 0
    while not eng.idle:
        steady += eng.step()
    t_steady = time.perf_counter() - t0
    measured_tok = t_steady / max(steady, 1)
    dm = DecodeModel.for_config(cfg, dict(mesh.shape), batch=8)
    # k_cand=4: at this toy vocab (96) the default 16-candidate gather
    # would exceed the full-vocab payload and invert the ranking
    ranked = rank_decode_plans(dm, dict(mesh.shape), k_cand=4)
    sim_rows = {r["sampler"]: r["token_time"] for r in ranked}
    # batch-steps, not tokens: one decode step advances every slot
    sim_step = sim_rows["topk"] * 1e0
    decode_plan_row = {
        "simulated": {k: round(v, 9) for k, v in sim_rows.items()},
        "simulated_topk_step_s": sim_step,
        "measured_tokens": steady,
        "measured_per_token_s": round(measured_tok, 6),
        "measured_per_step_s": round(measured_tok * 8, 6),
        "measured_over_simulated": round(
            (measured_tok * 8) / max(sim_step, 1e-12), 1),
        "note": "simulated prices a v5e mesh; measured is CPU fake "
                "devices — the ratio is the clock gap, not an error",
    }
    check("serve-decode-plans-verify-and-rank",
          len(ranked) == 3 and sim_rows["topk"] < sim_rows["full"])

    # 4. the open-loop shootout (the headline rows)
    prompts, budgets, arrivals = mixed_workload(24, seed=3)
    run_static(srv, 8, prompts, budgets, arrivals)      # warm static shapes
    eng.generate_batch([p for p in prompts[:3]], 4)     # warm prefill buckets
    static_row = run_static(srv, 8, prompts, budgets, arrivals)
    cont_row = run_continuous(eng, prompts, budgets, arrivals)
    print(f"[serve] static:     {static_row}")
    print(f"[serve] continuous: {cont_row}")
    check("serve-continuous-beats-static-tokens-per-s",
          cont_row["tokens_per_s"] > static_row["tokens_per_s"])
    check("serve-continuous-beats-static-p99",
          cont_row["p99_latency_s"] < static_row["p99_latency_s"])

    from repro.obs import bench_metadata

    out = {
        "bench": "serve",
        "meta": bench_metadata(mesh_shape=dict(mesh.shape)),
        "workload": {"requests": len(prompts),
                     "prompt_lens": [8, 16, 32],
                     "budgets": [4, 8, 16],
                     "inter_arrival_s": 0.02,
                     "slots": 8, "block_size": 16, "chunk": 4},
        "rows": {
            "bitexact_greedy_vs_static": bool(exact),
            "host_sync_delta": host_sync_row,
            "decode_plan_sim_vs_measured": decode_plan_row,
            "open_loop": {"static": static_row, "continuous": cont_row},
        },
        "checks": {"failed": FAILURES,
                   "wall_s": round(time.time() - t_start, 2)},
    }
    with open("BENCH_serve.json", "w") as f:
        json.dump(out, f, indent=1)
    print("[bench] wrote BENCH_serve.json")
    if FAILURES:
        print(f"FAILED: {len(FAILURES)} check(s): {FAILURES}")
        return 1
    print("DONE")
    return 0


if __name__ == "__main__":
    sys.exit(main())
