"""Continuous-batching LM serving, as ``repro.launch.serve --engine
continuous`` runs it: ``Server`` plus ``ContinuousScheduler`` over a
paged KV pool, greedy, no end-of-sequence token (every request runs to
its drawn length).

Set-up makes the weights from the seed, builds the engine and warms up
every prefill bucket the mix can produce and the decode chunk.  The
open-loop generator (``bench.harness.traffic``) then offers the mix's
lead-in, unmeasured, and the window's arrivals, each submitted when due;
the engine steps whenever it has work.  Requests due in the window are
followed to completion after it closes (``drain``), or left (for a mix
above the knee, whose backlog only grows).

End-to-end numbers: ``ttft_p95_s`` and ``tpot_p95_ms`` over the requests
due in the window (time to first token counted from when the request
was due; a failed request counts as infinite), ``serve_tokens_per_s``
over the tokens emitted between the first and the last step boundary of
the window (a step that straddles the window's start is left out, its
tokens and its time alike).

Compared numbers: ``served_logit_gap``, the widest gap by which a
served token's logit lies below the reference's best logit at its
position, over a seeded sample of finished requests that includes the
longest; and ``failed_requests``, the requests due in the window that
failed or ended with another number of tokens than they asked for.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench.harness import manifest, traffic as traffic_gen


def p95(values) -> float:
    """Nearest-rank 95th percentile (inf where a failure reaches it)."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(0.95 * len(v)) - 1)])


def program_config(cfg: dict):
    """The system's model configuration from the configuration file."""
    import jax.numpy as jnp

    from repro.configs import get_arch

    s = cfg["sizes"]
    if s["rms_norm_eps"] != 1e-6:
        raise ValueError("the system's RMSNorm epsilon is 1e-6")
    return get_arch(cfg["program"]["arch"]).make_config(
        tp=1, n_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"], kv_heads=s["num_key_value_heads"],
        d_ff=s["intermediate_size"], vocab=s["vocab_size"],
        head_dim=s["head_dim"], rope_theta=float(s["rope_theta"]),
        qk_norm=True, dtype=jnp.dtype(cfg["dtype"]))


class Recorder:
    """Wraps the engine's admission and prefill calls with the benchmark's
    spans, and records what the per-layer readers count: each admitted
    prompt's length, and each decode chunk's active slots (position,
    tokens left) as admission leaves them."""

    def __init__(self, eng):
        import jax

        self.eng = eng
        self.prefills: list[tuple[float, int]] = []        # (time, length)
        self.chunks: list[tuple[float, float, list]] = []  # (t0, t1, slots)
        self.occupancy: list[float] = []
        self._active: list = []
        admit, start = eng._admit, eng._start

        def _admit():
            with jax.profiler.TraceAnnotation("bench.serve.admit"):
                n = admit()
            self._active = [(s.pos, s.rem) for s in eng.slots if not s.free]
            return n

        def _start(w, req):
            with jax.profiler.TraceAnnotation("bench.serve.prefill"):
                self.prefills.append((time.perf_counter(), len(req.prompt)))
                return start(w, req)

        eng._admit, eng._start = _admit, _start

    def step(self) -> float:
        """One engine step; returns the host time it ended."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.serve.step"):
            self.eng.step()
        t1 = time.perf_counter()
        if self._active:
            self.chunks.append((t0, t1, self._active))
            self.occupancy.append(
                self.eng.metrics.gauge("serve.batch_fill").value)
        return t1


def build(cfg: dict, traffic: dict, seed: int, devices):
    """Weights from the seed, the ``Server`` and its engine, every prefill
    bucket of the mix and the decode chunk compiled and run once."""
    import jax

    from repro.launch.mesh import make_local_mesh
    from repro.models.registry import family_of
    from repro.runtime import ContinuousScheduler, Server

    sizes, engine = cfg["sizes"], cfg["engine"]
    ref = manifest.reference(cfg["reference"])
    prog = program_config(cfg)
    weights = ref.init_weights(seed, sizes, jax.numpy.dtype(cfg["dtype"]))
    like = jax.eval_shape(
        lambda: family_of(prog).init(jax.random.PRNGKey(0), prog))
    if (jax.tree.map(lambda x: (x.shape, x.dtype), weights)
            != jax.tree.map(lambda x: (x.shape, x.dtype), like)):
        raise ValueError("weight tree differs from the system's layout")
    server = Server(prog, make_local_mesh(1, devices=devices), weights,
                    max_len=engine["max_len"])
    del weights
    eng = ContinuousScheduler(server, slots=engine["slots"],
                              block_size=engine["block_size"],
                              chunk=engine["chunk"])
    rng = np.random.default_rng((seed, 2))
    buckets = traffic_gen.prompt_buckets(traffic, engine["block_size"])
    for sb in buckets:
        eng.generate_batch([rng.integers(1, sizes["vocab_size"], size=sb,
                                         dtype=np.int32)], 1)
    eng.generate_batch([rng.integers(1, sizes["vocab_size"], size=buckets[0],
                                     dtype=np.int32)], 2)
    return eng, len(buckets)


def _note_done(reqs, t: float) -> None:
    for r in reqs:
        if r["t_done"] is None and r["done"].queue:
            r["t_done"] = t


def window(eng, rec: Recorder, arrivals, seconds: float, lead: float,
           drain: bool, on_tick=None, on_close=None) -> dict:
    """Offer ``arrivals`` open loop: each is submitted when due (the lead-in
    from ``-lead``), the engine steps while it has work, and the window is
    [t0, t0 + seconds).  ``on_tick(now, t0)`` runs before each step (the
    trace starts there), ``on_close()`` as the window closes (the trace
    stops there).  With ``drain`` the requests due in the window are then
    followed to completion."""
    import jax

    reqs: list[dict] = []
    t0 = time.perf_counter() + lead
    i, late = 0, 0.0
    t_rate, emitted_t0 = None, 0
    while True:
        now = time.perf_counter()
        if t_rate is None and now >= t0:
            # the rate's window opens at the first step boundary at or
            # after t0, where the tokens emitted so far are counted
            t_rate = now
            emitted_t0 = sum(len(r["req"].tokens) for r in reqs if r["req"])
        while i < len(arrivals) and t0 + arrivals[i].due <= now:
            a = arrivals[i]
            late = max(late, now - (t0 + a.due))
            done = eng.submit(a.prompt, a.max_new)
            req = eng.queue.queue[-1] if done.empty() else None
            reqs.append({"a": a, "req": req, "done": done, "t_done": None})
            i += 1
        if now >= t0 + seconds:
            break
        if on_tick is not None:
            on_tick(now, t0)
        if eng.idle:
            nxt = t0 + arrivals[i].due if i < len(arrivals) else t0 + seconds
            with jax.profiler.TraceAnnotation("bench.serve.wait"):
                time.sleep(max(0.0, min(nxt, t0 + seconds) - now))
            continue
        _note_done(reqs, rec.step())
    t_end = now
    if on_close is not None:
        on_close()
    emitted = sum(len(r["req"].tokens) for r in reqs if r["req"])
    waiting = eng.queue.qsize() + len(eng._backlog)
    in_window = [r for r in reqs if r["a"].phase == "window"]
    while drain and any(r["t_done"] is None and r["req"] is not None
                        for r in in_window):
        _note_done(reqs, rec.step())
    out = summarize(in_window, t0)
    out.update(reqs=reqs, t0=t0, t_rate=t_rate, t_end=t_end,
               window_s=t_end - t_rate,
               tokens_per_s=((emitted - emitted_t0) / (t_end - t_rate)
                             if t_end > t_rate else math.nan),
               late_s=late, waiting_at_end=waiting)
    return out


def summarize(in_window, t0: float) -> dict:
    """Time to first token from when each request was due, time per
    output token, and failures (errors, or another number of tokens than
    asked for) over the requests due in the window."""
    ttft, tpot, failed = [], [], 0
    for r in in_window:
        out = r["done"].queue[0] if r["done"].queue else None
        req = r["req"]
        if req is None or isinstance(out, Exception) or (
                out is not None and len(out) != r["a"].max_new):
            failed += 1
            ttft.append(math.inf)
            tpot.append(math.inf)
            continue
        if req.t_first:
            ttft.append(req.t_first - (t0 + r["a"].due))
        if out is not None and len(out) > 1:
            tpot.append((r["t_done"] - req.t_first) / (len(out) - 1))
    return {"ttft": ttft, "tpot": tpot, "failed": failed,
            "due": len(in_window)}


def run(run) -> None:
    import jax

    cfg, tr = run.cell.config, run.cell.traffic
    sizes, engine = cfg["sizes"], cfg["engine"]
    ref = run.cell.reference()
    eng, n_buckets = build(cfg, tr, run.seed, run.devices)
    rec = Recorder(eng)
    arrivals = traffic_gen.schedule(tr, run.seed, run.seconds,
                                    sizes["vocab_size"])
    n_win = sum(a.phase == "window" for a in arrivals)
    run.log(f"[serve] warm-up of {n_buckets} prefill buckets done; "
            f"{len(arrivals)} arrivals ({n_win} in the window) at "
            f"{tr['rate']} requests/s")
    lead = float(tr.get("lead_in_s", 0.0))

    def tick(now, t0):
        if (run.trace and run.trace_window is None
                and now >= t0 + run.seconds - tr["trace_s"]):
            run.trace_start()

    w = window(eng, rec, arrivals, run.seconds, lead, tr.get("drain", True),
               tick, run.trace_stop)
    run.setup_s = w["t0"] - run.t_start
    run.attempted, run.failed = w["due"], w["failed"]
    run.e2e["ttft_p95_s"] = p95(w["ttft"])
    run.e2e["tpot_p95_ms"] = 1e3 * p95(w["tpot"])
    run.e2e["serve_tokens_per_s"] = w["tokens_per_s"]
    run.counters.update(
        window_s=w["window_s"], t0=w["t_rate"], t_end=w["t_end"],
        prefills=rec.prefills, chunks=rec.chunks, occupancy=rec.occupancy,
        slots=engine["slots"], chunk=engine["chunk"], sizes=sizes)
    run.log(f"[serve] window {w['window_s']:.3f}s: {w['due']} requests due, "
            f"{w['failed']} failed, ttft p95 {run.e2e['ttft_p95_s']:.4f}s, "
            f"tpot p95 {run.e2e['tpot_p95_ms']:.3f}ms, "
            f"{w['tokens_per_s']:.2f} tokens/s; {w['waiting_at_end']} waiting "
            f"at the close; generator at most {w['late_s'] * 1e3:.1f} ms late")
    run.read_memory_peak()

    # the reference, once the program's state is freed
    finished = [(r["a"].prompt, r["done"].queue[0]) for r in w["reqs"]
                if r["t_done"] is not None
                and not isinstance(r["done"].queue[0], Exception)]
    eng.pool_k = eng.pool_v = eng.server.params = None
    del eng, rec, w
    gc.collect()
    sample = sample_requests(finished, run.seed, tr["check_tokens"],
                             tr["check_requests"])
    weights = ref.init_weights(run.seed, sizes, jax.numpy.dtype(cfg["dtype"]))
    t = time.perf_counter()
    gap = 0.0
    for prompt, out in sample:
        gaps = served_gaps(ref, weights, sizes, prompt, out, engine["max_len"])
        gap = max(gap, float(gaps.max()))
    run.log(f"[serve] reference over {len(sample)} requests "
            f"({sum(len(o) for _, o in sample)} served tokens) "
            f"{time.perf_counter() - t:.3f}s")
    run.check("served_logit_gap", gap if sample else math.inf)
    run.check("failed_requests", run.failed)


def sample_requests(finished, seed: int, tokens: int, limit: int):
    """The finished request with the most served tokens, then others in
    a seeded order until ``tokens`` served tokens or ``limit`` requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda k: -len(finished[k][1]))
    rest = np.random.default_rng((seed, 3)).permutation(order[1:])
    out, n = [], 0
    for k in [order[0], *rest]:
        if n >= tokens or len(out) >= limit:
            break
        out.append(finished[k])
        n += len(finished[k][1])
    return out


def served_gaps(ref, w, sizes, prompt, out, max_len: int) -> np.ndarray:
    """Per served token, the gap below the reference's best logit."""
    L, n = len(prompt), len(out)
    seq = np.zeros(max_len, np.int32)
    seq[:L] = prompt
    seq[L:L + n - 1] = out[:-1]
    follow = np.zeros(max_len, np.int32)
    follow[L - 1:L - 1 + n] = out
    gaps, _ = ref.next_token_gaps(w, seq, follow, sizes)
    return gaps[L - 1:L - 1 + n]
