"""Smoke run of the main training and serving paths on a TPU.

    python chip_smoke.py               # one chip: train + serve phases
    python chip_smoke.py --four-chip   # four chips: the dp=4 phase only

Train: resnet50-cifar at full width, global batch 256, the default
``depcha`` strategy, through ``make_train_step`` + ``Trainer`` with
donation on (what ``repro.launch.train`` runs).  A few steps on fresh
batches, then a few on one repeated batch; every loss must be finite and
the repeated-batch loss must fall.

Serve: qwen3-1.7b at published widths (random weights from a seed)
through ``ContinuousScheduler`` (what ``repro.launch.serve`` runs):
8 greedy requests with prompts of a few hundred tokens, 32 new tokens
each.  Every request must complete, and the static ``Server`` given the
same prompts must pick the same first token.

Four chips: resnet50-cifar at dp=4 (global batch 256) under funnel,
concom, depcha and depcha with scheduled ZeRO-1, three steps each from
one init and one data stream, against a plain reference: the same step
with the whole gradient tree in one bucket on one channel.  These steps
are traced at float32 matmul precision (see FOUR_CHIP_PRECISION).

Everything runs in this one process.  The script exits nonzero when JAX
finds no TPU (there is no CPU fallback) or when any phase fails.  The
last line of stdout is one JSON object: ``{"ok": true, "device": ...}``.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_STEPS = 4           # fresh batches
REPEAT_STEPS = 4          # then the same batch again and again
TRAIN_LR = 0.1
SERVE_PROMPT_LENS = (192, 320)
SERVE_REQUESTS = 8
SERVE_MAX_NEW = 32
SERVE_BLOCK = 16
FOUR_CHIP_STEPS = 3
# dp=4 runs differ from the one-bucket reference only in how the
# gradient all-reduce is cut into buckets.  That changes how XLA fuses
# and orders the gradient's reductions, so they agree to round-off, not
# bit for bit, and resnet50 at init amplifies round-off: at lr 0.1 about
# 1000x per step (4 virtual CPU devices, batch 16), hence lr 1e-3.  The
# params are held to the reference's update (L2 over all leaves): there
# ZeRO-1 came to 0.8% of it and a run left unsynced to 99%.  At the
# TPU's default precision a float32 convolution takes one bf16 pass, and
# on a v5e 2x2 funnel/concom/depcha then ended 24% of the update away
# from the reference; at float32 precision, 0.5%.
FOUR_CHIP_LR = 1e-3
FOUR_CHIP_LOSS_RTOL = 1e-4
FOUR_CHIP_UPDATE_RTOL = 0.1
FOUR_CHIP_PRECISION = "float32"


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    """Platform, kind and count as JAX reports them; refuses a non-TPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{info['platform']!r}")
    return info


def _initializer(cfg):
    """Jitted ``seed -> params`` for ``cfg`` (random weights)."""
    import jax

    from repro.models.registry import family_of

    api = family_of(cfg)
    return jax.jit(lambda seed: api.init(jax.random.PRNGKey(seed), cfg))


class _ThenRepeat:
    """``batch_at(i)``: the pipeline's batch i for i < n, then batch n
    for every later step."""

    def __init__(self, pipe, n: int):
        self.pipe, self.n = pipe, n

    def batch_at(self, step: int):
        return self.pipe.batch_at(min(step, self.n))


def train_phase(mesh, cfg, batch: int, *, steps: int = TRAIN_STEPS,
                repeat: int = REPEAT_STEPS) -> dict:
    import jax

    from repro.core import GradSyncConfig
    from repro.data import ImagePipeline
    from repro.optim import sgd
    from repro.runtime import Trainer, make_train_step

    t0 = time.perf_counter()
    pipe = ImagePipeline(cfg.img_size, cfg.num_classes, batch, mesh=mesh)
    opt = sgd(TRAIN_LR, momentum=0.9)
    params = _initializer(cfg)(0)
    ts = make_train_step(cfg, mesh, GradSyncConfig(strategy="depcha"), opt,
                         batch_like=pipe.batch_at(0), params_like=params,
                         donate=True)
    params = jax.device_put(params, ts.shardings(ts.param_specs))
    trainer = Trainer(ts, _ThenRepeat(pipe, steps), None, log_every=1,
                      printer=log)
    _, _, hist = trainer.run(params, ts.init_opt(), steps + repeat)
    losses = hist["losses"]
    log(f"[train] {cfg.name} batch {batch} mesh {dict(mesh.shape)}: "
        f"first step (compile + run) {hist['compile_time']:.3f}s, "
        f"later steps {[round(t, 4) for t in trainer.step_times]}s")
    log(f"[train] losses {losses}")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite training loss: {losses}")
    if not losses[-1] < losses[steps]:
        raise RuntimeError(
            f"repeated-batch loss did not fall: {losses[steps:]}")
    return {"compile_s": hist["compile_time"],
            "total_s": time.perf_counter() - t0}


def _serve_pass(eng, prompts, max_new: int):
    dones = [eng.submit(p, max_new) for p in prompts]
    eng.run_until_idle()
    outs = [d.get_nowait() for d in dones]
    for i, o in enumerate(outs):
        if isinstance(o, Exception):
            raise RuntimeError(f"request {i} failed") from o
        if len(o) != max_new:
            raise RuntimeError(f"request {i} gave {len(o)} of "
                               f"{max_new} tokens")
    return outs


def serve_phase(mesh, cfg, *, lens=SERVE_PROMPT_LENS,
                n_req: int = SERVE_REQUESTS, max_new: int = SERVE_MAX_NEW,
                block: int = SERVE_BLOCK) -> dict:
    import numpy as np

    from repro.obs import MetricsRegistry
    from repro.runtime import ContinuousScheduler, Server

    t0 = time.perf_counter()
    max_len = -(-(max(lens) + max_new) // block) * block
    server = Server(cfg, mesh, _initializer(cfg)(0), max_len=max_len)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=lens[i % len(lens)],
                            dtype=np.int32) for i in range(n_req)]
    eng = ContinuousScheduler(server, slots=n_req, block_size=block)
    tw = time.perf_counter()
    _serve_pass(eng, prompts, max_new)     # compiles every shape
    warm_s = time.perf_counter() - tw
    eng.metrics = MetricsRegistry()
    tr = time.perf_counter()
    outs = _serve_pass(eng, prompts, max_new)
    run_s = time.perf_counter() - tr
    ttft = eng.metrics.histogram("serve.ttft_s").summary()
    log(f"[serve] {cfg.name} continuous: {n_req} requests x {max_new} "
        f"tokens, prompts {sorted(set(lens))}: first pass (compile + run) "
        f"{warm_s:.3f}s; timed pass {run_s:.3f}s = "
        f"{n_req * max_new / run_s:.1f} tokens/s (host clock); time to "
        f"first token min {ttft['min']:.4f}s p50 {ttft['p50']:.4f}s "
        f"max {ttft['max']:.4f}s")

    # the static engine on the same prompts, one request per batch: the
    # prefill then has the continuous engine's shape (1, L).  Batched
    # with others, a bf16 near-tie among random weights' logits can flip
    # the first token (seen on the chip at batch 4)
    ts = time.perf_counter()
    agree = []
    for p, o in zip(prompts, outs):
        s = server.generate(p[None], max_new)[0]
        agree.append(int(np.argmin(np.append(o == s, False))))
    static_s = time.perf_counter() - ts
    log(f"[serve] static engine (compile + run) {static_s:.3f}s; leading "
        f"tokens agreeing per request (of {max_new}): {agree}")
    if min(agree) < 1:
        raise RuntimeError(f"first token differs between the continuous "
                           f"and static engines: {agree}")
    return {"compile_s": warm_s, "total_s": time.perf_counter() - t0}


def _l2(arrays) -> float:
    import numpy as np

    return math.sqrt(sum(float(np.sum(np.square(a, dtype=np.float64)))
                         for a in arrays))


def _collective_counts(hlo: str) -> dict:
    return {k: len(re.findall(rf"\b{k}(?:-start)?\(", hlo))
            for k in ("all-reduce", "reduce-scatter", "all-gather")}


def four_chip_phase(mesh, cfg, batch: int,
                    *, steps: int = FOUR_CHIP_STEPS) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import GradSyncConfig
    from repro.data import ImagePipeline
    from repro.optim import sgd, zero1
    from repro.runtime import make_train_step

    ids = {d.id for d in mesh.devices.flat}
    log(f"[4chip] mesh {dict(mesh.shape)} over device ids {sorted(ids)}")
    if len(ids) != 4:
        raise RuntimeError(f"mesh spans {len(ids)} distinct devices, not 4")
    pipe = ImagePipeline(cfg.img_size, cfg.num_classes, batch, mesh=mesh)
    batches = [pipe.batch_at(k) for k in range(steps)]
    one_bucket = GradSyncConfig(strategy="funnel", num_channels=1,
                                bucket_bytes=1 << 40)
    runs = {"reference": (one_bucket, False)}
    for s in ("funnel", "concom", "depcha"):
        runs[s] = (GradSyncConfig(strategy=s), False)
    runs["depcha+zero1"] = (GradSyncConfig(strategy="depcha",
                                           exclude_axes=("data",)), True)
    t0 = time.perf_counter()
    init = _initializer(cfg)
    p0 = [np.asarray(x) for x in jax.tree.leaves(init(0))]
    built = {}
    for name, (sync, z1) in runs.items():
        opt = sgd(FOUR_CHIP_LR, momentum=0.9)
        if z1:
            opt = zero1(opt, ("data",), mesh.shape["data"])
        params = init(0)
        ts = make_train_step(cfg, mesh, sync, opt, batch_like=batches[0],
                             params_like=params, zero1_mode=z1,
                             donate=True)
        params = jax.device_put(params, ts.shardings(ts.param_specs))
        opt_state = ts.init_opt()
        with jax.default_matmul_precision(FOUR_CHIP_PRECISION):
            lowered = ts.fn.lower(params, opt_state, batches[0],
                                  jnp.int32(0))
        built[name] = (ts, params, opt_state, lowered)
    # the five programs are independent: compile them side by side
    tc = time.perf_counter()
    with ThreadPoolExecutor(len(built)) as ex:
        futs = {n: ex.submit(b[3].compile) for n, b in built.items()}
        exes = {n: f.result() for n, f in futs.items()}
    log(f"[4chip] lowering {tc - t0:.3f}s, compiling {len(exes)} step "
        f"programs in parallel {time.perf_counter() - tc:.3f}s")
    results = {}
    for name, (ts, params, opt_state, _) in built.items():
        exe = exes[name]
        counts = _collective_counts(exe.as_text())
        losses, times, snaps = [], [], []
        for k in range(steps):
            tk = time.perf_counter()
            params, opt_state, m = exe(params, opt_state, batches[k],
                                       jnp.int32(k))
            jax.block_until_ready((params, opt_state))
            times.append(time.perf_counter() - tk)
            losses.append(float(m["loss"]))
            snaps.append([np.asarray(x) for x in jax.tree.leaves(params)])
        results[name] = (losses, snaps)
        gs = ts.gradsync
        log(f"[4chip] {name}: buckets "
            f"{len((gs.dp_plan or gs.plan).buckets)}, collectives in the "
            f"compiled step {counts}, step times "
            f"{[round(t, 4) for t in times]}s, losses {losses}")
    ref_l, ref_s = results.pop("reference")
    # the reference's update after each step, and each run's distance
    # from the reference then, both L2 over all leaves
    moved = [_l2([a - b for a, b in zip(snap, p0)]) for snap in ref_s]
    log(f"[4chip] reference's param update after each step: L2 "
        f"{[f'{m:.3e}' for m in moved]}")
    for name, (losses, snaps) in results.items():
        dl = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_l))
        du = [_l2([a - b for a, b in zip(snap, ref)]) / m
              for snap, ref, m in zip(snaps, ref_s, moved)]
        dmax = max(float(np.max(np.abs(a - b)))
                   for a, b in zip(snaps[-1], ref_s[-1]))
        log(f"[4chip] {name} vs reference: loss rel diff {dl:.3e} "
            f"(bound {FOUR_CHIP_LOSS_RTOL}), param diff / reference "
            f"update (L2) per step {[f'{d:.3e}' for d in du]} (bound "
            f"{FOUR_CHIP_UPDATE_RTOL} at the end), param max abs diff "
            f"{dmax:.3e}")
        if dl > FOUR_CHIP_LOSS_RTOL or du[-1] > FOUR_CHIP_UPDATE_RTOL:
            raise RuntimeError(f"{name} disagrees with the one-bucket "
                               f"reference")
    return {"total_s": time.perf_counter() - t0}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the dp=4 strategy phase (4 chips)")
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax

    from repro.configs import get_arch
    from repro.kernels.collectives.ops import DEFAULT_STAGING
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh

    info = device_info()
    log(f"[setup] compile cache {enable_compile_cache()}; gradient "
        f"staging tier {DEFAULT_STAGING!r}")
    resnet = get_arch("resnet50-cifar")
    batch = resnet.shapes[0].global_batch
    if args.four_chip:
        if info["count"] != 4:
            raise SystemExit(f"--four-chip needs 4 devices, found "
                             f"{info['count']}")
        mesh = make_local_mesh(1)
        out = four_chip_phase(mesh, resnet.make_config(), batch)
        log(f"[done] four-chip phase {out['total_s']:.3f}s")
    else:
        mesh = make_local_mesh(1, devices=jax.devices()[:1])
        tr = train_phase(mesh, resnet.make_config(), batch)
        sv = serve_phase(mesh, get_arch("qwen3-1.7b").make_config(tp=1))
        log(f"[done] first-call (compile + run) time: train "
            f"{tr['compile_s']:.3f}s, serve {sv['compile_s']:.3f}s; phase "
            f"totals: train {tr['total_s']:.3f}s, serve "
            f"{sv['total_s']:.3f}s")
    log(f"[done] wall {time.perf_counter() - t_start:.3f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
