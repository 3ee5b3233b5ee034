"""Benchmark harness — one function per paper table/figure.

Each section also lands in a machine-readable ``BENCH_<section>.json``
(rows + metadata) so the perf trajectory is tracked across PRs; the
``strategy_step`` section records the repro.sim predicted step time next
to the measured one (simulated vs measured, per strategy × reducer).

Prints ``name,us_per_call,derived`` CSV rows:
  - fig13/14/15/16: strategy epoch times from the calibrated DAG cost
    model (benchmarks/paper_figures.py), validated against the paper's
    claims (1.6× DepCha/Funnel on Inception; CIFAR convergence at 32;
    ~50 s/epoch at 256).
  - strategy_step: MEASURED wall-clock per train step for each embedding
    strategy on this host (1 CPU device — orders overhead, not network).
  - kernel_*: measured interpret-mode kernel runtimes vs jnp reference.
  - roofline_summary: per-bottleneck cell counts from results/dryrun.json
    (run ``python -m repro.launch.dryrun --all --mesh both`` first).
"""
from __future__ import annotations

import json
import os


def _t(fn, *args, reps=3):
    """Median host wall time in us — the repro.obs timing convention
    (untimed warmup, then per-rep ``block_until_ready`` fences)."""
    from repro.obs import host_time_us

    return host_time_us(fn, *args, reps=reps)


def bench_paper_figures(emit):
    from benchmarks.paper_figures import fig13, fig14, fig15, fig16, validate

    for name, rows in (("fig13_cifar", fig13()), ("fig14_inception", fig14()),
                       ("fig15_resnet", fig15())):
        for row in rows:
            n, f, c, d = row
            emit(f"{name}_gpus{n}_funnel", f * 1e6, f"{f:.2f}s_epoch")
            emit(f"{name}_gpus{n}_concom", c * 1e6, f"{c:.2f}s_epoch")
            emit(f"{name}_gpus{n}_depcha", d * 1e6, f"{d:.2f}s_epoch")
    for n, t in fig16():
        emit(f"fig16_scaling_gpus{n}", t * 1e6, f"{t:.2f}s_epoch")
    v = validate()
    emit("paper_claim_inception_1.6x", 0,
         f"speedup={v['inception_depcha_speedup_min']:.2f}_"
         f"pass={v['claim_1.6x']}")
    emit("paper_claim_cifar_convergence", 0,
         f"gap8={v['cifar_gap_8']:.2f}_gap32={v['cifar_gap_32']:.2f}_"
         f"pass={v['claim_gap_shrinks']}")
    emit("paper_claim_50s_epoch_256gpu", v["imagenet_epoch_256"] * 1e6,
         f"pass={v['claim_50s']}")


def bench_strategy_steps(emit):
    import jax
    import jax.numpy as jnp

    import repro.sim  # noqa: F401  (registers the "auto" strategy)
    from repro.core import GradSyncConfig, strategy_names
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import transformer as tf
    from repro.optim import adamw
    from repro.runtime import make_train_step
    from repro.sim import compute_model_for, sim_config_for, simulate

    mesh = make_smoke_mesh(1, 1)
    cfg = tf.TransformerConfig(
        name="bench", n_layers=4, d_model=128, n_heads=8, kv_heads=4,
        d_ff=512, vocab=1024, tp=1, attn_chunk=64, dtype=jnp.float32)
    pipe = TokenPipeline(1024, 128, 8, mesh=mesh)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = pipe.batch_at(0)
    opt = adamw(1e-3)
    compute = compute_model_for(cfg, global_batch=8, seq_len=128,
                                n_devices=8)
    for strat in strategy_names():
        ts = make_train_step(
            cfg, mesh,
            GradSyncConfig(strategy=strat, num_channels=4,
                           bucket_bytes=1 << 16),
            opt, batch_like=batch, params_like=params)
        state = opt.init(params)
        us = _t(lambda: ts.fn(params, state, batch, jnp.int32(0)))
        # predicted step for the SAME planned schedule on a 2×4 mesh —
        # simulated (network model) next to measured (1-CPU overhead).
        # The bench config never emits in-scan psums (depcha_in_scan is
        # False), so depcha is predicted as the plain chains it runs as.
        tl = simulate(ts.gradsync.schedule, {"data": 2, "model": 4},
                      compute=compute,
                      sim=sim_config_for(
                          strat, in_scan_active=cfg.depcha_in_scan))
        emit(f"strategy_step_{strat}", us, "1cpu_4L_128d",
             strategy=strat, reducer="flat", measured_us=us,
             simulated_8dev_us=tl.step_time * 1e6,
             simulated_overlap=tl.overlap_fraction)


def bench_kernels(emit):
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.quantize.ops import quantize_blocks
    from repro.kernels.rwkv6.ops import wkv_chunk

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    B, S, H, D = 1, 256, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    us = _t(lambda: flash_attention(q, k, v, interpret=True))
    emit("kernel_flash_attention_interp", us, f"S{S}_H{H}_D{D}")
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    us = _t(lambda: attention_ref(qf, qf, qf))
    emit("kernel_flash_attention_jnp_ref", us, f"S{S}_H{H}_D{D}")

    x = jax.random.normal(ks[3], (1024 * 256,), jnp.float32)
    us = _t(lambda: quantize_blocks(x, interpret=True))
    emit("kernel_quantize_interp", us, "1M_elems")

    C, N = 32, 64
    r = jax.random.normal(ks[4], (2, C, 8, N), jnp.float32)
    lw = -jnp.exp(jax.random.normal(ks[5], (2, C, 8, N)) - 2)
    u = jnp.zeros((8, N), jnp.float32)
    st = jnp.zeros((2, 8, N, N), jnp.float32)
    us = _t(lambda: wkv_chunk(r, r, r, lw, u, st, interpret=True))
    emit("kernel_rwkv6_chunk_interp", us, f"C{C}_N{N}")


def bench_pack(emit):
    """§8 staging/collective microbenchmark → BENCH_pack.json.

    fused-vs-leafwise CopyFromTo staging through the REAL emitter
    (GradSync inside shard_map) on the resnet50 bucket plan — wall time
    AND post-optimization HLO copy/fusion-class op counts — plus
    measured ring-vs-psum allreduce rows from an 8-fake-device
    subprocess and the simulator's predicted staging delta.
    """
    import re
    import subprocess
    import sys

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.configs.base import param_structs
    from repro.core import GradSync, GradSyncConfig
    from repro.launch.mesh import make_smoke_mesh
    from repro.models.registry import family_of

    arch = get_arch("resnet50-cifar")
    cfg = arch.make_config(tp=1, dp_axes=("data",))
    mesh = make_smoke_mesh(1, 1)
    params_sds = param_structs(cfg)
    pspecs = family_of(cfg).param_rules(cfg).tree_specs(params_sds)
    grads = jax.tree.map(
        lambda l: jax.random.normal(jax.random.PRNGKey(0), l.shape,
                                    jnp.float32), params_sds)
    gspecs = jax.tree.map(lambda _: P(), grads)
    n_leaves = len(jax.tree.leaves(grads))

    def _best(fn, reps=8, trials=3):
        """best-of-trials mean: the wall rows must survive a noisy CI
        host (the deterministic emitted-op counts are the stable
        metric; this keeps the time metric honest too)."""
        import time as _time

        fn()   # warmup/compile
        best = float("inf")
        for _ in range(trials):
            t0 = _time.perf_counter()
            for _ in range(reps):
                r = fn()
            jax.block_until_ready(r)
            best = min(best, (_time.perf_counter() - t0) / reps)
        return best * 1e6

    copy_re = re.compile(
        r"= [a-z0-9\[\],{} ]*\b(fusion|copy|concatenate"
        r"|dynamic-update-slice)\(")
    results = {}
    for mode, fused in (("leafwise", False), ("fused", True)):
        sync = GradSyncConfig(strategy="concom", bucket_bytes=4 << 20,
                              comm_dtype=jnp.bfloat16,
                              use_fused_staging=fused)

        def run(g, _sync=sync):
            gs = GradSync(_sync, mesh, pspecs, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), g))
            return gs(g)

        f = jax.jit(lambda g, _r=run: jax.shard_map(
            _r, mesh=mesh, in_specs=(gspecs,), out_specs=gspecs,
            check_vma=False)(g))
        n_ops = len(copy_re.findall(f.lower(grads).compile().as_text()))
        us = _best(lambda _f=f: _f(grads))
        results[mode] = (us, n_ops)
        emit(f"staging_{mode}_resnet50", us,
             f"{n_leaves}leaves_bf16wire_hlo{n_ops}",
             staging=mode, hlo_copy_fusion_ops=n_ops)
    lw_us, lw_ops = results["leafwise"]
    fu_us, fu_ops = results["fused"]
    emit("staging_fused_speedup_resnet50", 0,
         f"wall{lw_us / fu_us:.2f}x_hloops{lw_ops / max(fu_ops, 1):.2f}x",
         wall_speedup=round(lw_us / fu_us, 3),
         hlo_op_ratio=round(lw_ops / max(fu_ops, 1), 3))

    # pack-side emission counts (lowered, pre-fusion): how many copy-class
    # staging ops each path ASKS the compiler for — per-leaf cast+concat
    # vs one concat + one whole-buffer cast per bucket.  (Post-fusion CPU
    # HLO merges both; on TPU the fused path is one Mosaic call/bucket.)
    from repro.core.buckets import make_bucket_plan, pack
    from repro.kernels.collectives.ops import fused_pack

    plan = make_bucket_plan(params_sds, pspecs, mesh,
                            bucket_bytes=4 << 20, comm_dtype=jnp.bfloat16)
    flat = jax.tree.leaves(grads)
    emit_re = re.compile(r"stablehlo\.(convert|concatenate|copy)")

    def pack_all_leafwise(g):
        return [pack(b, g, jnp.bfloat16) for b in plan.buckets]

    def pack_all_fused(g):
        return [fused_pack(b, g, jnp.bfloat16) for b in plan.buckets]

    for mode, fn in (("leafwise", pack_all_leafwise),
                     ("fused", pack_all_fused)):
        n_ops = len(emit_re.findall(jax.jit(fn).lower(flat).as_text()))
        jitted = jax.jit(fn)
        us = _best(lambda _f=jitted: _f(flat))
        emit(f"pack_only_{mode}_resnet50", us,
             f"{len(plan.buckets)}buckets_emitted_copy_ops{n_ops}",
             staging=mode, emitted_copy_ops=n_ops)

    # simulator's view of the same choice (what `auto` sees)
    from repro.sim import SimConfig, simulate_strategy

    plan = make_bucket_plan(params_sds, pspecs, mesh,
                            bucket_bytes=4 << 20, comm_dtype=jnp.bfloat16)
    mesh16 = {"data": 16, "model": 16}
    for mode, fused in (("leafwise", False), ("fused", True)):
        _, tl = simulate_strategy(
            "concom", plan, mesh16,
            sim=SimConfig(itemsize=2, fused_staging=fused))
        emit(f"staging_sim_{mode}_resnet50", tl.step_time * 1e6,
             "simulated_16x16", staging=mode,
             simulated_comm_us=tl.total_comm * 1e6)

    # measured ring-vs-psum allreduce (8 fake devices, subprocess)
    worker = os.path.join(os.path.dirname(__file__), "ring_bench_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    proc = subprocess.run([sys.executable, worker], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        emit("ring_vs_psum_failed", 0, proc.stderr[-120:].replace(",", ";"))
        return
    for line in proc.stdout.splitlines():
        if "," not in line:
            continue
        name, us = line.rsplit(",", 1)
        emit(f"ring_{name}_8dev", float(us), "8_fake_devices")


def bench_step(emit):
    """§9 StepProgram benchmark → BENCH_step.json.

    Scheduled-zero1 (per-bucket RS→UPDATE→AG + NORM clip) vs monolithic
    zero1 vs flat allreduce+update on the same small transformer:
    measured wall time per train step (1 CPU device — orders overhead),
    an AOT peak-memory proxy (temp + argument bytes from
    memory_analysis), and the simulator's predicted step time / exposed
    comm for the SAME planned schedules on a 2×4 mesh.
    """
    import jax
    import jax.numpy as jnp

    import repro.sim  # noqa: F401  (registers the "auto" strategy)
    from repro.core import GradSyncConfig
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import transformer as tf
    from repro.optim import adamw, zero1
    from repro.runtime import make_train_step
    from repro.sim import compute_model_for, rank_step_plans, simulate

    mesh = make_smoke_mesh(1, 1)
    cfg = tf.TransformerConfig(
        name="step", n_layers=4, d_model=128, n_heads=8, kv_heads=4,
        d_ff=512, vocab=1024, tp=1, attn_chunk=64, dtype=jnp.float32)
    pipe = TokenPipeline(1024, 128, 8, mesh=mesh)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = pipe.batch_at(0)
    mesh_shape = {"data": 2, "model": 4}
    compute = compute_model_for(cfg, global_batch=8, seq_len=128,
                                n_devices=8)

    def build(mode):
        # clip_norm=0 everywhere: the monolithic path cannot clip, so a
        # clipped scheduled program would pay for (and compute) more —
        # the wall ratio must compare like for like
        if mode == "flat":
            return make_train_step(
                cfg, mesh, GradSyncConfig(strategy="concom",
                                          bucket_bytes=1 << 16),
                adamw(1e-3), batch_like=batch, params_like=params,
                clip_norm=0.0)
        opt = zero1(adamw(1e-3), ("data",), 1)
        return make_train_step(
            cfg, mesh,
            GradSyncConfig(strategy="concom", bucket_bytes=1 << 16,
                           exclude_axes=("data",)),
            opt, batch_like=batch, params_like=params,
            zero1_mode=True, zero1_plan=mode, clip_norm=0.0)

    walls = {}
    for mode in ("flat", "monolithic", "scheduled"):
        ts = build(mode)
        state = ts.init_opt()
        compiled = ts.fn.lower(params, state, batch,
                               jax.ShapeDtypeStruct((), jnp.int32)
                               ).compile()
        m = compiled.memory_analysis()
        temp = int(getattr(m, "temp_size_in_bytes", 0) or 0)
        arg = int(getattr(m, "argument_size_in_bytes", 0) or 0)
        ir = ts.gradsync.schedule.stats()
        tl = simulate(ts.gradsync.schedule, mesh_shape, compute=compute)
        # time the AOT executable — going through ts.fn would re-trace
        # and re-compile the very program we just compiled
        step0 = jnp.int32(0)
        us = _t(lambda _f=compiled, _s=state: _f(params, _s, batch,
                                                 step0))
        walls[mode] = us
        emit(f"step_{mode}_wall", us,
             f"ops{ir['num_ops']}_upd{ir['kinds'].get('update', 0)}",
             mode=mode, ir_ops=ir["num_ops"],
             ir_update_ops=ir["kinds"].get("update", 0),
             temp_bytes=temp, argument_bytes=arg,
             peak_memory_proxy=temp + arg,
             simulated_step_us=tl.step_time * 1e6,
             simulated_exposed_us=tl.exposed_comm * 1e6)
    emit("step_scheduled_vs_monolithic", 0,
         f"wall{walls['monolithic'] / walls['scheduled']:.2f}x",
         wall_ratio=round(walls["monolithic"] / walls["scheduled"], 3))

    # predicted zero1-scheduled vs flat+monolithic-update plans on the
    # dp bucket plan itself (what `auto` ranks under zero1)
    from repro.core.stepprogram import zero1_bucket_plan
    from repro.models.registry import family_of

    pspecs = family_of(cfg).param_rules(cfg).tree_specs(params)
    dp_plan = zero1_bucket_plan(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params),
        pspecs, mesh, dp_axes=("data",), bucket_bytes=1 << 16)
    for name, tl in rank_step_plans(dp_plan, mesh_shape,
                                    dp_axes=("data",), compute=compute):
        emit(f"step_sim_{name.replace(':', '_')}", tl.step_time * 1e6,
             f"exposed{tl.exposed_comm * 1e6:.0f}us",
             plan=name, simulated_step_us=tl.step_time * 1e6,
             simulated_exposed_us=tl.exposed_comm * 1e6,
             overlap=round(tl.overlap_fraction, 3))


def bench_pipeline(emit):
    """§10 pipelined StepProgram benchmark → BENCH_pipeline.json.

    Deferred (phase-split: AGs at the NEXT step's top, update shards
    carried in opt_state) vs scheduled (same-step StepProgram) vs
    monolithic zero1, at grad-accumulation M ∈ {1, 4}: measured wall
    time per train step (1 CPU device — orders overhead) and the
    simulator's steady-state prediction for the SAME dp bucket plan on
    a 2×4 mesh (step time, exposed comm, overlap fraction; with M > 1
    the releases come only from the FINAL microbatch's backward).
    Accumulation GROWS the global batch at fixed microbatch shape
    (batch 8·M split M ways), matching the sim's per-microbatch model;
    bucket_bytes is 1 MB so the dp plan's all-gather wave fits the
    in-flight window — the regime the deferred plan is built for.
    """
    import jax
    import jax.numpy as jnp

    import repro.sim  # noqa: F401  (registers the "auto" strategy)
    from repro.core import GradSyncConfig
    from repro.core.stepprogram import zero1_bucket_plan
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import transformer as tf
    from repro.models.registry import family_of
    from repro.optim import adamw, zero1
    from repro.runtime import make_train_step
    from repro.sim import compute_model_for, rank_step_plans

    mesh = make_smoke_mesh(1, 1)
    cfg = tf.TransformerConfig(
        name="pipe", n_layers=4, d_model=128, n_heads=8, kv_heads=4,
        d_ff=512, vocab=1024, tp=1, attn_chunk=64, dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    mesh_shape = {"data": 2, "model": 4}
    bb = 1 << 20

    def build(mode, accum, batch):
        opt = zero1(adamw(1e-3), ("data",), 1)
        return make_train_step(
            cfg, mesh,
            GradSyncConfig(strategy="concom", bucket_bytes=bb,
                           exclude_axes=("data",)),
            opt, batch_like=batch, params_like=params,
            zero1_mode=True, zero1_plan=mode, clip_norm=0.0,
            microbatch=accum)

    walls = {}
    for accum in (1, 4):
        batch = TokenPipeline(1024, 128, 8 * accum, mesh=mesh).batch_at(0)
        for mode in ("monolithic", "scheduled", "deferred"):
            ts = build(mode, accum, batch)
            state = ts.init_opt()
            compiled = ts.fn.lower(params, state, batch,
                                   jax.ShapeDtypeStruct((), jnp.int32)
                                   ).compile()
            step0 = jnp.int32(0)
            us = _t(lambda _f=compiled, _s=state, _b=batch: _f(
                params, _s, _b, step0))
            walls[(mode, accum)] = us
            phases = ts.gradsync.schedule.phase_counts()
            emit(f"pipeline_{mode}_accum{accum}_wall", us,
                 f"pre{phases.get('pre', 0)}_post{phases.get('post', 0)}",
                 mode=mode, accum=accum,
                 ir_pre_ops=phases.get("pre", 0),
                 ir_post_ops=phases.get("post", 0),
                 deferred_bytes=ts.gradsync.schedule.deferred_bytes())
        emit(f"pipeline_deferred_vs_scheduled_accum{accum}", 0,
             f"wall{walls[('scheduled', accum)] / walls[('deferred', accum)]:.2f}x",
             accum=accum,
             wall_ratio=round(walls[("scheduled", accum)]
                              / walls[("deferred", accum)], 3))

    # simulated steady state on the dp bucket plan itself — the
    # deferred:<s> / zero1:<s> / flat:<s> leaderboard auto ranks
    pspecs = family_of(cfg).param_rules(cfg).tree_specs(params)
    dp_plan = zero1_bucket_plan(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params),
        pspecs, mesh, dp_axes=("data",), bucket_bytes=bb)
    # rank_step_plans wants the PER-MICROBATCH model when accum > 1;
    # the microbatch shape is batch 8 at every M (accumulation grows
    # the global batch), so the per-micro model is the same model
    micro = compute_model_for(cfg, global_batch=8, seq_len=128,
                              n_devices=8)
    for accum in (1, 4):
        ranked = rank_step_plans(dp_plan, mesh_shape, dp_axes=("data",),
                                 compute=micro, accum=accum)
        for name, tl in ranked:
            emit(f"pipeline_sim_{name.replace(':', '_')}_accum{accum}",
                 tl.step_time * 1e6,
                 f"exposed{tl.exposed_comm * 1e6:.0f}us",
                 plan=name, accum=accum,
                 simulated_step_us=tl.step_time * 1e6,
                 simulated_exposed_us=tl.exposed_comm * 1e6,
                 overlap=round(tl.overlap_fraction, 3))
        by = dict(ranked)
        bz = min(v.exposed_comm for k, v in by.items()
                 if k.startswith("zero1:"))
        bd = min(v.exposed_comm for k, v in by.items()
                 if k.startswith("deferred:"))
        emit(f"pipeline_sim_deferred_below_zero1_accum{accum}", 0,
             f"deferred{bd * 1e6:.1f}us_zero1{bz * 1e6:.1f}us_"
             f"pass={bd < bz}",
             accum=accum, deferred_exposed_us=bd * 1e6,
             zero1_exposed_us=bz * 1e6, strictly_below=bool(bd < bz))


PP_WORKER = r'''
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp
from repro.core import GradSyncConfig
from repro.data import TokenPipeline
from repro.launch.mesh import make_smoke_mesh
from repro.models import transformer as tf
from repro.models.registry import family_of
from repro.optim import adamw
from repro.runtime import make_train_step

cfg = tf.TransformerConfig(
    name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=2,
    d_ff=128, vocab=96, tp=2, attn_chunk=16, dtype=jnp.float32)
mesh = make_smoke_mesh(2, 2, stage=2)
params = family_of(cfg).init(jax.random.PRNGKey(0), cfg)
pipe = TokenPipeline(96, 32, 8, seed=7, mesh=mesh)
out = {}
for sched in ("gpipe", "1f1b"):
    ts = make_train_step(
        cfg, mesh, GradSyncConfig(strategy="concom",
                                  bucket_bytes=1 << 12),
        adamw(1e-3), batch_like=pipe.batch_at(0), params_like=params,
        clip_norm=0.0, microbatch=4, pp_stages=2, pp_schedule=sched)
    ps = jax.device_put(params, ts.shardings(ts.param_specs))
    st = ts.init_opt()
    ps, st, _ = ts.fn(ps, st, pipe.batch_at(0), jnp.int32(0))  # warmup
    jax.block_until_ready(ps)
    reps = 5
    t0 = time.perf_counter()
    for k in range(reps):
        ps, st, m = ts.fn(ps, st, pipe.batch_at(k + 1), jnp.int32(k + 1))
    jax.block_until_ready(ps)
    out[sched] = (time.perf_counter() - t0) / reps * 1e6
    out[sched + "_loss"] = float(m["loss"])
print("PPBENCH " + json.dumps(out))
'''


def bench_pp(emit):
    """§15 pipeline-parallel benchmark → BENCH_pp.json.

    Measured: GPipe vs 1F1B wall per train step at dp2 × stage2 × tp2,
    M=4 microbatches, on 8 fake CPU devices (subprocess — the main
    process pins 1 device; CPU walls order overhead, not bubbles).
    Simulated: analytic wall + bubble fraction per schedule at
    M ∈ {2, 4, 8} under the calibration-default network's stage hop,
    the joint ``pp:<sched>:<strategy>`` ranking at M=4, and the
    acceptance booleans — 1F1B bubble strictly below GPipe at M >= S,
    and the ``auto`` pick never worse than the best fixed schedule.
    """
    import subprocess
    import sys
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core.pipeline_program import plan_pipeline
    from repro.core.stepprogram import zero1_bucket_plan
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import transformer as tf
    from repro.models.registry import family_of
    from repro.sim import compute_model_for
    from repro.sim.autotune import choose_pp_schedule, rank_step_plans
    from repro.sim.compute import pipeline_timeline
    from repro.sim.netmodel import default_network

    S = 2
    cfg = tf.TransformerConfig(
        name="dense", n_layers=2, d_model=64, n_heads=8, kv_heads=2,
        d_ff=128, vocab=96, tp=2, attn_chunk=16, dtype=jnp.float32)
    mesh_shape = {"data": 2, "stage": 2, "model": 2}
    whole = compute_model_for(cfg, global_batch=8, seq_len=32,
                              n_devices=8)
    net = default_network()

    bubble_ok = True
    auto_ok = True
    for M in (2, 4, 8):
        act = (8 // 2 // M if M <= 4 else 1) * 32 * 64 * 4
        wire = net.p2p_time(act, "stage", mesh_shape)
        walls, bubbles = {}, {}
        for sched in ("gpipe", "1f1b"):
            tl = pipeline_timeline(
                plan_pipeline(S, M, kind=sched, activation_bytes=act),
                whole, wire_time=wire)
            walls[sched], bubbles[sched] = tl.wall, tl.bubble_fraction
            emit(f"pp_sim_{sched}_m{M}", tl.wall * 1e6,
                 f"bubble{tl.bubble_fraction:.4f}",
                 schedule=sched, microbatches=M, stages=S,
                 simulated_wall_us=tl.wall * 1e6,
                 bubble_fraction=round(tl.bubble_fraction, 6))
        if M >= S:
            bubble_ok &= bubbles["1f1b"] < bubbles["gpipe"]
        pick = choose_pp_schedule(
            S, M, activation_bytes=act, compute=whole, net=net,
            mesh_shape=mesh_shape)
        auto_ok &= walls[pick] <= min(walls.values()) + 1e-12
        emit(f"pp_sim_auto_pick_m{M}", walls[pick] * 1e6, pick,
             microbatches=M, pick=pick,
             never_worse=bool(walls[pick]
                              <= min(walls.values()) + 1e-12))
    emit("pp_sim_1f1b_bubble_below_gpipe", 0,
         f"pass={bubble_ok}", strictly_below=bool(bubble_ok))
    emit("pp_sim_auto_never_worse_than_fixed", 0,
         f"pass={auto_ok}", never_worse=bool(auto_ok))

    # joint pipeline × zero1 ranking on the real dp bucket plan
    params = family_of(cfg).init(jax.random.PRNGKey(0), cfg)
    pspecs = family_of(cfg).param_rules(cfg).tree_specs(params)
    mesh = make_smoke_mesh(1, 1)
    dp_plan = zero1_bucket_plan(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params),
        pspecs, mesh, dp_axes=("data",), bucket_bytes=1 << 16)
    act4 = 1 * 32 * 64 * 4
    ranked = rank_step_plans(
        dp_plan, mesh_shape, dp_axes=("data",), compute=whole,
        pp={"stages": S, "microbatches": 4, "activation_bytes": act4})
    pp_rows = [(n, tl) for n, tl in ranked if n.startswith("pp:")]
    for name, tl in pp_rows[:4]:
        emit(f"pp_rank_{name.replace(':', '_')}", tl.step_time * 1e6,
             f"exposed{tl.exposed_comm * 1e6:.0f}us", plan=name,
             simulated_step_us=tl.step_time * 1e6,
             simulated_exposed_us=tl.exposed_comm * 1e6,
             overlap=round(tl.overlap_fraction, 3))

    # measured walls on real stage process groups (subprocess)
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(PP_WORKER)
        path = f.name
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, path], env=env,
                          capture_output=True, text=True, timeout=1200)
    os.unlink(path)
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("PPBENCH ")]
    if proc.returncode != 0 or not line:
        emit("pp_meas_failed", 0,
             (proc.stderr or "no output")[-160:].replace(",", ";"))
        return
    meas = json.loads(line[0][len("PPBENCH "):])
    for sched in ("gpipe", "1f1b"):
        emit(f"pp_meas_{sched}_wall", meas[sched],
             f"loss{meas[sched + '_loss']:.3f}", schedule=sched,
             microbatches=4, stages=S, measured_wall_us=meas[sched])
    emit("pp_meas_1f1b_vs_gpipe", 0,
         f"wall{meas['gpipe'] / meas['1f1b']:.2f}x",
         wall_ratio=round(meas["gpipe"] / meas["1f1b"], 3))


def bench_roofline_summary(emit):
    path = "results/dryrun.json"
    if not os.path.exists(path):
        emit("roofline_summary", 0, "dryrun.json_missing_run_dryrun_first")
        return
    from benchmarks.roofline import assemble

    records = json.load(open(path))
    for mesh in ("single", "multi"):
        rows = assemble(records, mesh)
        if not rows:
            continue
        by = {}
        for r in rows:
            by[r["bottleneck"]] = by.get(r["bottleneck"], 0) + 1
        emit(f"roofline_cells_{mesh}", 0,
             "_".join(f"{k}:{v}" for k, v in sorted(by.items())))
        worst = min(rows, key=lambda r: r["roofline_frac"])
        emit(f"roofline_worst_{mesh}", worst["roofline_frac"] * 1e6,
             f"{worst['arch']}_{worst['shape']}")


SECTIONS = {
    "paper_figures": bench_paper_figures,
    "strategy_step": bench_strategy_steps,
    "kernels": bench_kernels,
    "pack": bench_pack,
    "step": bench_step,
    "pipeline": bench_pipeline,
    "pp": bench_pp,
    "roofline": bench_roofline_summary,
}


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--sections", default="",
                    help="comma-separated subset of "
                         f"{','.join(SECTIONS)} (default: all)")
    args = ap.parse_args(argv)
    wanted = [s for s in args.sections.split(",") if s] or list(SECTIONS)
    unknown = set(wanted) - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections: {sorted(unknown)}")

    print("name,us_per_call,derived")
    sections: dict[str, list] = {}

    def make_emit(section):
        rows = sections.setdefault(section, [])

        def emit(name, us, derived, **extra):
            print(f"{name},{us:.1f},{derived}")
            rows.append({"name": name, "us_per_call": round(us, 1),
                         "derived": derived, **extra})

        return emit

    for name in wanted:
        SECTIONS[name](make_emit(name))

    from repro.obs import bench_metadata

    meta = bench_metadata()
    for section, rows in sections.items():
        path = f"BENCH_{section}.json"
        with open(path, "w") as f:
            json.dump({"bench": section, "meta": meta, "rows": rows},
                      f, indent=1)
        print(f"[bench] wrote {path} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
