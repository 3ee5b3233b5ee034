import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

DOC = """Simulate every collective-embedding strategy for one (arch ×
shape × mesh) cell — predicted timelines, exposed communication and the
auto-tuned winner, all on CPU in seconds (no compile, no hardware).

  PYTHONPATH=src python -m repro.sim --arch resnet50-cifar
  PYTHONPATH=src python -m repro.sim --arch qwen3-1.7b --shape train_4k \
      --mesh multi --autotune --trace results/sim_trace.json
"""

import argparse
import jax  # noqa: F401

from repro.configs import get_arch
from repro.configs.base import param_structs
from repro.core.registry import fixed_strategy_names
from repro.core.buckets import make_bucket_plan
from repro.launch.mesh import make_production_mesh, mesh_shape_dict
from repro.models.registry import family_of
from repro.parallel.sharding import dp_axes_of, localize_structs
from repro.sim import (
    SimConfig,
    ascii_timeline,
    compute_model_for,
    grid_search,
    last_auto_report,
    plan_auto,
    rank_step_plans,
    simulate,
    simulate_strategy,
    write_chrome_trace,
)


def _make_mesh(spec: str):
    import jax
    from jax.sharding import AxisType

    if spec == "single":
        return make_production_mesh(multi_pod=False)
    if spec == "multi":
        return make_production_mesh(multi_pod=True)
    dims = tuple(int(v) for v in spec.split("x"))
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return jax.make_mesh(dims, axes,
                         axis_types=(AxisType.Auto,) * len(dims))


def main():
    ap = argparse.ArgumentParser(
        description=DOC, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None,
                    help="shape name (default: the arch's train shape)")
    ap.add_argument("--mesh", default="single",
                    help="single | multi | DxM | PxDxM")
    ap.add_argument("--channels", type=int, default=4)
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--reducer", default="flat")
    ap.add_argument("--comm-dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--staging", default="fused",
                    choices=["fused", "leafwise"],
                    help="CopyFromTo cost model: fused kernels vs "
                         "per-leaf pack/unpack")
    ap.add_argument("--autotune", action="store_true",
                    help="grid-search strategy × channels × bucket size")
    ap.add_argument("--zero1", action="store_true",
                    help="simulate the full-step ZeRO-1 StepProgram "
                         "(per-bucket RS→UPDATE→AG, plus the pipelined "
                         "deferred-AG variant) vs the flat allreduce + "
                         "monolithic update baseline")
    ap.add_argument("--clip", action="store_true",
                    help="with --zero1: plan the scheduled grad-norm "
                         "NORM op gating the updates")
    ap.add_argument("--accum", type=int, default=1,
                    help="grad-accumulation factor M: cost the "
                         "M-microbatch scan (M× compute; releases only "
                         "from the FINAL microbatch's backward — the "
                         "peeled-tail training shape)")
    ap.add_argument("--no-accum-overlap", action="store_true",
                    help="with --accum: releases at the scan's very end "
                         "(no peeled final microbatch)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace JSON of all timelines")
    ap.add_argument("--ascii", action="store_true",
                    help="render the best strategy's timeline")
    args = ap.parse_args()

    import jax.numpy as jnp

    arch = get_arch(args.arch)
    shape = arch.shape(args.shape) if args.shape else next(
        (s for s in arch.shapes if s.kind == "train"), arch.shapes[0])
    mesh = _make_mesh(args.mesh)
    mesh_shape = mesh_shape_dict(mesh)
    n_devices = 1
    for s in mesh_shape.values():
        n_devices *= s

    cfg = arch.make_config(tp=mesh_shape.get("model", 1),
                           dp_axes=dp_axes_of(mesh))
    params_sds = param_structs(cfg)
    pspecs = family_of(cfg).param_rules(cfg).tree_specs(params_sds)
    # GradSync runs inside shard_map: the comm payload is the LOCAL shard
    params_sds = localize_structs(params_sds, pspecs, mesh)
    compute = compute_model_for(
        cfg, global_batch=shape.global_batch, seq_len=shape.seq_len,
        n_devices=n_devices)
    # with --accum the step's FLOPs stay those of the full global batch;
    # the per-microbatch model is 1/M of it, and the folded model places
    # the releases where the accumulation scan actually produces them
    micro = compute
    if args.accum > 1:
        import dataclasses as _dc2

        micro = _dc2.replace(compute, t_fwd=compute.t_fwd / args.accum,
                             t_bwd=compute.t_bwd / args.accum)
        compute = micro.with_accum(args.accum,
                                   overlap_tail=not args.no_accum_overlap)
    itemsize = 2 if args.comm_dtype == "bf16" else 4
    comm_dtype = jnp.bfloat16 if args.comm_dtype == "bf16" else jnp.float32
    sim = SimConfig(window=args.window, itemsize=itemsize,
                    reducer=args.reducer,
                    fused_staging=args.staging == "fused")
    plan = make_bucket_plan(
        params_sds, pspecs, mesh,
        bucket_bytes=int(args.bucket_mb * 1024 * 1024),
        num_channels=args.channels, comm_dtype=comm_dtype)

    print(f"[sim] {args.arch} × {shape.name} × {args.mesh} "
          f"({'x'.join(f'{k}={v}' for k, v in mesh_shape.items())}), "
          f"{plan.total_bytes / 1e6:.1f} MB grads in "
          f"{len(plan.buckets)} buckets, "
          f"t_fwd={compute.t_fwd * 1e3:.2f} ms "
          f"t_bwd={compute.t_bwd * 1e3:.2f} ms"
          + (f" (accum M={args.accum}, releases in the final "
             f"microbatch's backward)" if args.accum > 1 else ""))

    print("strategy,ops,chains,step_ms,comm_ms,exposed_ms,overlap_pct")
    timelines = {}
    for name in fixed_strategy_names():
        schedule, tl = simulate_strategy(
            name, plan, mesh_shape, compute=compute, sim=sim)
        timelines[name] = tl
        print(f"{name},{len(schedule.ops)},{schedule.num_chains},"
              f"{tl.step_time * 1e3:.3f},{tl.total_comm * 1e3:.3f},"
              f"{tl.exposed_comm * 1e3:.3f},"
              f"{tl.overlap_fraction * 100:.1f}")

    auto_schedule = plan_auto(plan, context={
        "mesh_shape": mesh_shape, "reducer": args.reducer,
        "itemsize": itemsize, "compute": compute,
        "fused_staging": args.staging == "fused"})
    report = last_auto_report()
    auto_tl = simulate(auto_schedule, mesh_shape, compute=compute, sim=sim)
    timelines["auto"] = auto_tl
    print(f"[sim] auto → {report['winner']} "
          f"(predicted {report['ranking'][0][1] * 1e3:.3f} ms/step)")

    # fused vs leafwise CopyFromTo on the winner's schedule — the §8
    # staging cost the fused kernels remove (import dataclasses locally
    # to keep the CLI's import cost down)
    import dataclasses as _dc
    both = {
        mode: simulate(auto_schedule, mesh_shape, compute=compute,
                       sim=_dc.replace(sim, fused_staging=mode == "fused"))
        for mode in ("fused", "leafwise")}
    print(f"[sim] staging ({report['winner']}): "
          f"fused {both['fused'].step_time * 1e3:.3f} ms/step vs "
          f"leafwise {both['leafwise'].step_time * 1e3:.3f} ms/step "
          f"(Δ {(both['leafwise'].step_time - both['fused'].step_time) * 1e6:.1f} us)")

    if args.zero1:
        # the full-step StepProgram arc on one leaderboard: pipelined
        # deferred-AG (PRE gathers hidden under the next forward) vs
        # zero1 RS→UPDATE→AG triples vs flat allreduce + ONE monolithic
        # update (same wire bytes, progressively less of them exposed)
        # — UPDATE/NORM ops costed by the engine
        from repro.core.stepprogram import zero1_bucket_plan

        dp = dp_axes_of(mesh)
        if not dp:
            raise SystemExit("[sim] --zero1 needs a data-parallel axis")
        dp_plan = zero1_bucket_plan(
            params_sds, pspecs, mesh, dp_axes=dp,
            bucket_bytes=int(args.bucket_mb * 1024 * 1024),
            num_channels=args.channels)
        ranked = rank_step_plans(
            dp_plan, mesh_shape, dp_axes=dp, clip=args.clip,
            compute=micro, sim=sim, accum=args.accum,
            accum_overlap=not args.no_accum_overlap)
        print("step_plan,ops,update_ops,step_ms,exposed_ms,overlap_pct")
        for name, tl in ranked:
            ups = sum(1 for e in tl.events if e.kind == "update")
            print(f"{name},{len(tl.events)},{ups},"
                  f"{tl.step_time * 1e3:.3f},"
                  f"{tl.exposed_comm * 1e3:.3f},"
                  f"{tl.overlap_fraction * 100:.1f}")
            timelines[name] = tl
        best_d = next(t for n, t in ranked if n.startswith("deferred:"))
        best_z = next(t for n, t in ranked if n.startswith("zero1:"))
        best_f = next(t for n, t in ranked if n.startswith("flat:"))
        print(f"[sim] deferred-pipelined {best_d.step_time * 1e3:.3f} "
              f"(exposed {best_d.exposed_comm * 1e3:.3f}) vs "
              f"zero1-scheduled {best_z.step_time * 1e3:.3f} "
              f"(exposed {best_z.exposed_comm * 1e3:.3f}) vs "
              f"flat+monolithic {best_f.step_time * 1e3:.3f} ms/step")

    if args.ascii:
        best = report["winner"]
        print(f"[sim] timeline: {best}")
        print(ascii_timeline(timelines[best]))

    if args.autotune:
        preds = grid_search(
            params_sds, pspecs, mesh, mesh_shape=mesh_shape,
            compute=compute, sim=sim, comm_dtype=comm_dtype)
        print("tuned: strategy,channels,bucket_mb,step_ms,overlap_pct")
        for p in preds[:10]:
            print(f"tuned: {p.strategy},{p.num_channels},"
                  f"{p.bucket_bytes / (1 << 20):.0f},"
                  f"{p.step_time * 1e3:.3f},"
                  f"{p.overlap_fraction * 100:.1f}")
        best = preds[0]
        print(f"[sim] best config: --strategy {best.strategy} "
              f"--channels {best.num_channels} "
              f"--bucket-mb {best.bucket_bytes / (1 << 20):.0f}")

    if args.trace:
        os.makedirs(os.path.dirname(args.trace) or ".", exist_ok=True)
        write_chrome_trace(args.trace, timelines)
        n_events = sum(len(t.events) for t in timelines.values())
        print(f"[sim] wrote {args.trace} ({n_events} op events, "
              f"open in chrome://tracing or Perfetto)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
