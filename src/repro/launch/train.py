"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 100 --strategy depcha [--smoke]

Without ``--smoke`` the arch's full config runs on a mesh over the local
devices (``data = count // tp``, ``model = --tp``) at the arch's first
shape; ``--batch``/``--seq`` override that shape where the host cannot
hold its global batch (the LM shapes assume 256 chips).  ``--smoke``
runs the reduced config on one device.  ``repro.launch.dryrun``
AOT-compiles the full config for the 256/512-chip production mesh.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.core import (
    GradSyncConfig,
    get_strategy,
    reducer_names,
    strategy_names,
)
import repro.sim  # noqa: F401  (registers "auto" → --strategy auto)
from repro.data import ImagePipeline, TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_smoke_mesh
from repro.models.registry import family_of
from repro.optim import adamw, cosine_warmup, sgd, zero1
from repro.parallel.sharding import dp_axes_of
from repro.runtime import Trainer, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--strategy", default="depcha",
                    choices=strategy_names())
    ap.add_argument("--reducer", default="flat",
                    choices=reducer_names())
    ap.add_argument("--channels", type=int, default=4)
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--zero1-plan", default="scheduled",
                    choices=["scheduled", "deferred", "monolithic"],
                    help="scheduled = StepProgram (per-bucket RS→UPDATE→"
                         "AG planned by the strategy, clipped via the "
                         "NORM op); deferred = pipelined StepProgram "
                         "(AGs detach into the next step's top, update "
                         "shards carried in opt_state); monolithic = "
                         "opaque optimizer.update")
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--pp-stages", type=int, default=1,
                    help="pipeline stages over a 'stage' mesh axis "
                         "(smoke mesh only; --microbatch doubles as the "
                         "pipeline microbatch count M)")
    ap.add_argument("--pp-schedule", default="auto",
                    choices=["auto", "gpipe", "1f1b"],
                    help="pipeline schedule; auto = argmin of the "
                         "analytic pipeline wall (repro.sim."
                         "choose_pp_schedule)")
    ap.add_argument("--no-accum-overlap", action="store_true",
                    help="keep the final microbatch inside the "
                         "accumulation scan (sync waits for the whole "
                         "scan) instead of peeling it for overlap")
    ap.add_argument("--tp", type=int, default=1,
                    help="'model' axis extent of the local mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on one local device")
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: the arch's first "
                         "shape; 64 with --smoke)")
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: the arch's first "
                         "shape; 8 with --smoke)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--events-jsonl", default="",
                    help="append per-step JSONL telemetry (repro.obs "
                         "EventLog) to this path")
    ap.add_argument("--metrics-json", default="",
                    help="write the final metrics snapshot here")
    args = ap.parse_args()

    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.smoke:
        mesh = make_smoke_mesh(1, 1, stage=args.pp_stages
                               if args.pp_stages > 1 else 0)
        cfg = arch.make_smoke()
        seq, batch = args.seq or 64, args.batch or 8
    else:
        if args.pp_stages > 1:
            raise SystemExit(
                "--pp-stages needs the smoke mesh (--smoke); the "
                "local mesh has no 'stage' axis")
        mesh = make_local_mesh(args.tp)
        cfg = arch.make_config(
            tp=mesh.shape["model"], dp_axes=dp_axes_of(mesh),
            depcha_in_scan=get_strategy(args.strategy).uses_in_scan)
        shape = arch.shapes[0]
        seq = args.seq or shape.seq_len
        batch = args.batch or shape.global_batch
    print(f"[train] {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind} mesh {dict(mesh.shape)} "
          f"batch {batch}" + (f" seq {seq}" if seq else ""))

    api = family_of(cfg)
    if arch.family in ("resnet", "inception"):
        pipe = ImagePipeline(cfg.img_size, cfg.num_classes, batch,
                             mesh=mesh)
        opt = sgd(cosine_warmup(args.lr, 10, args.steps), momentum=0.9)
    else:
        extras = {
            name: (tuple(shape_fn(cfg, seq)), jnp.float32)
            for name, shape_fn, _ in arch.extra_inputs}
        pipe = TokenPipeline(cfg.vocab, seq, batch, mesh=mesh,
                             extra_specs=extras)
        opt = adamw(cosine_warmup(args.lr, 10, args.steps))
    if args.zero1:
        import numpy as np

        dp = dp_axes_of(mesh)
        dp_size = int(np.prod([mesh.shape[a] for a in dp]))
        opt = zero1(opt, dp, dp_size)

    sync = GradSyncConfig(
        strategy=args.strategy, reducer=args.reducer,
        bucket_bytes=int(args.bucket_mb * 1024 * 1024),
        num_channels=args.channels,
        exclude_axes=dp_axes_of(mesh) if args.zero1 else ())
    params = api.init(jax.random.PRNGKey(0), cfg)
    # donate params/opt_state on the production path: the optimizer
    # update reuses their buffers in place (halves peak state memory).
    # Smoke runs keep donation off so the host copies stay comparable.
    ts = make_train_step(cfg, mesh, sync, opt,
                         batch_like=pipe.batch_at(0), params_like=params,
                         clip_norm=args.clip_norm,
                         zero1_mode=args.zero1,
                         zero1_plan=args.zero1_plan,
                         microbatch=args.microbatch,
                         accum_overlap=not args.no_accum_overlap,
                         donate=not args.smoke,
                         pp_stages=args.pp_stages,
                         pp_schedule=args.pp_schedule)
    ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) \
        if args.ckpt_dir else None
    trainer = Trainer(ts, pipe, ckpt, log_every=10,
                      events_path=args.events_jsonl or None)
    # place the state on the step's shardings (the mesh may span several
    # chips); init_opt derives zero1 shard sizes from the step's LOCAL
    # shapes (opt.init on global TP-sharded params would size them wrong)
    params = jax.device_put(params, ts.shardings(ts.param_specs))
    opt_state = ts.init_opt()
    # (deferred plan: checkpoints keep params + opt_state["pending"]
    # consistent, so resume is exact as-is; a consumer exporting params
    # must flush the carried shards with ts.finalize(params, opt_state))
    _, _, hist = trainer.run(params, opt_state, args.steps)
    print(f"[train] {args.arch} {args.strategy}: "
          f"loss {hist['losses'][0]:.3f} -> {hist['losses'][-1]:.3f}")
    snap = hist.get("metrics", {})
    compile_s = hist.get("compile_time")
    tps = snap.get("tokens_per_s")
    if compile_s is not None:
        print(f"[train] compile {compile_s:.2f}s (excluded from "
              f"throughput)"
              + (f", {tps:,.0f} tokens/s" if tps else ""))
    if args.metrics_json:
        import json

        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
        print(f"[train] metrics snapshot -> {args.metrics_json}")


if __name__ == "__main__":
    main()
