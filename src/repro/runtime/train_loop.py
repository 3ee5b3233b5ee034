"""Training runtime: step factory (shard_map + GradSync strategies) and a
fault-tolerant loop (checkpoint/restart, failure recovery, straggler
detection, elastic re-mesh).

Grad-reduction rule (DESIGN.md; see also the TP-transpose note): after
``jax.grad`` inside shard_map(check_vma=False), every gradient is
``tp ×`` its true per-shard value (psum-transpose inflation), and still
needs a psum over the mesh axes missing from its param spec.  So:

    grads ← grads / tp                 (uniform correction)
    grads ← strategy psums over missing axes (GradSync buckets; depcha
            leaves already reduced inside the backward scan are skipped)
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import GradSync, GradSyncConfig, get_strategy
from repro.models.registry import family_of
from repro.optim.optimizers import (
    Optimizer,
    apply_updates,
    clip_by_global_norm,
)
from repro.optim.zero import (
    scheduled_update,
    shard_size,
    zero1_pending_structs,
    zero1_state_structs,
)
from repro.parallel.sharding import batch_spec, dp_axes_of


class SimulatedFailure(RuntimeError):
    """Injected node failure (testing the recovery path)."""


class TransientStepError(RuntimeError):
    """Injected transient step fault — retryable IN PLACE (rung 1 of the
    elastic policy ladder): the step never committed state, so the same
    step simply runs again up to ``step_retries`` times before
    escalating to checkpoint recovery."""


class RankLost(SimulatedFailure):
    """Injected loss of mesh member(s): THIS mesh cannot continue.  The
    Trainer attaches the last committed state (``.step``/``.params``/
    ``.opt_state``) and re-raises — recovery means a NEW mesh, which is
    the supervisor's job (``repro.elastic.supervisor``), not the
    loop's."""

    def __init__(self, message: str = "rank lost"):
        super().__init__(message)
        self.step: int = 0
        self.params: Any = None
        self.opt_state: Any = None


class RemeshRequest(SimulatedFailure):
    """Straggler-driven shrink request (opt-in via ``remesh_hook``):
    like ``RankLost``, carries the post-step state for the supervisor's
    shrink path — but the state is healthy; the mesh is just slow."""

    def __init__(self, message: str = "remesh requested"):
        super().__init__(message)
        self.step: int = 0
        self.params: Any = None
        self.opt_state: Any = None


def _batch_specs(batch_like: Any, mesh: Mesh) -> Any:
    bspec = batch_spec(mesh)
    return {
        k: (P() if np.ndim(v) == 0 else bspec)
        for k, v in batch_like.items()
    }


def _micro_compute(cfg: Any, batch_like: Any, mesh: Mesh,
                   microbatch: int):
    """PER-MICROBATCH ComputeModel for meta-strategy (auto) ranking —
    derived from the batch shape the step will actually run.  Returns
    None for configs outside the arch registry's FLOP model (auto then
    ranks on comm alone, as before)."""
    try:
        from repro.sim.compute import compute_model_for

        dims = next(np.shape(v) for v in jax.tree.leaves(batch_like)
                    if np.ndim(v) > 0)
        cm = compute_model_for(
            cfg, global_batch=int(dims[0]),
            seq_len=int(dims[1]) if len(dims) > 1 else 1,
            n_devices=int(mesh.devices.size))
        if microbatch > 1:
            cm = dataclasses.replace(cm, t_fwd=cm.t_fwd / microbatch,
                                     t_bwd=cm.t_bwd / microbatch)
        return cm
    except Exception:
        return None


def _opt_state_specs(state_like: Any, params_like: Any, pspecs: Any,
                     mesh: Mesh) -> Any:
    """Specs for optimizer state: param-shaped sub-trees mirror param
    specs; flat ZeRO shards are sharded over the DP axes."""
    params_td = jax.tree_util.tree_structure(params_like)
    dp = dp_axes_of(mesh)
    dp_spec = P(dp if len(dp) > 1 else dp[0]) if dp else P()

    def sub(v):
        td = jax.tree_util.tree_structure(v)
        if td == params_td:
            return pspecs
        return jax.tree.map(lambda _: dp_spec, v)   # zero1 flat shards

    return {k: sub(v) for k, v in state_like.items()}


@dataclasses.dataclass
class TrainStep:
    fn: Callable[..., Any]            # jitted (params, opt_state, batch, i)
    param_specs: Any
    opt_specs: Any
    batch_specs: Any
    mesh: Mesh
    gradsync: GradSync | None
    opt_state_like: Any = None        # global ShapeDtypeStructs
    # deferred StepProgram only: jitted (params, opt_state) -> params
    # that all-gathers + applies the carried update shards, so the last
    # trained step's update lands before an eval/checkpoint/export reads
    # the params (during training the NEXT step's PRE program does this)
    finalize: Callable[..., Any] | None = None

    def shardings(self, tree_specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), tree_specs)

    def init_opt(self) -> Any:
        """Zero-initialized optimizer state with the step's shardings.

        Required for ZeRO-1 under TP (the flat shard size depends on the
        LOCAL param shapes, which ``optimizer.init(global_params)`` cannot
        see); valid for every shipped optimizer (states are zero-init)."""
        sh = self.shardings(self.opt_specs)
        return jax.tree.map(
            lambda l, s: jax.device_put(jnp.zeros(l.shape, l.dtype), s),
            self.opt_state_like, sh)


def make_train_step(
    cfg: Any,
    mesh: Mesh,
    sync: GradSyncConfig,
    optimizer: Optimizer,
    *,
    batch_like: Any,
    params_like: Any,
    clip_norm: float = 1.0,
    zero1_mode: bool = False,
    zero1_plan: str = "scheduled",  # "scheduled" | "deferred" | "monolithic"
    microbatch: int = 1,    # grad-accumulation factor (memory §Perf lever)
    accum_overlap: bool = True,  # peel the last microbatch out of the scan
    donate: bool = False,   # enable in production (launcher); off for tests
    pp_stages: int = 1,     # pipeline stages over the "stage" mesh axis
    pp_schedule: str = "auto",   # "auto" | "gpipe" | "1f1b"
) -> TrainStep:
    """Build the jitted, shard_map'd train step for one (arch, mesh, sync).

    ``batch_like``/``params_like`` may be ShapeDtypeStructs (dry-run) or
    concrete arrays (training) — only shapes/dtypes are read here.

    With a zero1-wrapped optimizer, ``zero1_plan="scheduled"`` (default)
    plans the optimizer step as first-class CommSchedule ops: per-bucket
    RS→UPDATE→AG triples planned by the configured strategy, spliced
    after the sync ops in ONE StepProgram schedule (DESIGN.md §9), with
    gradient clipping as a scheduled NORM op (psum'd squared norms, clip
    on shards before the update).  ``"deferred"`` pipelines that program
    across the step boundary (DESIGN.md §10): the all-gathers detach
    into the TOP of the next step — the update shards ride along in
    ``opt_state["pending"]``, each step first gathers + applies them
    (overlapping its own forward) and ends with fresh shards instead of
    a serialized AG tail; ``TrainStep.finalize`` flushes the last
    pending shards when training stops.  ``"monolithic"`` keeps the
    optimizer opaque: one flat RS→update→AG after the full sync (no
    clipping — grads are still DP-partial when a norm could be taken
    locally).

    With ``microbatch > 1`` and ``accum_overlap`` (default) the FINAL
    microbatch is peeled out of the accumulation scan: its backward is
    emitted inline, so each sync/RS bucket can start the moment that
    backward produces its gradients — comm overlaps the last
    microbatch's compute instead of waiting for the whole scan
    (bit-exact with the plain scan: same accumulation order).

    With a "stage" axis in the mesh (DESIGN.md §15) the step runs the
    staged wave pipeline instead of the accumulation scan: ``microbatch``
    doubles as the pipeline microbatch count M, the stacked block params
    are sharded dim-0 over "stage" (each device holds one stage's layer
    slice), and activations hop stage→stage+1 via ppermute inside the
    forward.  ``pp_schedule="gpipe"`` differentiates the full M-wave
    scan in one backward; ``"1f1b"`` splits M into chunks of S
    microbatches with an accumulated ``jax.grad`` per chunk — the 1F1B
    memory shape (≤ S microbatches of activations live at once).
    ``"auto"`` delegates to ``repro.sim.choose_pp_schedule`` (the argmin
    of the analytic pipeline wall over the fixed schedules).  A staged
    run is bit-exact with the stage=1 reference (same mesh family with a
    stage axis of extent 1): off-stage compute is where-masked to exact
    zeros, and cross-stage psums only ever add those zeros.
    """
    api = family_of(cfg)
    rules = api.param_rules(cfg)
    pspecs = rules.tree_specs(params_like)
    pp_axis = "stage"
    pp_active = pp_stages > 1 or pp_axis in mesh.axis_names
    pp_sched = None
    if pp_active:
        if pp_axis not in mesh.axis_names:
            raise ValueError(
                f"pp_stages={pp_stages} needs a {pp_axis!r} mesh axis "
                f"(make_smoke_mesh(..., stage=N)); mesh has "
                f"{mesh.axis_names}")
        if int(mesh.shape[pp_axis]) != pp_stages:
            raise ValueError(
                f"pp_stages={pp_stages} != mesh {pp_axis!r} extent "
                f"{mesh.shape[pp_axis]}")
        if api.pipeline_train_forward is None:
            raise ValueError(
                f"family {api.family!r} has no pipeline_train_forward")
        if getattr(cfg, "depcha_in_scan", False):
            raise ValueError(
                "depcha_in_scan is not supported with pipeline stages")
        n_layers = getattr(cfg, "n_layers", 0)
        if n_layers and n_layers % pp_stages:
            raise ValueError(
                f"n_layers={n_layers} not divisible by "
                f"pp_stages={pp_stages}")
        from repro.parallel.sharding import stage_shard_specs

        pspecs = stage_shard_specs(pspecs, axis=pp_axis)
        # stage-boundary activation payload for the cost model: one
        # microbatch of (local_B, S, d_model) in the compute dtype
        pp_mb = max(int(microbatch), 1)
        try:
            dims = next(np.shape(v) for v in jax.tree.leaves(batch_like)
                        if np.ndim(v) > 0)
            b_local = int(dims[0]) // max(
                int(np.prod([mesh.shape[a] for a in dp_axes_of(mesh)])), 1)
            act_bytes = (b_local // pp_mb
                         * (int(dims[1]) if len(dims) > 1 else 1)
                         * int(getattr(cfg, "d_model", 0))
                         * np.dtype(getattr(cfg, "dtype", np.float32)
                                    ).itemsize)
        except StopIteration:
            act_bytes = 0
        if pp_schedule == "auto":
            from repro.sim.autotune import choose_pp_schedule

            pp_sched = choose_pp_schedule(
                pp_stages, pp_mb, activation_bytes=act_bytes,
                compute=_micro_compute(cfg, batch_like, mesh, 1),
                mesh_shape=dict(zip(mesh.axis_names, mesh.devices.shape)))
        elif pp_schedule in ("gpipe", "1f1b"):
            pp_sched = pp_schedule
        else:
            raise ValueError(
                f"pp_schedule must be 'auto', 'gpipe' or '1f1b', "
                f"got {pp_schedule!r}")
        sync = dataclasses.replace(
            sync, pp_stages=pp_stages, pp_schedule=pp_sched,
            pp_microbatches=pp_mb, pp_activation_bytes=act_bytes)
    bspecs = _batch_specs(batch_like, mesh)
    tp = getattr(cfg, "tp", 1)
    dp = dp_axes_of(mesh)
    if zero1_plan not in ("scheduled", "deferred", "monolithic"):
        raise ValueError(f"unknown zero1_plan {zero1_plan!r}")
    zmeta = getattr(optimizer, "zero1_meta", None)
    zero1_scheduled = bool(zmeta) and zero1_mode \
        and zero1_plan in ("scheduled", "deferred")
    defer_ag = zero1_scheduled and zero1_plan == "deferred"
    if pp_active and zero1_scheduled and clip_norm:
        # the NORM op psums squared shard norms over the DP axes only —
        # under pipeline stages the blocks are stage-sharded and the
        # cross-stage terms would be silently missing from the norm
        raise ValueError(
            "scheduled ZeRO-1 clipping is not supported with pipeline "
            "stages; pass clip_norm=0")

    # skip leaves from the post-backward schedule ONLY when the model is
    # actually emitting their psums inside the backward scan — otherwise
    # a depcha config without depcha_in_scan would leave them unreduced
    in_scan = (api.in_scan_names(params_like)
               if get_strategy(sync.strategy).uses_in_scan
               and getattr(cfg, "depcha_in_scan", False) else frozenset())
    # bucket plan must see LOCAL shard shapes (it runs inside shard_map)
    from repro.parallel.sharding import localize_structs
    grads_local = localize_structs(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     params_like),
        pspecs, mesh)
    if zero1_scheduled:
        sync = dataclasses.replace(
            sync, exclude_axes=tuple(dp), zero1_dp_axes=tuple(dp),
            zero1_clip=bool(clip_norm), zero1_defer_ag=defer_ag,
            zero1_accum=microbatch, zero1_accum_overlap=accum_overlap)
    if get_strategy(sync.strategy).meta and sync.sim_compute is None:
        sync = dataclasses.replace(
            sync, sim_compute=_micro_compute(cfg, batch_like, mesh,
                                             microbatch))
    gs = GradSync(sync, mesh, pspecs, grads_local, in_scan_names=in_scan)

    if zmeta:
        inner_opt, dp_size, _ = zmeta
        if zero1_scheduled:
            local_like = zero1_state_structs(inner_opt, gs.dp_plan, dp_size)
            if defer_ag:
                # the deferred-AG carry: last step's update shards
                local_like["pending"] = zero1_pending_structs(
                    gs.dp_plan, dp_size)
        else:
            # monolithic ZeRO-1: ONE flat shard sized from LOCAL params
            n_local = sum(int(np.prod(l.shape)) for l in
                          jax.tree.leaves(grads_local))
            local_like = {"inner": jax.eval_shape(
                inner_opt.init,
                jax.ShapeDtypeStruct((shard_size(n_local, dp_size),),
                                     jnp.float32))}
        # global view: each local leaf is dp-sharded on dim 0
        opt_state_like = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((l.shape[0] * dp_size,
                                            *l.shape[1:]), l.dtype),
            local_like)
    else:
        opt_state_like = jax.eval_shape(optimizer.init, params_like)
    ospecs = _opt_state_specs(opt_state_like, params_like, pspecs, mesh)

    # deferred-AG: dp bucket_id ↔ pending-state key (both derived from
    # gs.dp_plan, so the pairing is static) + the phase-split schedule
    if defer_ag:
        pend_keys = tuple((b.bucket_id, str(i))
                          for i, b in enumerate(gs.dp_plan.buckets))
        post_sched = gs.program.post_schedule()

        def gather_pending(params, opt_state):
            """PRE program (DESIGN.md §10): all-gather the PREVIOUS
            step's update shards and apply them to the params.  The
            gathers free-fly, overlapping the input pipeline and each
            other; the zero-initialized carry gathers to an identity
            update, so a fresh run's step 0 starts unchanged.  Shared
            by the step prologue and ``finalize`` so the two stay
            bit-identical."""
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            prev = gs.apply_pending(
                zeros, {bid: opt_state["pending"][k]
                        for bid, k in pend_keys})
            return apply_updates(params, prev)

    def step(params, opt_state, batch, step_idx):
        if defer_ag:
            # apply LAST step's deferred update shards before anything
            # reads the params
            params = gather_pending(params, opt_state)
        if pp_active:
            # staged wave pipeline (§15): microbatch IS the pipeline
            # microbatch count M; the batch splits exactly like the
            # accumulation path (global_tokens sees its 1/M share, the
            # summed loss/grads divide by M below)
            def psplit(path, x):
                if np.ndim(x) == 0:
                    if any(getattr(k, "key", None) == "global_tokens"
                           for k in path):
                        x = x / pp_mb
                    return jnp.broadcast_to(x, (pp_mb,))
                b = x.shape[0]
                return x.reshape(pp_mb, b // pp_mb, *x.shape[1:])
            mbs = jax.tree_util.tree_map_with_path(psplit, batch)

            def pipe_loss(p, mb_tree):
                return api.pipeline_train_forward(
                    p, mb_tree, cfg, n_stages=pp_stages,
                    stage_axis=pp_axis)

            if pp_sched == "gpipe":
                # one M-wave scan, one backward — autodiff replays the
                # waves in reverse, the synchronous GPipe flush
                loss, grads = jax.value_and_grad(
                    lambda p: pipe_loss(p, mbs))(params)
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32), grads)
            else:
                # 1f1b: chunks of S microbatches, each differentiated on
                # its own — at most S microbatches of activations live
                # at once (the 1F1B in-flight bound)
                loss = jnp.float32(0.0)
                grads = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                for c0 in range(0, pp_mb, pp_stages):
                    chunk = jax.tree.map(
                        lambda v: v[c0:c0 + pp_stages], mbs)
                    l, g = jax.value_and_grad(
                        lambda p: pipe_loss(p, chunk))(params)
                    loss = loss + l
                    grads = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), grads, g)
            loss = loss / pp_mb
            grads = jax.tree.map(lambda g: g / pp_mb, grads)
        elif microbatch > 1:
            # grad accumulation: scan over microbatches — activations live
            # only for one microbatch (temp memory ÷ microbatch).  Each
            # microbatch sees its 1/M share of the batch-level
            # normalizer, and the accumulated loss/grads are divided by
            # M below — the mean over microbatches, NOT the sum, so the
            # effective LR and the reported loss are independent of M.
            def split(path, x):
                if np.ndim(x) == 0:
                    if any(getattr(k, "key", None) == "global_tokens"
                           for k in path):
                        x = x / microbatch
                    return jnp.broadcast_to(x, (microbatch,))
                b = x.shape[0]
                return x.reshape(microbatch, b // microbatch, *x.shape[1:])
            mbs = jax.tree_util.tree_map_with_path(split, batch)

            def body(acc, mb):
                l, g = jax.value_and_grad(
                    lambda p: api.train_forward(p, mb, cfg))(params)
                acc_l, acc_g = acc
                acc_g = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc_g, g)
                return (acc_l + l, acc_g), None

            zero = (jnp.float32(0.0),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            # chunk_unroll = exact-HLO-accounting mode (dry-run deltas):
            # unroll so cost_analysis sees every microbatch
            mb_unroll = microbatch if getattr(
                cfg, "chunk_unroll", False) else 1
            if accum_overlap:
                # accumulation-overlapped sync: peel the FINAL
                # microbatch out of the scan so its backward is emitted
                # inline — each sync/RS bucket starts the moment this
                # backward produces its gradients, overlapping the
                # accumulation tail instead of waiting behind the scan.
                # Same accumulation order as the plain scan: bit-exact.
                head = jax.tree.map(lambda v: v[:-1], mbs)
                last = jax.tree.map(lambda v: v[-1], mbs)
                acc, _ = jax.lax.scan(body, zero, head, unroll=mb_unroll)
                (loss, grads), _ = body(acc, last)
            else:
                (loss, grads), _ = jax.lax.scan(body, zero, mbs,
                                                unroll=mb_unroll)
            loss = loss / microbatch
            grads = jax.tree.map(lambda g: g / microbatch, grads)
        else:
            loss, grads = jax.value_and_grad(
                lambda p: api.train_forward(p, batch, cfg))(params)
        if tp > 1:   # psum-transpose inflation (module docstring)
            grads = jax.tree.map(lambda g: g / tp, grads)
        if zero1_scheduled:
            # StepProgram: ONE schedule carries the model-axis sync ops
            # AND the per-bucket zero1 RS→UPDATE→AG triples; clipping is
            # the scheduled NORM op (psum'd squared shard norms, applied
            # to the grad shards before each update)
            update_fn, new_state = scheduled_update(
                inner_opt, gs.dp_plan, params, opt_state, step_idx,
                dp_size=dp_size)
            aux: dict = {}
            updates = gs(grads, update_fn=update_fn,
                         clip_norm=float(clip_norm or 0.0), aux=aux,
                         schedule=post_sched if defer_ag else None)
            if defer_ag:
                # the AGs were deferred: carry this step's update shards
                # to the next step's PRE program instead of applying
                new_state["pending"] = {
                    k: aux["update_shards"][bid] for bid, k in pend_keys}
                updates = None
            opt_state = new_state
            gnorm = aux.get("grad_norm", jnp.float32(0.0))
        else:
            # zero1_mode (monolithic): sync.exclude_axes=dp — buckets
            # carry only the model-axis reductions; the DP sum happens
            # in zero1's reduce-scatter inside optimizer.update.
            grads = gs(grads)
            if clip_norm and not zero1_mode:
                if pp_active:
                    # stage-sharded blocks: their squared norms psum
                    # over "stage"; stage-replicated leaves count once
                    from repro.parallel.sharding import flat_spec_axes

                    stg = [pp_axis in flat_spec_axes(s)
                           for s in jax.tree.leaves(pspecs)]

                    def _sq(g, staged):
                        g32 = jnp.square(g.astype(jnp.float32))
                        if staged:
                            # reduce each stacked layer row, psum the
                            # per-leaf partial over "stage" BEFORE the
                            # cross-leaf sum: the scalar then matches
                            # the stage=1 layout bit-for-bit (psum adds
                            # the same per-layer partials in the same
                            # layer order, leaf by leaf)
                            return jax.lax.psum(jnp.sum(jnp.sum(
                                g32.reshape(g32.shape[0], -1), axis=1)),
                                pp_axis)
                        return jnp.sum(g32)

                    sq = [_sq(g, t) for g, t in
                          zip(jax.tree.leaves(grads), stg)]
                    sh = sum(s for s, t in zip(sq, stg) if t)
                    rep = sum(s for s, t in zip(sq, stg) if not t)
                    gnorm = jnp.sqrt(jnp.float32(sh) + jnp.float32(rep))
                    scale = jnp.minimum(
                        1.0, clip_norm / (gnorm + 1e-9))
                    grads = jax.tree.map(
                        lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads)
                else:
                    # (monolithic zero1: grads are still DP-partial
                    # here — use zero1_plan="scheduled" for clipped
                    # ZeRO training)
                    grads, gnorm = clip_by_global_norm(grads, clip_norm)
            else:
                gnorm = jnp.float32(0.0)
            updates, opt_state = optimizer.update(
                grads, opt_state, params, step_idx)
        if updates is not None:
            params = apply_updates(params, updates)
        if pp_active:
            # the staged loss is nonzero only on the last stage — the
            # psum adds the other stages' exact zeros (bit-exact)
            loss = jax.lax.psum(loss, pp_axis)
        loss = jax.lax.psum(loss, dp) if dp else loss
        metrics = {"loss": loss, "grad_norm": gnorm}
        return params, opt_state, metrics

    mspecs = {"loss": P(), "grad_norm": P()}
    wrapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ospecs, bspecs, P()),
        out_specs=(pspecs, ospecs, mspecs),
        check_vma=False)
    jitted = jax.jit(wrapped, donate_argnums=(0, 1) if donate else ())

    finalize = None
    if defer_ag:
        # flush the carried update shards (same PRE program the next
        # step would run) — for eval/checkpoint-export/parity checks
        finalize = jax.jit(jax.shard_map(
            gather_pending, mesh=mesh, in_specs=(pspecs, ospecs),
            out_specs=pspecs, check_vma=False))

    return TrainStep(jitted, pspecs, ospecs, bspecs, mesh, gs,
                     opt_state_like, finalize=finalize)


class Trainer:
    """Fault-tolerant training driver.

    - checkpoint/restart via CheckpointManager (atomic, async)
    - deterministic data (batch = f(seed, step)) → exact resume
    - failure injection (``fail_at``): simulates node loss at given steps;
      recovery = restore latest checkpoint and replay
    - straggler mitigation: steps slower than ``straggler_factor`` × the
      running median are logged and counted; after ``straggler_patience``
      consecutive hits the (simulated) response is a re-shard event —
      on a real fleet this triggers hot-spare swap-in
    - one step of input lookahead: once step k is dispatched, and before
      the host waits on it, ``run`` builds step k+1's batch, so the build
      runs while the device computes (DESIGN.md §12).  This relies on
      ``pipeline.batch_at`` being a pure function of the step.  At most
      one batch is built ahead and resident on the device, never for a
      step at or past ``num_steps``, and it lives only in ``run``'s
      locals.  An exception the lookahead build raises is kept until the
      step it belongs to and raised there, so step k still commits.  A
      retried step reuses its batch; a batch kept for a step that a
      recovery or restart skips is dropped (``input_ahead_total`` counts
      committed steps that trained on a batch built ahead,
      ``input_ahead_dropped_total`` those dropped)
    """

    def __init__(self, step_fn: TrainStep, pipeline, ckpt,
                 *, fail_at: frozenset[int] = frozenset(),
                 straggler_factor: float = 3.0,
                 straggler_patience: int = 3,
                 step_retries: int = 0,
                 fault_injector: Callable[[int], None] | None = None,
                 remesh_hook: Callable[[int], str | None] | None = None,
                 log_every: int = 10,
                 printer: Callable[[str], None] = print,
                 metrics: "MetricsRegistry | None" = None,
                 events_path: str | None = None,
                 loss_window: int = 10_000):
        from repro.obs import EventLog, MetricsRegistry

        self.step_fn = step_fn
        self.pipeline = pipeline
        self.ckpt = ckpt
        self.fail_at = set(fail_at)
        self.straggler_factor = straggler_factor
        self.straggler_patience = straggler_patience
        # elastic policy ladder (DESIGN.md §13): transient faults retry
        # the same step in place before escalating to checkpoint
        # recovery; ``fault_injector(step)`` runs at the top of every
        # step attempt (raise TransientStepError / RankLost / sleep to
        # fake a straggler); ``remesh_hook(step)`` decides the response
        # to persistent stragglers ("shrink" → raise RemeshRequest for
        # the supervisor; anything else → log only)
        self.step_retries = step_retries
        self.fault_injector = fault_injector
        self.remesh_hook = remesh_hook
        self.log_every = log_every
        self.printer = printer
        self.step_times: list[float] = []
        self.events: list[dict] = []
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.event_log = EventLog(events_path)
        self.loss_window = loss_window
        # first executed step spans the jit warmup compile — reported
        # separately, excluded from step_times / throughput stats
        self.compile_time: float | None = None

    def _event(self, kind: str, **fields) -> None:
        """Record a lifecycle event in-memory AND on the JSONL stream."""
        self.events.append({"kind": kind, **fields})
        self.event_log.emit(kind, **fields)

    def _place_restored(self, tree: Any, specs: Any) -> Any:
        """Commit restored leaves to the step's shardings.  Host (numpy)
        leaves are device_put; leaves that are already device arrays
        (an ElasticCheckpointer decode) pass through unchanged."""
        sh = self.step_fn.shardings(specs)
        return jax.tree.map(
            lambda v, s: jax.device_put(v, s)
            if isinstance(v, np.ndarray) else v, tree, sh)

    def _guard_pending(self, step: int) -> None:
        """Deferred-plan restore guard: if this step carries an
        ``opt_state["pending"]`` tree, the checkpoint being restored must
        actually contain one — otherwise the resume would silently read
        a zero carry where the saved trajectory had live update shards,
        and the replayed run diverges from the original."""
        like = getattr(self.step_fn, "opt_state_like", None)
        if not isinstance(like, dict) or "pending" not in like:
            return
        manifest = getattr(self.ckpt, "manifest", None)
        if manifest is None:
            return
        try:
            names = manifest(step)
        except (OSError, KeyError, ValueError):
            return      # no manifest to check against — restore decides
        if not any("pending" in n for n in names):
            raise RuntimeError(
                f"checkpoint at step {step} has no opt_state['pending'] "
                f"carry but this zero1_plan='deferred' step requires one "
                f"— resuming would silently drop the deferred updates "
                f"(flush via TrainStep.finalize before saving, or restore "
                f"into a scheduled-plan step)")

    def _recover(self, params, opt_state):
        """Restore-and-replay (rung 2 of the policy ladder).  Returns
        ``(step, params, opt_state)`` or None when no checkpoint
        exists."""
        if self.ckpt is None or self.ckpt.latest() is None:
            return None
        self._guard_pending(self.ckpt.latest())
        s, state = self.ckpt.restore({"params": params, "opt": opt_state})
        params = self._place_restored(state["params"],
                                      self.step_fn.param_specs)
        opt_state = self._place_restored(state["opt"],
                                         self.step_fn.opt_specs)
        self._event("recover", step=s)
        return s, params, opt_state

    def _account_static(self, params, opt_state) -> None:
        """One-time gauges/counters that don't change per step: comm
        bytes per step by op kind/reducer/phase (from the planned
        schedule), a peak-memory proxy (resident params + opt state),
        and the simulator's exposed-comm estimate for this plan."""
        from repro.obs import comm_byte_counters

        state_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves((params, opt_state))
            if hasattr(x, "shape"))
        self.metrics.gauge("mem.state_bytes").set(state_bytes)
        gs = getattr(self.step_fn, "gradsync", None)
        if gs is None:
            return
        comm_byte_counters(
            gs.schedule, self.metrics,
            itemsize=np.dtype(gs.cfg.comm_dtype).itemsize)
        try:
            from repro.sim.engine import SimConfig, simulate

            tl = simulate(
                gs.schedule, gs.mesh_shape,
                compute=gs.cfg.sim_compute,
                sim=SimConfig(
                    itemsize=np.dtype(gs.cfg.comm_dtype).itemsize,
                    reducer=gs.cfg.reducer,
                    fused_staging=gs.cfg.use_fused_staging))
            self.metrics.gauge("sim.step_time_s").set(tl.step_time)
            self.metrics.gauge("sim.exposed_comm_s").set(tl.exposed_comm)
        except Exception:
            pass    # an estimate must never take down training

    def _account(self, step: int, dt: float, tokens: int, metrics,
                 losses, params, opt_state, first: bool,
                 consec_slow: int) -> int:
        """After a committed step: step-time stats, straggler check,
        loss readback, metrics, events, heartbeat and the checkpoint of
        the post-step state; returns the new run of slow steps."""
        from repro.obs import heartbeat_line

        if first:
            # the first executed step spans the jit warmup compile:
            # report it separately, keep it out of every throughput
            # stat (step_times, histograms, tokens/s, stragglers)
            self.compile_time = dt
            self.metrics.gauge("compile_time_s").set(dt)
            self._event("compile", step=step, dt=dt)
        else:
            if len(self.step_times) >= 5:
                med = statistics.median(self.step_times[-50:])
                if dt > self.straggler_factor * med:
                    consec_slow += 1
                    self._event("straggler", step=step, dt=dt,
                                median=med)
                    if consec_slow >= self.straggler_patience:
                        decision = (self.remesh_hook(step)
                                    if self.remesh_hook else None)
                        self._event("remesh_requested", step=step,
                                    decision=decision or "log-only")
                        self.printer(
                            f"[trainer] {consec_slow} consecutive "
                            f"straggler steps — requesting re-shard / "
                            f"hot-spare swap "
                            f"({decision or 'log-only'})")
                        consec_slow = 0
                        if decision == "shrink":
                            # hand the committed post-step state to
                            # the supervisor; resume at step + 1
                            e = RemeshRequest(
                                f"straggler shrink @ {step}")
                            e.step = step + 1
                            e.params, e.opt_state = params, opt_state
                            raise e
                else:
                    consec_slow = 0
            self.step_times.append(dt)
            self.metrics.histogram("step_time_s").observe(dt)
            if tokens:
                self.metrics.counter("tokens_total").inc(tokens)
                self.metrics.gauge("tokens_per_s").set(tokens / dt)

        loss = float(metrics["loss"])
        gnorm = float(metrics.get("grad_norm", 0.0))
        losses.append(loss)
        self.metrics.counter("steps_total").inc()
        self.metrics.gauge("loss").set(loss)
        self.metrics.gauge("grad_norm").set(gnorm)
        self.event_log.emit(
            "step", step=step, loss=loss, dt=dt, grad_norm=gnorm,
            tokens=tokens, compile_step=self.compile_time == dt)
        if step % self.log_every == 0:
            self.printer(
                f"[trainer] step {step} loss {losses[-1]:.4f} "
                f"({dt*1e3:.1f} ms)")
            avg = (sum(self.step_times[-50:])
                   / max(len(self.step_times[-50:]), 1) * 1e3
                   if self.step_times else None)
            self.printer(heartbeat_line(
                step, loss=loss, step_ms=dt * 1e3, avg_ms=avg,
                tokens_per_s=(tokens / dt if tokens else None),
                grad_norm=gnorm, compile_s=self.compile_time))
        if self.ckpt is not None:
            self.ckpt.maybe_save(
                step + 1, {"params": params, "opt": opt_state})
        return consec_slow

    def _build_ahead(self, step: int) -> tuple[int, Any, bool]:
        """``step``'s batch built ahead, or the exception its build raised,
        kept for that step: ``(step, batch or exception, True)``."""
        from repro.obs import span

        with span("train.input", ahead=True):
            try:
                return step, self.pipeline.batch_at(step), True
            except Exception as e:
                # raised at the top of ``step``, where a build in place
                # would have raised it
                return step, e, True

    def run(self, params, opt_state, num_steps: int,
            start_step: int = 0) -> tuple[Any, Any, dict]:
        from collections import deque

        from repro.obs import span, step_span

        step = start_step
        if self.ckpt is not None and self.ckpt.latest() is not None:
            self._guard_pending(self.ckpt.latest())
            step, state = self.ckpt.restore(
                {"params": params, "opt": opt_state})
            params = self._place_restored(state["params"],
                                          self.step_fn.param_specs)
            opt_state = self._place_restored(state["opt"],
                                             self.step_fn.opt_specs)
            self._event("restore", step=step)
            self.printer(f"[trainer] restored checkpoint at step {step}")

        self._account_static(params, opt_state)
        losses = deque(maxlen=self.loss_window)
        consec_slow = 0
        retries_used = 0
        first_timed = self.compile_time is None
        ahead_used = self.metrics.counter("input_ahead_total")
        ahead_dropped = self.metrics.counter("input_ahead_dropped_total")
        # (step, its batch or the exception its build raised, built ahead)
        kept: tuple[int, Any, bool] | None = None
        while step < num_steps:
            with step_span("train.step", step):
                if kept is not None and kept[0] != step:
                    if kept[2]:
                        ahead_dropped.inc()
                    kept = None
                if kept is None:
                    with span("train.input", ahead=False):
                        kept = (step, self.pipeline.batch_at(step), False)
                _, batch, built_ahead = kept
                if isinstance(batch, Exception):
                    raise batch
                tokens = sum(
                    int(np.prod(v.shape)) for k, v in batch.items()
                    if k == "tokens") if isinstance(batch, dict) else 0
                t0 = time.perf_counter()
                try:
                    # injected faults fire at the top of the attempt —
                    # AFTER t0, so a straggler sleep injected here counts
                    # in dt
                    if self.fault_injector is not None:
                        self.fault_injector(step)
                    if step in self.fail_at:
                        self.fail_at.discard(step)
                        raise SimulatedFailure(f"injected node loss @ {step}")
                    with span("train.dispatch"):
                        params, opt_state, metrics = self.step_fn.fn(
                            params, opt_state, batch, jnp.int32(step))
                    nxt = (self._build_ahead(step + 1)
                           if step + 1 < num_steps else None)
                    with span("train.wait"):
                        jax.block_until_ready(metrics["loss"])
                    retries_used = 0
                except TransientStepError as e:
                    # rung 1: the step never committed state — retry in place
                    retries_used += 1
                    if retries_used <= self.step_retries:
                        self._event("retry", step=step, attempt=retries_used)
                        self.printer(f"[trainer] transient fault @ {step} "
                                     f"({e}); retry {retries_used}/"
                                     f"{self.step_retries}")
                        continue
                    retries_used = 0
                    self._event("retry_exhausted", step=step)
                    self.printer(f"[trainer] {e}; retries exhausted — "
                                 f"recovering from checkpoint")
                    recovered = self._recover(params, opt_state)
                    if recovered is None:
                        self.printer("[trainer] no checkpoint; restart from 0")
                        step = start_step
                        continue
                    step, params, opt_state = recovered
                    continue
                except RankLost as e:
                    # rung 3 lives OUTSIDE the loop: a lost rank means this
                    # mesh is gone — hand the last committed state to the
                    # supervisor (repro.elastic) and unwind
                    e.step = step
                    e.params, e.opt_state = params, opt_state
                    self._event("rank_lost", step=step)
                    self.printer(f"[trainer] {e}; surrendering to supervisor")
                    raise
                except SimulatedFailure as e:
                    self._event("failure", step=step)
                    self.printer(f"[trainer] {e}; recovering from checkpoint")
                    recovered = self._recover(params, opt_state)
                    if recovered is None:
                        self.printer("[trainer] no checkpoint; restart from 0")
                        step = start_step
                        continue
                    step, params, opt_state = recovered
                    continue

                with span("train.account"):
                    consec_slow = self._account(
                        step, time.perf_counter() - t0, tokens, metrics,
                        losses, params, opt_state, first_timed, consec_slow)
                if built_ahead:
                    ahead_used.inc()
                kept = nxt
                first_timed = False
                step += 1

        if self.ckpt is not None:
            self.ckpt.wait()
        return params, opt_state, {
            "losses": list(losses),
            "events": self.events,
            "compile_time": self.compile_time,
            "metrics": self.metrics.snapshot(),
        }
