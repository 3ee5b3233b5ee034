"""Data-parallel image-classifier training, as ``repro.launch.train`` runs
it: ``make_train_step`` over a ``data = chips`` mesh, driven by
``Trainer.run`` on the synthetic ``ImagePipeline`` (seeded by ``--seed``).

Set-up builds one step and its state, and drives it through its first
``check_steps`` steps with the window's own ``Trainer`` and feed; the
plain reference follows those steps.  The window then continues the same
run, a closed loop of steps, until ``--seconds`` have passed: every step
whose input was built inside the window counts, and input generation is
part of the window.

Readings against the reference (the configuration's ``limits`` name
the ones compared; every one is logged):

- ``loss_gap``: the largest relative gap of the first steps' losses
  (``loss0_gap``: the first step's alone);
- ``grad_norm_gap``: the relative gap of the first gradient's global
  norm before clipping (the step's own ``grad_norm`` metric);
- ``grad_gap``: the gradient as SGD received it at step 1 (the momentum
  buffer after one step), per leaf: the gap between the program's norm
  and the reference's, over the larger of that leaf's reference norm and
  the median leaf's; the worst leaf (``grad_gap_median``: the median);
- ``update_gap``: the same for the change of each parameter over the
  first steps (``update_gap_median``: the median leaf).
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np


class WindowClosed(Exception):
    """Raised by the feed at the first step due after the window."""


class Feed:
    """``batch_at`` for the Trainer: times each input build under the
    span ``bench.train.input``; once armed, it opens the window at its
    first call, starts the trace when the traced part begins, and ends
    the window by raising ``WindowClosed``."""

    def __init__(self, pipe, run):
        self.pipe, self.run = pipe, run
        self.armed = False
        self.t0 = self.t_end = None
        self.builds: list[tuple[float, float]] = []   # (start, seconds)

    def batch_at(self, step: int):
        import jax

        now = time.perf_counter()
        if self.armed:
            if self.t0 is None:
                self.t0 = now
            run = self.run
            trace_at = self.t0 + run.seconds - run.cell.traffic["trace_s"]
            if run.trace and run.trace_window is None and now >= trace_at:
                run.trace_start()
            if now >= self.t0 + run.seconds:
                self.t_end = now
                run.trace_stop()
                raise WindowClosed
        with jax.profiler.TraceAnnotation("bench.train.input"):
            t = time.perf_counter()
            batch = self.pipe.batch_at(step)
            self.builds.append((t, time.perf_counter() - t))
        return batch


def _host(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _leaf_norms(tree) -> list[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree.leaves(tree)]


def leaf_gaps(prog: list[float], ref: list[float],
              keep: list[bool] | None = None) -> list[float]:
    """Per leaf, |prog norm - ref norm| / max(ref norm, median ref norm);
    0 for the leaves ``keep`` leaves out."""
    keep = keep or [True] * len(ref)
    med = statistics.median(r for r, k in zip(ref, keep) if k)
    return [abs(p - r) / max(r, med) if k else 0.0
            for p, r, k in zip(prog, ref, keep)]


def _worst(run, name, gaps, paths, n: int = 3) -> float:
    top = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:n]
    run.log(f"[train] {name} worst leaves "
            + ", ".join(f"{paths[i]} {gaps[i]:.4f}" for i in top))
    return max(gaps)


def program_config(cfg: dict):
    """The system's model configuration from the configuration file."""
    from repro.configs import get_arch

    s = cfg["sizes"]
    if (s["stem_kernel"], s["bottleneck_ratio"]) != (3, 4):
        raise ValueError("the system's ResNet has a 3x3 stem and 4x "
                         "bottlenecks")
    return get_arch(cfg["program"]["arch"]).make_config(
        stages=tuple(s["stages"]), widths=tuple(s["widths"]),
        stem_width=s["stem_width"], num_classes=s["num_classes"],
        img_size=s["image_size"])


def readings(run, prog_losses, prog_mom1, prog_p, prog_norm0, p0,
             ref_out) -> dict:
    """Every candidate number of the comparison (module docstring), with
    the worst leaves of each per-leaf gap logged."""
    import jax

    ref_losses, ref_mom1, ref_p, ref_norm0 = ref_out
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(ref_mom1)[0]]
    rel = [abs(a - b) / abs(b) for a, b in zip(prog_losses, ref_losses)]
    g_ref = _leaf_norms(ref_mom1)
    g = leaf_gaps(_leaf_norms(prog_mom1), g_ref)
    d_prog = [a - b for a, b in zip(jax.tree.leaves(prog_p),
                                    jax.tree.leaves(p0))]
    d_ref = [a - b for a, b in zip(jax.tree.leaves(ref_p),
                                   jax.tree.leaves(p0))]
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone: left out by a rule on that gradient
    med = statistics.median(g_ref)
    keep = [x >= 1e-3 * med for x in g_ref]
    u = leaf_gaps(_leaf_norms(d_prog), _leaf_norms(d_ref), keep)
    run.log(f"[train] losses program {prog_losses} reference {ref_losses}")
    return {"loss_gap": max(rel), "loss0_gap": rel[0],
            "grad_norm_gap": abs(prog_norm0 - ref_norm0) / ref_norm0,
            "grad_gap": _worst(run, "grad_gap", g, paths),
            "grad_gap_median": statistics.median(g),
            "update_gap": _worst(run, "update_gap", u, paths),
            "update_gap_median": statistics.median(
                x for x, k in zip(u, keep) if k)}


def compare(run, *args) -> None:
    """Check the numbers the cell's limits name; log the others."""
    got = readings(run, *args)
    run.log("[train] readings " + " ".join(f"{k} {v!r}"
                                           for k, v in got.items()))
    for name in run.cell.limits():
        run.check(name, got[name])


def run(run) -> None:
    import jax

    from repro.core import GradSyncConfig
    from repro.data import ImagePipeline
    from repro.launch.mesh import make_local_mesh
    from repro.models.registry import family_of
    from repro.optim import sgd
    from repro.runtime import Trainer, make_train_step

    cfg, tr = run.cell.config, run.cell.traffic
    sizes, opt_cfg, sync_cfg = cfg["sizes"], cfg["optimizer"], cfg["grad_sync"]
    ref = run.cell.reference()
    prog = program_config(cfg)
    chips = run.cell.chips
    if tr["mesh"]["data"] != chips:
        raise ValueError(f"traffic mesh {tr['mesh']} needs {chips} chips")
    mesh = make_local_mesh(1, devices=run.devices)
    B, n_check = tr["global_batch"], tr["check_steps"]
    pipe = ImagePipeline(sizes["image_size"], sizes["num_classes"], B,
                         seed=run.seed, mesh=mesh)

    params = ref.init_weights(run.seed, sizes)
    like = jax.eval_shape(
        lambda: family_of(prog).init(jax.random.PRNGKey(0), prog))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), like)
    if got != want:
        raise ValueError("weight tree differs from the system's layout")
    p0 = _host(params)
    opt = sgd(opt_cfg["lr"], momentum=opt_cfg["momentum"])
    ts = make_train_step(
        prog, mesh, GradSyncConfig(strategy=sync_cfg["strategy"],
                                   bucket_bytes=sync_cfg["bucket_bytes"],
                                   num_channels=sync_cfg["channels"],
                                   comm_dtype=jax.numpy.dtype(
                                       sync_cfg["wire_dtype"])),
        opt, batch_like=pipe.batch_at(0), params_like=params,
        clip_norm=opt_cfg["clip_norm"], donate=cfg["donate"])
    params = jax.device_put(params, ts.shardings(ts.param_specs))
    feed = Feed(pipe, run)
    trainer = Trainer(ts, feed, None, log_every=10**9, printer=run.log)

    # the first steps, through the window's own trainer and feed
    params, opt_state, h = trainer.run(params, ts.init_opt(), 1)
    losses = list(h["losses"])
    mom1 = _host(opt_state["mom"])
    norm0 = trainer.metrics.gauge("grad_norm").value
    params, opt_state, h = trainer.run(params, opt_state, n_check,
                                       start_step=1)
    losses += list(h["losses"])
    p_check = _host(params)
    run.log(f"[train] first step (compile + run) {trainer.compile_time:.3f}s")

    # the window: the same run, continued
    feed.armed = True
    n_builds = len(feed.builds)
    try:
        trainer.run(params, opt_state, 10**12, start_step=n_check)
    except WindowClosed:
        pass
    del params, opt_state
    run.setup_s = feed.t0 - run.t_start
    window = feed.t_end - feed.t0
    steps = len(feed.builds) - n_builds
    run.attempted, run.failed = steps, 0
    run.e2e["train_samples_per_s"] = steps * B / window
    builds = feed.builds[n_builds:]
    run.counters.update(
        window_s=window, steps=steps, global_batch=B,
        samples_per_s=steps * B / window,
        input_s=[s for _, s in builds],
        flops_per_sample=ref.train_flops_per_sample(sizes))
    run.log(f"[train] window {window:.3f}s: {steps} steps, "
            f"{steps * B / window:.1f} samples/s; input build mean "
            f"{1e3 * statistics.mean(s for _, s in builds):.2f} ms")
    run.read_memory_peak()

    # the reference, once the program's state is freed
    del trainer, ts, feed, pipe
    gc.collect()
    batches = [ref.batch(run.seed, k, B, sizes["image_size"],
                         sizes["num_classes"]) for k in range(n_check)]
    t = time.perf_counter()
    ref_out = ref.sgd_run(ref.init_weights(run.seed, sizes), batches, sizes,
                          groups=chips, lr=opt_cfg["lr"],
                          momentum=opt_cfg["momentum"],
                          clip_norm=opt_cfg["clip_norm"])
    run.log(f"[train] reference {time.perf_counter() - t:.3f}s")
    compare(run, losses, mom1, p_check, norm0, p0, ref_out)
