"""HLO schedule evidence: how each strategy's dependency structure lands
in the compiled program (EXPERIMENTS §Paper-validation point 3).

Compiles one small train step per REGISTERED strategy (8 fake devices —
run standalone), plain AND as the ZeRO-1 StepProgram (`<name>+zero1`
rows), and reports, per row:
  - the CommSchedule IR statistics (op count, chain count, longest
    chain, UPDATE-op count) — the planned dependency structure,
    asserted in microseconds.  StepProgram rows carry the per-bucket
    RS→UPDATE→AG triples + the NORM clip op in the same IR,
  - number of HLO collective ops (all-reduce + reduce-scatter +
    all-gather) and how many sit inside the while-loop body (depcha:
    per-layer in-scan psums → pipelinable by XLA),
  - the repro.sim discrete-event prediction for the SAME planned
    schedule on the same 2×4 mesh (step time, exposed comm, overlap;
    UPDATE ops costed as shard-update HBM time) — the simulated
    timeline printed next to the chain stats it explains.

Expected IR shapes: funnel = 1 chain through every bucket; concom and
priority ≈ num_channels chains; rsag = 2 ops (RS+AG) per bucket; auto
delegates to the simulator's predicted winner; `+zero1` rows add
3 ops per dp bucket + 1 NORM.

    PYTHONPATH=src python -m benchmarks.schedule_analysis
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import re
import warnings

warnings.filterwarnings("ignore")

_COLL = r"(?:all-reduce|reduce-scatter|all-gather)"


def analyze(strategy: str, zero1: str = "") -> dict:
    """One row: ``zero1`` is "" (plain sync), "scheduled" (StepProgram)
    or "deferred" (phase-split StepProgram — the AGs tagged PRE)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.core import GradSyncConfig, get_strategy
    from repro.data import TokenPipeline
    from repro.models import transformer as tf
    from repro.optim import adamw, zero1 as make_zero1
    from repro.runtime import make_train_step
    from repro.sim import compute_model_for, sim_config_for, simulate

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = tf.TransformerConfig(
        name="sched", n_layers=4, d_model=64, n_heads=8, kv_heads=4,
        d_ff=128, vocab=128, tp=4, attn_chunk=32, dtype=jnp.float32,
        depcha_in_scan=get_strategy(strategy).uses_in_scan)
    pipe = TokenPipeline(cfg.vocab, 32, 8, mesh=mesh)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = pipe.batch_at(0)
    opt = make_zero1(adamw(1e-3), ("data",), 2) if zero1 else adamw(1e-3)
    ts = make_train_step(
        cfg, mesh,
        GradSyncConfig(strategy=strategy, num_channels=4, bucket_bytes=0,
                       exclude_axes=("data",) if zero1 else ()),
        opt, batch_like=batch, params_like=params, zero1_mode=bool(zero1),
        zero1_plan=zero1 or "scheduled")
    ir = ts.gradsync.schedule.stats()
    phases = ir["phases"]
    # static analyzer verdict for the planned schedule (DESIGN.md §11):
    # "OK" or the distinct pass:code error classes
    from repro.analysis import run_passes

    report = run_passes(
        ts.gradsync.schedule,
        mesh_shape=ts.gradsync.mesh_shape,
        default_reducer=ts.gradsync.cfg.reducer,
        plan_comm_dtype=ts.gradsync.cfg.comm_dtype,
        expect_defer=zero1 == "deferred")
    verdict = "OK" if report.ok else ";".join(report.error_classes)
    # simulated timeline of the SAME planned schedule on this 2×4 mesh
    # (UPDATE/NORM ops of the StepProgram rows costed by the engine;
    # deferred rows in pipelined steady state — PRE gathers at the top)
    mesh_shape = {"data": 2, "model": 4}
    compute = compute_model_for(cfg, global_batch=8, seq_len=32,
                                n_devices=8)
    if zero1 == "deferred":
        from repro.sim import simulate_pipelined

        post, pre = ts.gradsync.schedule.split_phases()
        tl = simulate_pipelined(post, pre, mesh_shape, compute=compute,
                                sim=sim_config_for(strategy))
    else:
        tl = simulate(ts.gradsync.schedule, mesh_shape, compute=compute,
                      sim=sim_config_for(strategy))
    opt_state = ts.init_opt()
    lowered = ts.fn.lower(params, opt_state, batch, jnp.int32(0))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # measured wall time of the compiled step (8 fake CPU devices —
    # orders overhead, not network) next to the sim prediction
    from repro.obs import host_time_us

    step0 = jnp.int32(0)
    measured_us = host_time_us(
        lambda: compiled(params, opt_state, batch, step0), reps=3)

    total = len(re.findall(rf"= [^=\n]*{_COLL}\(", hlo))
    # collectives inside while-loop bodies (depcha: per-layer in-scan psums)
    body_names = set(re.findall(r"body=%([\w.-]+)", hlo))
    in_loop = 0
    for name in body_names:
        idx = hlo.find("\n%" + name)
        if idx < 0:
            continue
        end = hlo.find("\n}", idx)
        seg = hlo[idx:end if end > 0 else idx + 200000]
        in_loop += len(re.findall(rf"= [^=\n]*{_COLL}\(", seg))
    tag = {"": "", "scheduled": "+zero1", "deferred": "+zero1d"}[zero1]
    return {"strategy": strategy + tag,
            "analyzer": verdict,
            "ir_ops": ir["num_ops"],
            "ir_chains": ir["num_chains"],
            "ir_max_chain": ir["max_chain_len"],
            "ir_update_ops": ir["kinds"].get("update", 0),
            "ir_pre_ops": phases.get("pre", 0),
            "ir_post_ops": phases.get("post", 0),
            "deferred_kb": ts.gradsync.schedule.deferred_bytes() / 1024,
            "collective_ops": total,
            "in_loop_body": in_loop,
            "loop_trip_multiplied": in_loop * 4,   # n_layers=4
            "sim_step_us": tl.step_time * 1e6,
            "sim_exposed_us": tl.exposed_comm * 1e6,
            "sim_overlap": tl.overlap_fraction,
            "measured_us": measured_us,
            "measured_vs_sim": measured_us / (tl.step_time * 1e6)}


def main():
    import repro.sim  # noqa: F401  (registers the "auto" strategy)

    from repro.core import strategy_names

    print("strategy,analyzer,ir_ops,ir_chains,ir_max_chain,ir_update_ops,"
          "ir_pre_ops,ir_post_ops,deferred_kb,"
          "collective_ops_static,in_loop_body,runtime_collectives(~),"
          "sim_step_us,sim_exposed_us,sim_overlap,"
          "measured_us,measured_vs_sim")
    for s in strategy_names():
        for zero1 in ("", "scheduled", "deferred"):
            r = analyze(s, zero1=zero1)
            runtime = (r["collective_ops"] - r["in_loop_body"]
                       + r["loop_trip_multiplied"])
            print(f"{r['strategy']},{r['analyzer']},"
                  f"{r['ir_ops']},{r['ir_chains']},"
                  f"{r['ir_max_chain']},{r['ir_update_ops']},"
                  f"{r['ir_pre_ops']},{r['ir_post_ops']},"
                  f"{r['deferred_kb']:.0f},"
                  f"{r['collective_ops']},"
                  f"{r['in_loop_body']},{runtime},"
                  f"{r['sim_step_us']:.1f},{r['sim_exposed_us']:.1f},"
                  f"{r['sim_overlap']:.2f},"
                  f"{r['measured_us']:.1f},{r['measured_vs_sim']:.2f}")


if __name__ == "__main__":
    main()
