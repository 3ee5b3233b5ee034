"""Elastic scaling demo — online-first (DESIGN.md §13).

Part 1 (online): a fault-injecting `repro.elastic.Supervisor` drives
ZeRO-1 training through a live 8→4→8 device cycle: a transient step
fault is retried in place, checkpoint-I/O faults are absorbed by the
manager's backoff, and a simulated rank loss at step 5 shrinks the mesh
tp4→tp2 by resharding params AND optimizer shards as *scheduled*
RESHARD/REGROUP collectives — then grows back. A clean scripted replay
of the same mesh trajectory reproduces the faulty run bit-for-bit.

Part 2 (offline fallback): the original checkpoint-round-trip resize —
restore an 8-device checkpoint onto a 4-device mesh via
`checkpoint.reshard` — kept for the cold-restart path where no live
group survives.

This script forces 8 fake CPU devices, so run it standalone:

    PYTHONPATH=src python examples/elastic_restart.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import tempfile
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.checkpoint import CheckpointManager, reshard
from repro.core import GradSyncConfig
from repro.data import TokenPipeline
from repro.elastic import FaultPlan, Supervisor
from repro.models import transformer as tf
from repro.models.registry import family_of
from repro.optim import adamw, zero1
from repro.runtime import Trainer, make_train_step
from repro.utils.trees import named_leaves


def mk_mesh(data, model, n):
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:n])


def maxdiff(a, b):
    return max((float(np.max(np.abs(np.asarray(x, np.float32)
                                    - np.asarray(y, np.float32))))
                for (_, x), (_, y) in zip(named_leaves(a),
                                          named_leaves(b))
                if np.asarray(x).size), default=0.0)


# ---------------------------------------------------------------- online

def mk_cfg(tp):
    return tf.TransformerConfig(
        name="elastic", n_layers=2, d_model=64, n_heads=8, kv_heads=2,
        d_ff=128, vocab=96, tp=tp, attn_chunk=16, dtype=jnp.float32)


MESHES = {"tp4": ((2, 4), 8, 4), "tp2": ((2, 2), 4, 2)}
_BUILT = {}


def build_for(key):
    """Builder for the Supervisor's ladder: one (train_step, pipeline,
    placed_params) per mesh rung. The batch schedule is mesh-independent
    (same seed, same dp extent), so a replay sees identical data."""
    if key not in _BUILT:
        dims, ndev, tp = MESHES[key]
        mesh = mk_mesh(*dims, ndev)
        cfg = mk_cfg(tp)
        pipe = TokenPipeline(96, 32, 8, seed=5, mesh=mesh)
        params = family_of(cfg).init(jax.random.PRNGKey(2), mk_cfg(1))
        sync = GradSyncConfig(strategy="concom", bucket_bytes=1 << 12,
                              exclude_axes=("data",))
        ts = make_train_step(
            cfg, mesh, sync, zero1(adamw(1e-3), ("data",), 2),
            batch_like=pipe.batch_at(0), params_like=params,
            zero1_mode=True, clip_norm=0.0)
        ps = jax.device_put(params, ts.shardings(ts.param_specs))
        _BUILT[key] = (ts, pipe, ps)
    return _BUILT[key]


def online():
    plan = FaultPlan(rank_loss=frozenset({5}), transient=frozenset({2}),
                     step_retries=1, ckpt_io_faults=2, ckpt_retries=3)
    with tempfile.TemporaryDirectory() as root:
        sup = Supervisor(build_for, ("tp4", "tp2"), root, plan=plan,
                         every=4, grow_back_after=5)
        params, opt, rep = sup.run(12)
    for t in rep["transitions"]:
        print(f"[elastic] {t['reason']}: {t['from_key']}->{t['to_key']} "
              f"@ step {t['resume_step']}, "
              f"{t['reshard_bytes'] / 1e6:.2f} MB resharded in "
              f"{t['latency_s'] * 1e3:.0f} ms")

    # replay the realized mesh trajectory with zero faults: bit-exact
    with tempfile.TemporaryDirectory() as root:
        clean = Supervisor(build_for, ("tp4", "tp2"), root,
                           script=rep["script"], every=4,
                           printer=lambda s: None)
        p2, o2, _ = clean.run(12)
    d = max(maxdiff(params, p2), maxdiff(opt, o2))
    print(f"[elastic] faulty vs clean scripted replay: maxdiff {d:g}")
    assert d == 0.0
    print("[elastic] online 8->4->8 cycle under faults: OK")


# --------------------------------------------------------------- offline

def build_plain(cfg, mesh, pipe, params_like):
    opt = adamw(1e-3)
    ts = make_train_step(
        cfg, mesh, GradSyncConfig(strategy="depcha", num_channels=2),
        opt, batch_like=pipe.batch_at(0), params_like=params_like)
    return opt, ts


def offline():
    """Cold-restart fallback: no live group survives, so resize goes
    through a checkpoint round-trip (`checkpoint.reshard`)."""
    mesh8 = mk_mesh(2, 4, 8)
    cfg8 = tf.TransformerConfig(
        name="elastic", n_layers=2, d_model=64, n_heads=8, kv_heads=4,
        d_ff=128, vocab=128, tp=4, attn_chunk=32, dtype=jnp.float32,
        depcha_in_scan=True)
    pipe8 = TokenPipeline(cfg8.vocab, 32, 8, seed=5, mesh=mesh8)
    params = tf.init_params(jax.random.PRNGKey(0), cfg8)
    rules8 = family_of(cfg8).param_rules(cfg8)
    params = reshard(params, rules8, mesh8)

    with tempfile.TemporaryDirectory() as ckdir:
        ckpt = CheckpointManager(ckdir, every=10, keep=2, blocking=True)
        opt, ts = build_plain(cfg8, mesh8, pipe8, params)
        trainer = Trainer(ts, pipe8, ckpt, log_every=10)
        params, opt_state, _ = trainer.run(params, opt.init(params), 20)
        print("[elastic] trained 20 steps on 8 devices (2 DP x 4 TP)")

        # ---- the whole fleet restarted: only 4 devices come back ----
        mesh4 = mk_mesh(2, 2, 4)
        cfg4 = tf.TransformerConfig(
            name="elastic", n_layers=2, d_model=64, n_heads=8, kv_heads=4,
            d_ff=128, vocab=128, tp=2, attn_chunk=32, dtype=jnp.float32,
            depcha_in_scan=True)
        rules4 = family_of(cfg4).param_rules(cfg4)
        pipe4 = TokenPipeline(cfg4.vocab, 32, 8, seed=5, mesh=mesh4)

        step, state = ckpt.restore(
            {"params": jax.tree.map(np.asarray, params),
             "opt": jax.tree.map(np.asarray, opt_state)})
        params4 = reshard(state["params"], rules4, mesh4)
        opt4, ts4 = build_plain(cfg4, mesh4, pipe4, params4)
        # optimizer state is param-shaped: reshard each sub-tree
        opt_state4 = {
            k: reshard(v, rules4, mesh4) for k, v in state["opt"].items()}
        trainer4 = Trainer(ts4, pipe4, None, log_every=10)
        params4, _, hist = trainer4.run(params4, opt_state4, 40,
                                        start_step=step)
        print(f"[elastic] resumed at step {step} on 4 devices (2 DP x "
              f"2 TP); final loss {hist['losses'][-1]:.3f}")
        print("[elastic] offline checkpoint-reshard fallback: OK")


def main():
    online()
    offline()


if __name__ == "__main__":
    main()
