"""The plain references against the system's models at small sizes on
the CPU, the analytic counts against hand counts, and the peak table."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import core
from bench.harness.manifest import BENCH
from bench.reference import qwen3, resnet

FIX = Path(__file__).parent / "fixture" / "configs"
RSIZES = json.loads((FIX / "resnet-tiny.json").read_text())["sizes"]
QCFG = json.loads((FIX / "qwen-tiny.json").read_text())
QSIZES = QCFG["sizes"]


def test_resnet_loss_and_grads_match_the_system():
    from bench.jobs.train_image import program_config
    from repro.models.resnet import train_forward

    cfg = program_config({"sizes": RSIZES,
                          "program": {"arch": "resnet50-cifar"}})
    w = resnet.init_weights(7, RSIZES)
    b = resnet.batch(7, 0, 8, RSIZES["image_size"], RSIZES["num_classes"])
    batch = {"images": jnp.asarray(b["images"]),
             "labels": jnp.asarray(b["labels"]),
             "global_tokens": jnp.float32(8)}
    with jax.default_matmul_precision("highest"):
        l_sys, g_sys = jax.value_and_grad(train_forward)(w, batch, cfg)
        l_ref, g_ref = jax.value_and_grad(resnet.loss_fn)(
            w, batch["images"], batch["labels"], RSIZES, 1)
    assert float(l_ref) == pytest.approx(float(l_sys), rel=1e-5)
    for a, r in zip(jax.tree.leaves(g_sys), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, r, rtol=2e-3, atol=1e-5)


def test_resnet_groups_are_the_workers_batchnorm():
    """BatchNorm over g groups equals each worker's batch alone."""
    w = resnet.init_weights(3, RSIZES)
    b = resnet.batch(3, 1, 8, RSIZES["image_size"], RSIZES["num_classes"])
    x = jnp.asarray(b["images"])
    with jax.default_matmul_precision("highest"):
        both = resnet.forward(w, x, RSIZES, 2)
        parts = [resnet.forward(w, x[i * 4:(i + 1) * 4], RSIZES, 1)
                 for i in range(2)]
    np.testing.assert_allclose(both, jnp.concatenate(parts), rtol=1e-5,
                               atol=1e-5)


def test_qwen3_logits_match_the_system_prefill():
    from bench.jobs.serve_lm import program_config
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_smoke_mesh
    from repro.models.transformer import prefill

    cfg = program_config(QCFG)
    w = qwen3.init_weights(5, QSIZES, jnp.float32)
    tokens = np.random.default_rng(0).integers(1, QSIZES["vocab_size"], 12)
    mesh = make_smoke_mesh(1, 1)
    # the served head is the embedding's transpose: the tied model
    np.testing.assert_array_equal(w["lm_head"], w["embed"].T)
    with jax.default_matmul_precision("highest"):
        h = qwen3.hidden_states(w, jnp.asarray(tokens), QSIZES)
        ref_logits = h @ w["embed"].T
        for last in (0, 5, 11):
            f = jax.shard_map(
                lambda p, t, last=last: prefill(p, t, cfg, last_pos=last)[0],
                mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_vma=False)
            sys_logits = f(w, jnp.asarray(tokens[None], jnp.int32))[0]
            np.testing.assert_allclose(sys_logits, ref_logits[last],
                                       rtol=1e-4, atol=1e-4)


def test_qwen3_gaps_and_control():
    w = qwen3.init_weights(5, QSIZES, jnp.bfloat16)
    tokens = np.random.default_rng(1).integers(1, QSIZES["vocab_size"], 16)
    gaps, top = qwen3.next_token_gaps(w, tokens, np.zeros(16, np.int32),
                                      QSIZES)
    again, _ = qwen3.next_token_gaps(w, tokens, top, QSIZES)
    assert np.all(gaps >= 0) and np.all(again == 0)
    _, top8 = qwen3.next_token_gaps(w, tokens, top, QSIZES, "float8_e4m3fn")
    g8, _ = qwen3.next_token_gaps(w, tokens, top8, QSIZES)
    assert np.all(g8 >= 0)


def test_resnet_flops_hand_count():
    # stem 221,184; stage 0 753,664; stage 1 (stride 2) 950,272; head 1,280
    assert resnet.forward_flops_per_sample(RSIZES) == 1_926_400
    assert resnet.train_flops_per_sample(RSIZES) == 3 * 1_926_400 - 221_184


def test_resnet50_flops_match_the_published_scale():
    sizes = json.loads(
        (BENCH / "configs" / "resnet50-cifar.json").read_text())["sizes"]
    # about 7.6 GFLOP a 32x32 image for forward and backward
    assert 7.4e9 < resnet.train_flops_per_sample(sizes) < 7.8e9


def test_qwen3_counts_hand_count():
    # per token per layer: qkv 16,384 + o 8,192 + mlp 49,152 = 73,728;
    # attention 512 a key over both layers; head 12,416
    assert qwen3.prefill_flops(QSIZES, 5) == 5 * 147_456 + 512 * 15 + 12_416
    assert qwen3.decode_flops(QSIZES, 7) == 147_456 + 512 * 7 + 12_416
    assert qwen3.kv_bytes_per_position(QSIZES) == 256
    assert qwen3.decode_weight_bytes(QSIZES, 3) == 161_024
    assert qwen3.decode_step_bytes(QSIZES, [3, 5, 10]) == 161_024 + 256 * 18


def test_peaks_by_device_kind():
    assert core.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        core.peaks_for("TPU v9 imaginary")
