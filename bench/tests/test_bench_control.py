"""The controls, kept at sizes a test run can hold: each has to read
above one of its cell's limits (the real cells' limits), so that the
lower precision a later change might be tempted by comes out not
correct.  On the chip they run at the cells' own sizes through
``bench/tools/control.py``.

- Training: the reference computed in bfloat16 (parameters included),
  at widths large enough (about 7 M parameters) that, as at ResNet-50's,
  an update falls below bfloat16's resolution of a weight.
- Serving: the reference with its weights read in float8 (e4m3), at a
  width and vocabulary large enough for near-ties among the logits; and
  the same control put in the program's place in a whole run of the
  small serving cell, where the harness's own verdict has to read false.
"""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import manifest
from bench.reference import qwen3 as q
from bench.tools import control

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).parent / "fixture"
SERVE_SIZES = dict(hidden_size=256, intermediate_size=768,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=64, vocab_size=32000)


def _limits(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / name).read_text())[
        "limits"]


def test_training_control_is_not_correct():
    cell = manifest.cell(FIX / "BENCHMARK.json", "tiny.train")
    cell.config["sizes"].update(stages=[1, 1], widths=[1024, 2048],
                                stem_width=64, image_size=8)
    got = control.train_readings(cell, 5)["control_bfloat16"]
    limits = _limits("resnet50-cifar.json")
    assert any(got[k] > v for k, v in limits.items()), (got, limits)
    assert not got["correct"]


def test_serving_control_is_not_correct():
    sizes = dict(SERVE_SIZES, rms_norm_eps=1e-6, rope_theta=1e6)
    w = q.init_weights(1, sizes, jnp.bfloat16)
    toks = np.random.default_rng(1).integers(1, sizes["vocab_size"], 256)
    _, top = q.next_token_gaps(w, toks, np.zeros(256, np.int32), sizes)
    again, _ = q.next_token_gaps(w, toks, top, sizes)
    _, top8 = q.next_token_gaps(w, toks, top, sizes, "float8_e4m3fn")
    gap8, _ = q.next_token_gaps(w, toks, top8, sizes)
    limit = _limits("qwen3-1.7b.json")["served_logit_gap"]
    assert float(again.max()) == 0.0                 # the reference itself
    assert float(gap8.max()) > limit


def test_serving_control_through_the_harness_is_not_correct(tmp_path):
    """The control in the program's place, in a whole run of a small
    serving cell (look for a chip skipped) at the width and vocabulary
    of the test above, under the real cell's limits: ``correct`` comes
    out false, and the program itself, served in float32 here, reads 0
    on the same sample."""
    shutil.copytree(FIX, tmp_path / "fix")
    conf = tmp_path / "fix" / "configs" / "qwen-tiny.json"
    c = json.loads(conf.read_text())
    c["sizes"].update(SERVE_SIZES)
    c["limits"] = _limits("qwen3-1.7b.json")
    conf.write_text(json.dumps(c))
    cell = manifest.cell(tmp_path / "fix" / "BENCHMARK.json", "tiny.serve")
    got = control.serve_readings(tmp_path / "fix", cell, 2**40 + 9, 0.5,
                                 device_check=lambda n: jax.devices()[:n])
    assert not got["correct"], got
    assert got["checks"]["served_logit_gap"] > c["limits"]["served_logit_gap"]
    assert got["program_served_logit_gap"] == 0.0
