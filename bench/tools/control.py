"""Readings that set the limits of ``correct``: the sound program, the
control and the planted faults, each against the plain reference, on
several seeds, with the cell's own limits applied to each.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 20] [--what program,control]

Training cells.  ``program``: a benchmark run of the cell with a short
window (``--seconds``), every reading of the comparison kept, compared
or not.  ``control``: the reference put in the program's place at the
cell's own size (no program needed): computed in bfloat16 (the control),
with half of each worker's batch left out and the mean taken over the
rest, and, where the cell has several workers, with the gradient exchange
left out (each worker steps on its own gradient; the reported loss is the
sum of the workers' losses).

Serving cells: a benchmark run of the cell at its own load with a short
window, in which the control is put in the program's place for the
comparison: at each position of the sampled requests' prompts and served
tokens, the gap, under the float32 reference, of the token that the
reference with its weights read in float8 (e4m3) puts first.  The
harness's verdict on it is printed as ``correct``.  The program's own
gap on the same sample is read beside it, and so is the same control in
int8.

Each row is one JSON line; runs on the chip, not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CONTROL = "float8_e4m3fn"       # the control's weight type (serving)


def verdict(cell, got: dict) -> bool:
    """The harness's rule: every compared number within its limit."""
    return all(got[k] <= v for k, v in cell.limits().items())


def train_program(root, cell, seed: int, seconds: float) -> dict:
    """A benchmark run of a training cell, every reading kept."""
    from bench.harness import core
    from bench.jobs import train_image

    got: dict = {}
    orig = train_image.readings

    def keep(*args):
        got.update(orig(*args))
        return got

    train_image.readings = keep
    try:
        res = core.execute(root, cell.name, seed, seconds, False,
                           time.perf_counter())
    finally:
        train_image.readings = orig
    return {"program": got, "correct": res["correct"],
            "metrics": res["metrics"]}


def train_readings(cell, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.jobs import train_image

    ref, cfg, tr = cell.reference(), cell.config, cell.traffic
    sizes, opt = cfg["sizes"], cfg["optimizer"]
    B, n, g = tr["global_batch"], tr["check_steps"], cell.chips
    batches = [ref.batch(seed, k, B, sizes["image_size"],
                         sizes["num_classes"]) for k in range(n)]
    w0 = ref.init_weights(seed, sizes)
    p0 = jax.tree.map(np.asarray, w0)
    kw = dict(lr=opt["lr"], momentum=opt["momentum"],
              clip_norm=opt["clip_norm"])
    base = ref.sgd_run(w0, batches, sizes, groups=g, **kw)
    out = {}

    class Quiet:
        log = staticmethod(lambda msg: None)

    def reading(name, got):
        losses, mom1, p, norm0 = got
        r = train_image.readings(Quiet(), losses, mom1, p, norm0, p0, base)
        out[name] = dict(r, correct=verdict(cell, r))

    reading("control_bfloat16",
            ref.sgd_run(w0, batches, sizes, groups=g, dtype=jnp.bfloat16,
                        **kw))
    half = [{k: v.reshape(g, B // g, *v.shape[1:])[:, :B // (2 * g)]
             .reshape(B // 2, *v.shape[1:]) for k, v in b.items()}
            for b in batches]
    reading("fault_half_batch",
            ref.sgd_run(w0, half, sizes, groups=g, **kw))
    if g > 1:
        reading("fault_no_exchange", no_exchange(ref, w0, batches, sizes, g,
                                                 **kw))
    return out


def no_exchange(ref, w0, batches, sizes, g, *, lr, momentum, clip_norm):
    """Each of ``g`` workers steps on the gradient of its own share of
    the loss (sum over its rows / global batch); the reported loss sums
    the workers'; worker 0's state is read, as from the first chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    B = batches[0]["labels"].shape[0]

    def local_loss(p, images, labels):
        logits = ref.forward(p, images, sizes, 1).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], 1)) / B

    @jax.jit
    def step(p, mom, images, labels):
        loss, gr = jax.value_and_grad(local_loss)(p, images, labels)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(gr)))
        s = jnp.minimum(1.0, clip_norm / (norm + 1e-9))
        mom = jax.tree.map(lambda m, x: momentum * m + x * s, mom, gr)
        return jax.tree.map(lambda w, m: w - lr * m, p, mom), mom, loss, norm

    with jax.default_matmul_precision("highest"):
        ps = [w0] * g
        moms = [jax.tree.map(jnp.zeros_like, w0)] * g
        losses, mom1, norm0 = [], None, None
        for b in batches:
            tot = 0.0
            for k in range(g):
                rows = slice(k * B // g, (k + 1) * B // g)
                ps[k], moms[k], l, n = step(ps[k], moms[k],
                                            jnp.asarray(b["images"][rows]),
                                            jnp.asarray(b["labels"][rows]))
                tot += float(l)
                if k == 0 and norm0 is None:
                    norm0 = float(n)
            losses.append(tot)
            if mom1 is None:
                mom1 = jax.tree.map(np.asarray, moms[0])
    return losses, mom1, jax.tree.map(np.asarray, ps[0]), norm0


def control_gaps(ref, w, sizes, prompt, out, max_len: int, wdtype: str):
    """Per served position, the gap under the float32 reference of the
    token that the reference with weights read in ``wdtype`` puts first."""
    import numpy as np

    L, n = len(prompt), len(out)
    seq = np.zeros(max_len, np.int32)
    seq[:L] = prompt
    seq[L:L + n - 1] = out[:-1]
    _, top = ref.next_token_gaps(w, seq, np.zeros(max_len, np.int32), sizes,
                                 wdtype)
    g, _ = ref.next_token_gaps(w, seq, top, sizes)
    return g[L - 1:L - 1 + n]


def serve_readings(root, cell, seed: int, seconds: float,
                   device_check=None) -> dict:
    """A benchmark run of a serving cell with the control in the
    program's place for the comparison (module docstring)."""
    from bench.harness import core
    from bench.jobs import serve_lm

    seen = {"program": 0.0, "int8": 0.0}
    orig = serve_lm.served_gaps

    def control(ref, w, sizes, prompt, out, max_len):
        prog = orig(ref, w, sizes, prompt, out, max_len)
        seen["program"] = max(seen["program"], float(prog.max()))
        i8 = control_gaps(ref, w, sizes, prompt, out, max_len, "int8")
        seen["int8"] = max(seen["int8"], float(i8.max()))
        return control_gaps(ref, w, sizes, prompt, out, max_len, CONTROL)

    serve_lm.served_gaps = control
    try:
        res = core.execute(root, cell.name, seed, seconds, False,
                           time.perf_counter(),
                           device_check=device_check or core.device_check)
    finally:
        serve_lm.served_gaps = orig
    checks = {k: v["value"] for k, v in res["checks"].items()}
    return {"control": CONTROL, "correct": res["correct"],
            "checks": checks, "program_served_logit_gap": seen["program"],
            "int8_served_logit_gap": seen["int8"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--what", default="program,control",
                    help="training cells: program, control or both")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench.harness import core, manifest

    cell = manifest.cell(ROOT / "BENCHMARK.json", args.workload)
    core.device_check(cell.chips)
    what = set(args.what.split(","))
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        row: dict = {"seed": seed}
        if cell.config["job"] == "train_image":
            if "program" in what:
                row.update(train_program(ROOT, cell, seed, args.seconds))
            if "control" in what:
                row.update(train_readings(cell, seed))
        else:
            row.update(serve_readings(ROOT, cell, seed, args.seconds))
        row["wall_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
