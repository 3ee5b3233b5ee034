"""Pipeline-parallel mechanism test (subprocess: needs >1 device)."""
import os
import subprocess
import sys


WORKER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.parallel.pipeline import pipeline_forward, bubble_fraction

mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
S, M, D = 4, 6, 8
# stage i: x -> x * w_i  (stacked weights, one per stage)
w = jnp.arange(1.0, S + 1.0)          # sharded over stage
mbs = jnp.arange(M * D, dtype=jnp.float32).reshape(M, D) + 1.0

def stage_fn(wi, x):
    return x * wi[0]

def run(w, mbs):
    return pipeline_forward(stage_fn, w, mbs, axis="stage", n_stages=S)

out = jax.jit(lambda w, m: jax.shard_map(
    run, mesh=mesh, in_specs=(P("stage"), P()), out_specs=P(),
    check_vma=False)(w, m))(w, mbs)
expect = mbs * float(np.prod(np.arange(1, S + 1)))
ok = np.allclose(np.asarray(out), np.asarray(expect))
print("PIPE_OK" if ok else f"PIPE_FAIL {np.asarray(out)[0]} vs {np.asarray(expect)[0]}")
assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
print("BUBBLE_OK")
'''


def test_pipeline_parallel_subprocess(tmp_path):
    script = tmp_path / "pipe_worker.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PIPE_OK" in proc.stdout, proc.stdout
    assert "BUBBLE_OK" in proc.stdout
