# NOTE: deliberately NO XLA_FLAGS here — smoke tests and benches must see
# the real single CPU device; only launch/dryrun.py forces 512 devices.
import os
import tempfile
import warnings

warnings.filterwarnings("ignore")

# point the fitted-NetworkModel lookup at an empty dir: a profile written
# by a local `make calibrate-smoke` must not leak into `auto`-ranking
# tests (obs tests override this per-test).  Inherited by the
# subprocess-based multidevice/bench workers via os.environ.
os.environ["REPRO_NETPROFILE_DIR"] = tempfile.mkdtemp(
    prefix="repro-netprofiles-test-")

import jax
import pytest

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="session")
def smoke_mesh():
    from repro.launch.mesh import make_smoke_mesh

    return make_smoke_mesh(1, 1)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny_train(smoke_mesh):
    """A two-layer transformer's train step on the smoke mesh, with its
    token pipeline, initial params and optimizer: the trainer tests'
    configuration."""
    import jax.numpy as jnp

    from repro.core import GradSyncConfig
    from repro.data import TokenPipeline
    from repro.models import transformer as tf
    from repro.optim import adamw
    from repro.runtime import make_train_step

    cfg = tf.TransformerConfig(
        name="obs", n_layers=2, d_model=32, n_heads=4, kv_heads=2,
        d_ff=64, vocab=64, tp=1, attn_chunk=16, dtype=jnp.float32)
    pipe = TokenPipeline(64, 16, 4, seed=13, mesh=smoke_mesh)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-3)
    ts = make_train_step(
        cfg, smoke_mesh,
        GradSyncConfig(strategy="concom", bucket_bytes=1 << 14),
        opt, batch_like=pipe.batch_at(0), params_like=params)
    return ts, pipe, params, opt
