"""Entry point: force 8 fake CPU devices BEFORE jax loads (same pattern
as ``python -m repro.sim``) so the 2x4 (data, model) mesh exists on any
host, then hand off to the CLI."""
import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
from repro.obs.cli import main  # noqa: E402

main()
