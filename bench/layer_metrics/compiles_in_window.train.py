"""Backend compiles that start inside the traced window: the program's
``jax.compile`` spans, which a ``jax.monitoring`` listener records.  Any
is a fault of the warm-up, since nothing should compile in the window.
Moves ``train_samples_per_s``."""
from bench.harness.spans import compiles_in_window


def read(ctx):
    return compiles_in_window(ctx)
