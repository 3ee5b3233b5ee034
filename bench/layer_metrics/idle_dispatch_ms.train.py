"""Device idle time per training step from the call of the jitted step
until the device has finished it: the own time of the program's spans
``train.dispatch`` (enqueue) and ``train.wait`` (``block_until_ready`` on
the loss).  Moves ``train_samples_per_s``."""
from bench.harness.spans import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, ("train.dispatch", "train.wait"))
