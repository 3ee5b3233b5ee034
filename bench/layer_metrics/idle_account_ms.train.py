"""Device idle time per training step while the trainer does its
bookkeeping after the step: the own time of the program's span
``train.account`` (loss readback, metrics, events, straggler check,
checkpoint).  Moves ``train_samples_per_s``."""
from bench.harness.spans import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, ("train.account",))
