"""Device idle time per training step while the host builds the step's
input: the own time of the program's spans ``train.input`` (the trainer
waiting for its batch), ``data.synth`` (numpy generation) and
``data.place`` (``device_put`` of the batch).  Moves
``train_samples_per_s``."""
from bench.harness.spans import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, ("train.input", "data.synth", "data.place"))
