"""Share of the traced window's device idle time that no span of the
program covers (the step span ``train.step`` counts as none: it frames
the phases and names none).  Moves ``train_samples_per_s``."""
from bench.harness.spans import idle_unspanned_share


def read(ctx):
    return idle_unspanned_share(ctx)
