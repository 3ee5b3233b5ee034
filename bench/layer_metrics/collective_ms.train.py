"""Device time per training step in which a collective (all-reduce,
reduce-scatter, all-gather, ...) was in flight, averaged over the chips.
Steps are the runs of the program with the most device time (the train
step).  Moves ``train_samples_per_s``."""


def read(ctx):
    tr = ctx.trace
    step = tr.biggest_module()
    steps = tr.module_count(lambda n: n == step)
    if not tr.has_collectives() or not steps:
        return None
    return 1e3 * tr.collective_s() / steps
