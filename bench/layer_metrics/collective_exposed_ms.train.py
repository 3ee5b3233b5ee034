"""The part of ``collective_ms.train`` during which no other op ran on the
same device: the gradient exchange the step waits for.  Moves
``train_samples_per_s``."""


def read(ctx):
    tr = ctx.trace
    step = tr.biggest_module()
    steps = tr.module_count(lambda n: n == step)
    if not tr.has_collectives() or not steps:
        return None
    return 1e3 * tr.collective_exposed_s() / steps
