"""Share of the traced window in which no op ran on a device (averaged
over the chips).  Moves ``train_samples_per_s``."""


def read(ctx):
    share = ctx.trace.idle_share()
    return None if share is None else 100 * share
