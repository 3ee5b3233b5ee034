"""Share of the traced window in which no op ran on the chip, in the cell
below its knee.  Moves ``tpot_p95_ms``."""


def read(ctx):
    share = ctx.trace.idle_share()
    return None if share is None else 100 * share
