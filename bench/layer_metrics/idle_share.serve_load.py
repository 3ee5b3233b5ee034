"""Share of the traced window in which no op ran on the chip, in the cell
above its knee.  Moves ``serve_tokens_per_s``."""


def read(ctx):
    share = ctx.trace.idle_share()
    return None if share is None else 100 * share
