"""Mean share of the decode batch's slots in use, over the decode chunks
of the window, as the engine's ``serve.batch_fill`` gauge reads after
each ``step()``.  Moves ``serve_tokens_per_s``."""
import statistics


def read(ctx):
    c = ctx.counters
    fills = [f for (t0, t1, _), f in zip(c["chunks"], c["occupancy"])
             if c["t0"] <= t0 and t1 <= c["t_end"]]
    return 100 * statistics.mean(fills) if fills else None
