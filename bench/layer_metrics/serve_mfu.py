"""The whole serving step's share of the chip's peak: analytic operations
of every prompt and output token processed in the window, over the
window and the bf16 peak.  Moves ``serve_tokens_per_s``."""
from bench.harness import counts


def read(ctx):
    if not ctx.peaks:                 # no peak table: not a chip
        return None
    c = ctx.counters
    if not c.get("chunks"):
        return None
    flops = counts.served_flops(ctx, c["t0"], c["t_end"])
    return 100 * flops / (c["window_s"] * ctx.peaks["bf16_flops_per_s"])
