"""The whole training step's share of the chips' peak: samples/s over
the window times the analytic forward-plus-backward operations of one
sample (``train_flops_per_sample`` of the reference), over chips times
the bf16 peak.  Moves ``train_samples_per_s``."""


def read(ctx):
    if not ctx.peaks:                 # no peak table: not a chip
        return None
    c = ctx.counters
    if not c.get("steps"):
        return None
    return 100 * c["samples_per_s"] * c["flops_per_sample"] / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
