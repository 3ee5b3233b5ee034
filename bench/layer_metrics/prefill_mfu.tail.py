"""The admission prefill's share of the chip's peak: the analytic
operations of the real prompt tokens (``prefill_flops`` of the
reference, per prompt admitted in the traced window) over the prefill
programs' device time and the bf16 peak.  The engine's bucketed B=1
prefill is the program ``jit_pf``.  Moves ``ttft_p95_s``."""
import statistics

from bench.harness import counts

PREFILL = "jit_pf"


def read(ctx):
    if not ctx.peaks:                 # no peak table: not a chip
        return None
    tr, c = ctx.trace, ctx.counters
    runs = tr.module_count(lambda n: n == PREFILL)
    lens = counts.prompt_lengths(c["prefills"], *c["trace_window"])
    if not runs or not lens:
        return None
    flops = statistics.mean(ctx.reference.prefill_flops(c["sizes"], n)
                            for n in lens) * runs
    return 100 * flops / tr.module_s(lambda n: n == PREFILL) / (
        ctx.peaks["bf16_flops_per_s"])
