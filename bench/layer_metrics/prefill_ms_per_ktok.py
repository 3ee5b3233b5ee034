"""Device time of the admission prefill programs (``jit_pf``) per 1,000
real prompt tokens admitted in the traced window.  Moves
``serve_tokens_per_s``."""
import statistics

from bench.harness import counts

PREFILL = "jit_pf"


def read(ctx):
    tr, c = ctx.trace, ctx.counters
    runs = tr.module_count(lambda n: n == PREFILL)
    lens = counts.prompt_lengths(c["prefills"], *c["trace_window"])
    if not runs or not lens:
        return None
    ktok = statistics.mean(lens) * runs / 1e3
    return 1e3 * tr.module_s(lambda n: n == PREFILL) / ktok
