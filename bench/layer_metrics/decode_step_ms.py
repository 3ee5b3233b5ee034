"""Device time of one decode step: the decode-chunk programs' device
time over the decode steps they ran (``chunk`` per run).  The engine's
decode chunk is the program ``jit_dc``.  Moves ``tpot_p95_ms``."""

DECODE = "jit_dc"


def read(ctx):
    tr = ctx.trace
    runs = tr.module_count(lambda n: n == DECODE)
    if not runs:
        return None
    return 1e3 * tr.module_s(lambda n: n == DECODE) / (
        runs * ctx.counters["chunk"])
