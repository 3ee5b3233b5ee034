"""Decode's share of its HBM roofline: the least bytes the traced decode
steps had to move (every weight once, each active slot's cache up to its
real length; ``decode_step_bytes`` of the reference) over the chip's HBM
bandwidth, over the decode programs' device time.  Moves
``tpot_p95_ms``."""
from bench.harness import counts

DECODE = "jit_dc"


def read(ctx):
    if not ctx.peaks:                 # no peak table: not a chip
        return None
    tr, c = ctx.trace, ctx.counters
    runs = tr.module_count(lambda n: n == DECODE)
    lo, hi = c["trace_window"]
    matched = [ch for ch in c["chunks"] if lo <= ch[0] and ch[1] <= hi]
    if not runs or not matched:
        return None
    need = sum(ctx.reference.decode_step_bytes(c["sizes"], lens)
               for lens in counts.decode_steps(matched, c["chunk"], lo, hi))
    per_run_s = tr.module_s(lambda n: n == DECODE) / runs
    return 100 * need / len(matched) / ctx.peaks["hbm_bytes_per_s"] / per_run_s
