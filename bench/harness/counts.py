"""Work counted on the host while a serving window runs, for the
per-layer readers: the decode steps of each chunk and the prompts
admitted, turned into operations and bytes by the reference's analytic
functions."""
from __future__ import annotations


def decode_steps(chunks, chunk: int, lo: float, hi: float):
    """For every decode step of the chunks that ran inside [lo, hi], the
    cache lengths (new token included) of the slots active in it."""
    for t0, t1, slots in chunks:
        if lo <= t0 and t1 <= hi:
            for t in range(chunk):
                lens = [pos + t + 1 for pos, rem in slots if rem > t]
                if lens:
                    yield lens


def prompt_lengths(prefills, lo: float, hi: float) -> list[int]:
    return [n for t, n in prefills if lo <= t <= hi]


def served_flops(ctx, lo: float, hi: float) -> float:
    """Analytic operations of every prompt and output token the engine
    processed inside [lo, hi]."""
    c, ref = ctx.counters, ctx.reference
    sizes = c["sizes"]
    total = sum(ref.prefill_flops(sizes, n)
                for n in prompt_lengths(c["prefills"], lo, hi))
    for lens in decode_steps(c["chunks"], c["chunk"], lo, hi):
        total += sum(ref.decode_flops(sizes, k) for k in lens)
    return total

