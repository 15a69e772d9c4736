"""Model FLOPs of the prefill chunks in the traced window over the
prefill-chunk program's device time at the chip's bf16 peak."""

from bench import roofline as RL
from bench.trace_reduce import time_of

PROGRAM = r"suffix_chunk"


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None:
        return None
    secs, n = time_of(t["programs"], PROGRAM)
    chunks = ctx["work"].chunks
    if not n or not chunks or secs <= 0:
        return None
    flops = sum(RL.chunk_flops(ctx["model"], q0, c) for q0, c in chunks)
    return 100.0 * flops / (secs * peak["bf16_flops_per_s"])
