"""Device time of the prefill-chunk program per run of it, in the trace."""

from bench.trace_reduce import time_of

PROGRAM = r"suffix_chunk"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    secs, n = time_of(t["programs"], PROGRAM)
    return 1e3 * secs / n if n else None
