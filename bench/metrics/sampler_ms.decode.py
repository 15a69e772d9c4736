"""Device time of the serve step under the engine's ``engine.sample``
scope (the greedy argmax, or the WTA bank of comparator trials), per run
of the step in the traced window (``bench/spans.py``)."""

from bench.spans import scope_time
from bench.trace_reduce import time_of

PROGRAM = r"serve_step"
SCOPE = "engine.sample"


def read(ctx):
    s, t = ctx["spans"], ctx["trace"]
    if s is None or t is None:
        return None
    _, n = time_of(t["programs"], PROGRAM)
    secs = scope_time(s, PROGRAM, SCOPE)
    return 1e3 * secs / n if n and secs > 0 else None
