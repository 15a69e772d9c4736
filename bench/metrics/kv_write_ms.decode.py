"""Device time of the serve step under the engine's ``engine.kv_write``
scope (the new K/V rows into the paged pool, with the int8 pool's
stochastic rounding and scale planes), per run of the step in the traced
window (``bench/spans.py``)."""

from bench.spans import scope_time
from bench.trace_reduce import time_of

PROGRAM = r"serve_step"
SCOPE = "engine.kv_write"


def read(ctx):
    s, t = ctx["spans"], ctx["trace"]
    if s is None or t is None:
        return None
    _, n = time_of(t["programs"], PROGRAM)
    secs = scope_time(s, PROGRAM, SCOPE)
    return 1e3 * secs / n if n and secs > 0 else None
