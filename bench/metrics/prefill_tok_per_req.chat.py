"""Prefill tokens computed per request admitted in the window: the
engine's ``prefill_tokens`` counter over the window (left pads count, as
the engine computes them) over the requests whose first token came in
the window."""


def read(ctx):
    n = ctx["admitted_in_window"]
    if n <= 0:
        return None
    return ctx["counters"]["prefill_tokens"] / n
