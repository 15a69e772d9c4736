"""Share of the decode slots busy, per decode step, over the window: the
engine's ``occupancy_mean`` counter, differenced at the window's edges."""


def read(ctx):
    c = ctx["counters"]
    if c["decode_steps"] <= 0:
        return None
    return 100.0 * c["occupancy_sum"] / c["decode_steps"]
