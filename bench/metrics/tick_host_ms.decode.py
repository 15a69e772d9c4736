"""Host time of the engine's tick, the mean over the ticks wholly inside
the traced window: each ``engine.tick`` span less the ``engine.*.wait``
spans in it, in which the host is blocked on the device
(``bench/spans.py``)."""


def read(ctx):
    s = ctx["spans"]
    if s is None or not s["ticks"]:
        return None
    return 1e3 * sum(s["ticks"]) / len(s["ticks"])
