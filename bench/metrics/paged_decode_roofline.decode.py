"""Roofline share of the paged decode-attention kernel in the trace.

Operations and bytes come from the cell's architecture's
``decode_attention`` (``bench.roofline``'s for dense) over the live
lengths of every decode step the traced window ran; the time is the
kernel's device time there: the Pallas call of the serve step whose
result is the attention readout, f32[slots, heads, head_dim]."""

from bench import roofline as RL
from bench.trace_reduce import time_of


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None:
        return None
    arch = ctx["arch"]
    m = arch.dims(ctx["model"])
    kernel = (rf"serve_step: \S+ f32\[\d+,{m['H']},{m['Dh']}\] "
              r"custom-call\(tpu_custom_call\)")
    secs, n = time_of(t["ops"], kernel)
    steps = ctx["work"].decode_steps
    if not n or not steps:
        return None
    flops = byts = 0.0
    for lengths in steps:
        f, b = arch.decode_attention(ctx["model"], ctx["kv_dtype"], lengths)
        flops, byts = flops + f, byts + b
    return RL.roofline_share(flops, byts, secs, peak)
