"""Roofline share of the chunked-prefill attention kernel in the trace.

Operations and bytes come from the cell's architecture's
``prefill_attention`` (``bench.roofline``'s for dense) over the (start,
length) of every prefill chunk the traced window ran; the time is the
kernel's device time there: the Pallas call of the prefill chunk whose
result is the attention readout, f32[kv_heads, group, rows, head_dim]."""

from bench import roofline as RL
from bench.trace_reduce import time_of


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None:
        return None
    arch = ctx["arch"]
    m = arch.dims(ctx["model"])
    kernel = (rf"suffix_chunk: \S+ f32\[{m['Hkv']},{m['H'] // m['Hkv']},"
              rf"\d+,{m['Dh']}\] custom-call\(tpu_custom_call\)")
    secs, n = time_of(t["ops"], kernel)
    chunks = ctx["work"].chunks
    if not n or not chunks:
        return None
    flops = byts = 0.0
    for q0, c in chunks:
        f, b = arch.prefill_attention(ctx["model"], ctx["kv_dtype"], q0, c)
        flops, byts = flops + f, byts + b
    return RL.roofline_share(flops, byts, secs, peak)
