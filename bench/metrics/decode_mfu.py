"""Model FLOPs of the traced window over the window at the chip's bf16
peak: every token decoded (its matmuls, head and attention at its live
length) and every prefill chunk computed."""

from bench import roofline as RL


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    if t is None or peak is None or t["window_s"] <= 0:
        return None
    work, model = ctx["work"], ctx["model"]
    flops = sum(RL.token_flops(model, n - 1, head=True)
                for step in work.decode_steps for n in step)
    flops += sum(RL.chunk_flops(model, q0, c) for q0, c in work.chunks)
    if flops <= 0:
        return None
    return 100.0 * flops / (t["window_s"] * peak["bf16_flops_per_s"])
