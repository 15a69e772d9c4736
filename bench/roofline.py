"""Operations and bytes that the algorithm needs, from shapes, and the
chip's peaks.

The counts are of what attention must do, not of what a kernel happens
to do: only the live positions of each request (its padded prompt and
the tokens decoded so far), K/V in the pool's own type (int8 codes plus
their float32 scales), queries and outputs in bfloat16, and no copy,
cast or padding of lanes or windows.  A kernel's roofline share is the
least time those counts allow at the chip's peaks over the time the
kernel took, so a kernel that skips work it never needed can approach
but not pass 100%.
"""

from __future__ import annotations

import json
import os

from bench.weights import dims

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
ACT_BYTES = 2  # bfloat16 queries and readouts


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; the table has "
            f"{sorted(table)}"
        )
    return table[device_kind]


def kv_bytes_per_position(model: dict, kv_dtype: str) -> float:
    """Bytes of K and V of one position in one layer, in the pool's type."""
    m = dims(model)
    if kv_dtype == "int8":
        return 2 * m["Hkv"] * (m["Dh"] * 1 + 4)   # codes + f32 scale
    return 2 * m["Hkv"] * m["Dh"] * 2              # bfloat16


def decode_attention(model: dict, kv_dtype: str, lengths) -> tuple[float, float]:
    """(flops, bytes) of one decode step's attention over all layers:
    one query per active request against its ``lengths[i]`` positions."""
    m = dims(model)
    n = float(sum(lengths))
    flops = 4.0 * m["H"] * m["Dh"] * n
    byts = (n * kv_bytes_per_position(model, kv_dtype)
            + len(lengths) * 2 * m["H"] * m["Dh"] * ACT_BYTES)
    return m["L"] * flops, m["L"] * byts


def prefill_attention(model: dict, kv_dtype: str, q0: int, c: int
                      ) -> tuple[float, float]:
    """(flops, bytes) of one prefill chunk's attention over all layers:
    queries at positions q0 .. q0+c-1, each over the positions up to
    itself (causal), reading the K/V of positions [0, q0 + c) once."""
    m = dims(model)
    pairs = c * q0 + c * (c + 1) / 2
    flops = 4.0 * m["H"] * m["Dh"] * pairs
    byts = ((q0 + c) * kv_bytes_per_position(model, kv_dtype)
            + c * 2 * m["H"] * m["Dh"] * ACT_BYTES)
    return m["L"] * flops, m["L"] * byts


def matmul_params(model: dict) -> int:
    """Weights a token multiplies through in the layer stack."""
    m = dims(model)
    attn = m["D"] * (2 * m["H"] * m["Dh"] + 2 * m["Hkv"] * m["Dh"])
    return m["L"] * (attn + 3 * m["D"] * m["F"])


def token_flops(model: dict, position: int, head: bool) -> float:
    """Model FLOPs of one token at ``position`` (0-based): its matmuls,
    its causal attention over position + 1 keys, and the head where the
    token's logits are computed."""
    m = dims(model)
    f = 2.0 * matmul_params(model)
    f += 4.0 * m["L"] * m["H"] * m["Dh"] * (position + 1)
    if head:
        f += 2.0 * m["D"] * m["V"]
    return f


def chunk_flops(model: dict, q0: int, c: int) -> float:
    """Model FLOPs of a prefill chunk: c tokens, logits of the last."""
    m = dims(model)
    f = c * 2.0 * matmul_params(model)
    f += 4.0 * m["L"] * m["H"] * m["Dh"] * (c * q0 + c * (c + 1) / 2)
    return f + 2.0 * m["D"] * m["V"]


def roofline_share(flops: float, byts: float, seconds: float, peak: dict,
                   int8: bool = False) -> float | None:
    """Least time at the peaks over the time taken, in percent."""
    if seconds <= 0 or (flops <= 0 and byts <= 0):
        return None
    rate = peak["int8_ops_per_s"] if int8 else peak["bf16_flops_per_s"]
    least = max(flops / rate, byts / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
