"""A test architecture, found by name as any other: sliding-window and
full attention layers in a repeating pattern, and sparse experts in every
layer.  Its model group takes Hugging Face's key names (``layer_types``
of ``sliding_attention`` / ``full_attention``, ``sliding_window``,
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``tie_word_embeddings``).

The reference is the dense one's (``bench/reference.py``: RMSNorm,
rotary over the whole head, grouped-query attention over every position
the engine served, float32 at ``highest``) with two changes.  A
windowed layer's query at position s sees the keys in (s - window, s].
The MLP is a mixture of SwiGLU experts: the router's softmax over the
experts in float32, the top ``num_experts_per_tok`` of it renormalised
to sum to 1 as the gates, and every expert computed for every row, the
gates weighing them.  That routes every token, so the program must drop
none: the configuration's ``capacity_factor`` must give each expert room
for every token of a sequence."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as REF
from bench.roofline import ACT_BYTES

KINDS = {"sliding_attention": "local", "full_attention": "global"}


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return {
        "L": model["num_hidden_layers"], "D": d, "H": h,
        "Hkv": model["num_key_value_heads"],
        "Dh": model.get("head_dim") or d // h,
        "F": model["moe_intermediate_size"], "E": model["num_experts"],
        "K": model["num_experts_per_tok"], "V": model["vocab_size"],
        "W": model["sliding_window"],
        "kinds": tuple(KINDS[t] for t in model["layer_types"]),
        "tied": bool(model["tie_word_embeddings"]),
        "dtype": model.get("torch_dtype", "bfloat16"),
    }


def pattern(m: dict) -> tuple:
    """The shortest unit of layer kinds that repeats through the stack."""
    kinds = m["kinds"]
    return next(kinds[:p] for p in range(1, len(kinds) + 1)
                if len(kinds) % p == 0
                and kinds == kinds[:p] * (len(kinds) // p))


def layout(model: dict) -> dict:
    m = dims(model)
    D, H, Hkv, Dh, F, E, V = (m[k] for k in ("D", "H", "Hkv", "Dh", "F",
                                            "E", "V"))
    dt = m["dtype"]
    pat = pattern(m)
    U = m["L"] // len(pat)
    out = {"embed/embedding": ((V, D), dt, D),
           "final_norm/scale": ((D,), "float32", 0)}
    if not m["tied"]:
        out["head/w"] = ((D, V), dt, D)
    for i in range(len(pat)):
        u = f"units/l{i}/"
        out.update({
            u + "ln1/scale": ((U, D), "float32", 0),
            u + "attn/wq": ((U, D, H * Dh), dt, D),
            u + "attn/wk": ((U, D, Hkv * Dh), dt, D),
            u + "attn/wv": ((U, D, Hkv * Dh), dt, D),
            u + "attn/wo": ((U, H * Dh, D), dt, H * Dh),
            u + "ln2/scale": ((U, D), "float32", 0),
            u + "moe/router": ((U, D, E), "float32", D),
            u + "moe/w_gate": ((U, E, D, F), dt, D),
            u + "moe/w_up": ((U, E, D, F), dt, D),
            u + "moe/w_down": ((U, E, F, D), dt, F),
        })
    return out


def program_fields(model: dict, as_run: dict) -> dict:
    m = dims(model)
    cf = float(as_run["capacity_factor"])
    if cf * m["K"] < m["E"]:
        raise ValueError(f"capacity_factor {cf} drops tokens at capacity; "
                         f"the reference routes every token")
    return {
        "n_layers": m["L"], "d_model": m["D"], "n_heads": m["H"],
        "n_kv_heads": m["Hkv"], "head_dim": m["Dh"], "d_ff": m["F"],
        "vocab": m["V"], "rope_theta": float(as_run["rope_theta"]),
        "norm_eps": float(as_run["norm_eps"]), "mlp": "swiglu",
        "layer_pattern": pattern(m), "local_window": m["W"],
        "n_experts": m["E"], "moe_topk": m["K"], "capacity_factor": cf,
        "tie_embeddings": m["tied"], "dtype": m["dtype"],
    }


# -- the reference -----------------------------------------------------------


def attention_mask(s: int, kind: str, window: int):
    """Which keys (columns) each query (row) of a sequence of s sees."""
    q, k = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    ok = k <= q
    if kind == "local":
        ok &= k > q - window
    return ok


@functools.partial(jax.jit, static_argnames=("m", "kind", "eps", "fp8",
                                             "kv"))
def _layer(x, w, cos, sin, *, m, kind, eps, fp8, kv):
    m = dict(m)
    s, H, Hkv, Dh = x.shape[0], m["H"], m["Hkv"], m["Dh"]
    h = REF._rms(x, w["ln1"], eps)
    q = REF._mm(h, w["wq"], fp8).reshape(s, H, Dh)
    k = REF._mm(h, w["wk"], fp8).reshape(s, Hkv, Dh)
    v = REF._mm(h, w["wv"], fp8).reshape(s, Hkv, Dh)
    q, k = REF._rope(q, cos, sin), REF._rope(k, cos, sin)
    if kv:
        k, v = REF._kv_round(k, kv), REF._kv_round(v, kv)
    qg = q.reshape(s, Hkv, H // Hkv, Dh)
    sc = jnp.einsum("skgd,tkd->kgst", qg, k, precision=REF.HI) * Dh ** -0.5
    sc = jnp.where(attention_mask(s, kind, m["W"]), sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=REF.HI).reshape(s, H * Dh)
    x = x + REF._mm(o, w["wo"], fp8)
    h = REF._rms(x, w["ln2"], eps)
    probs = jax.nn.softmax(REF._mm(h, w["router"], fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, m["K"])
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    weigh = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], idx].set(gates)
    for e in range(m["E"]):
        g = REF._mm(h, w["w_gate"][e], fp8)
        y = REF._mm(jax.nn.silu(g) * REF._mm(h, w["w_up"][e], fp8),
                    w["w_down"][e], fp8)
        x = x + weigh[:, e:e + 1] * y
    return x


def _sublayer(weights: dict, u: int, i: int) -> dict:
    sub = weights["units"][f"l{i}"]
    return {"ln1": sub["ln1"]["scale"][u], "ln2": sub["ln2"]["scale"][u],
            **{k: v[u] for k, v in sub["attn"].items()},
            **{k: v[u] for k, v in sub["moe"].items()}}


def logits_rows(weights, model: dict, as_run: dict, tokens, rows,
                variant: dict | None = None):
    m = dims(model)
    pat = pattern(m)
    n = len(tokens)
    cos, sin = REF._rope_tables(n, m["Dh"], float(as_run["rope_theta"]))
    variant = variant or {}
    fp8 = variant.get("matmuls") == "fp8"
    kv = variant.get("kv", "")
    eps = float(as_run["norm_eps"])
    static = tuple(sorted(m.items()))
    head = (weights["embed"]["embedding"].T if m["tied"]
            else weights["head"]["w"])
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["embedding"][jnp.asarray(tokens)]
        x = x.astype(jnp.float32)
        for u in range(m["L"] // len(pat)):
            for i, kind in enumerate(pat):
                x = _layer(x, _sublayer(weights, u, i), cos, sin, m=static,
                           kind=kind, eps=eps, fp8=fp8, kv=kv)
        padded = np.zeros(n, np.int32)
        padded[:len(rows)] = rows
        z = REF._head(x, jnp.asarray(padded), weights["final_norm"]["scale"],
                      head, eps=eps, fp8=fp8)
        return z[:len(rows)]


# -- the work counts ---------------------------------------------------------


def _kv_bytes(m: dict, kv_dtype: str) -> float:
    if kv_dtype == "int8":
        return 2 * m["Hkv"] * (m["Dh"] * 1 + 4)   # codes + f32 scale
    return 2 * m["Hkv"] * m["Dh"] * 2              # bfloat16


def _seen(kind: str, m: dict, n: int) -> int:
    """Keys a query at live length n attends to in a layer of ``kind``."""
    return min(n, m["W"]) if kind == "local" else n


def _pairs(kind: str, m: dict, q0: int, c: int) -> float:
    """(query, key) pairs of queries at positions q0 .. q0+c-1."""
    w = m["W"] if kind == "local" else q0 + c

    def upto(n):  # sum of min(v, w) for v = 1 .. n
        return n * (n + 1) / 2 if n <= w else w * (w + 1) / 2 + (n - w) * w

    return upto(q0 + c) - upto(q0)


def decode_attention(model: dict, kv_dtype: str, lengths):
    m = dims(model)
    flops = byts = 0.0
    for kind in m["kinds"]:
        n = float(sum(_seen(kind, m, x) for x in lengths))
        flops += 4.0 * m["H"] * m["Dh"] * n
        byts += (n * _kv_bytes(m, kv_dtype)
                 + len(lengths) * 2 * m["H"] * m["Dh"] * ACT_BYTES)
    return flops, byts


def prefill_attention(model: dict, kv_dtype: str, q0: int, c: int):
    m = dims(model)
    flops = byts = 0.0
    for kind in m["kinds"]:
        first = max(0, q0 - m["W"] + 1) if kind == "local" else 0
        flops += 4.0 * m["H"] * m["Dh"] * _pairs(kind, m, q0, c)
        byts += ((q0 + c - first) * _kv_bytes(m, kv_dtype)
                 + c * 2 * m["H"] * m["Dh"] * ACT_BYTES)
    return flops, byts


def matmul_params(model: dict) -> int:
    """Weights a token multiplies through: attention, the router and its
    top-k experts."""
    m = dims(model)
    attn = m["D"] * (2 * m["H"] * m["Dh"] + 2 * m["Hkv"] * m["Dh"])
    return m["L"] * (attn + m["D"] * m["E"] + m["K"] * 3 * m["D"] * m["F"])


def token_flops(model: dict, position: int, head: bool) -> float:
    m = dims(model)
    f = 2.0 * matmul_params(model)
    f += 4.0 * m["H"] * m["Dh"] * sum(_seen(k, m, position + 1)
                                      for k in m["kinds"])
    return f + (2.0 * m["D"] * m["V"] if head else 0.0)


def chunk_flops(model: dict, q0: int, c: int) -> float:
    m = dims(model)
    f = c * 2.0 * matmul_params(model)
    f += 4.0 * m["H"] * m["Dh"] * sum(_pairs(k, m, q0, c) for k in m["kinds"])
    return f + 2.0 * m["D"] * m["V"]
