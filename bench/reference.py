"""The plain reference of a served decoder LM, and the numbers compared.

A straightforward float32 forward pass in ``jax.numpy`` at ``highest``
matmul precision: RMSNorm ``x · rsqrt(mean x² + eps) · (1 + scale)``,
rotary embedding over the whole head (first half / second half), causal
grouped-query attention over every position of the sequence the engine
served (its left pads included), a SwiGLU MLP and an untied head.  It
imports nothing of the program and reads only the benchmark's weights.

``variant`` selects the control: the same pass with every weight matmul
in float8 e4m3 (weights scaled per output channel, activations per row,
float32 accumulation of the rounded values) and K/V rounded to float8
e4m3 or to int4 per (position, head).

The number compared is the gap of a served token: how far the
reference's logit of that token must rise before the reference's own
sampler would have chosen it.  For greedy decoding that is
``max(z) - z[t]``.  For the WTA sampler it is computed from the same
comparator noise the engine drew (``wta_*`` below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dims, layer

HI = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope_tables(n: int, dh: int, theta: float):
    half = dh // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):  # x (S, n, Dh); cos/sin (S, Dh/2)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


FP8_MAX = 448.0  # largest finite float8 e4m3fn


def _fp8(x, axis: int):
    """x rounded to float8 e4m3 under a scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _int(x, bits: int, axis: int):
    """x rounded to signed ``bits``-bit integers under a scale per slice."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, axis=-1), _fp8(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def _kv_round(x, kv: str):  # (S, Hkv, Dh), per (position, head)
    return _fp8(x, axis=-1) if kv == "fp8" else _int(x, 4, axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "eps", "fp8", "kv"))
def _layer(x, w, cos, sin, *, m, eps, fp8, kv):
    m = dict(m)
    s, H, Hkv, Dh = x.shape[0], m["H"], m["Hkv"], m["Dh"]
    h = _rms(x, w["ln1"], eps)
    q = _mm(h, w["wq"], fp8).reshape(s, H, Dh)
    k = _mm(h, w["wk"], fp8).reshape(s, Hkv, Dh)
    v = _mm(h, w["wv"], fp8).reshape(s, Hkv, Dh)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    if kv:
        k, v = _kv_round(k, kv), _kv_round(v, kv)
    qg = q.reshape(s, Hkv, H // Hkv, Dh)
    sc = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) * Dh ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI).reshape(s, H * Dh)
    x = x + _mm(o, w["wo"], fp8)
    h = _rms(x, w["ln2"], eps)
    g = _mm(h, w["w_gate"], fp8)
    return x + _mm(jax.nn.silu(g) * _mm(h, w["w_up"], fp8), w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, rows, scale, w, *, eps, fp8):
    return _mm(_rms(x[rows], scale, eps), w, fp8)


def logits_rows(weights, model: dict, arch: dict, tokens, rows,
                variant: dict | None = None):
    """Logits (len(rows), V) f32 of the sequence ``tokens`` at ``rows``.

    ``arch`` is the config's ``as_run`` group (norm eps, rope theta);
    ``variant`` None is the reference, else the control
    (``{"matmuls": "fp8", "kv": "fp8" | "int4"}``)."""
    m = dims(model)
    n = len(tokens)
    cos, sin = _rope_tables(n, m["Dh"], float(arch["rope_theta"]))
    variant = variant or {}
    fp8 = variant.get("matmuls") == "fp8"
    kv = variant.get("kv", "")
    eps = float(arch["norm_eps"])
    static = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["embedding"][jnp.asarray(tokens)]
        x = x.astype(jnp.float32)
        for i in range(m["L"]):
            x = _layer(x, layer(weights, i), cos, sin, m=static, eps=eps,
                       fp8=fp8, kv=kv)
        # rows padded to the sequence's length: one compile for every request
        padded = np.zeros(n, np.int32)
        padded[:len(rows)] = rows
        z = _head(x, jnp.asarray(padded), weights["final_norm"]["scale"],
                  weights["head"]["w"], eps=eps, fp8=fp8)
        return z[:len(rows)]


# ---------------------------------------------------------------------------
# The numbers compared
# ---------------------------------------------------------------------------


def greedy_gaps(z_ref, served) -> np.ndarray:
    """max(z) - z[t] per row: how far the served token lies below the
    reference's best."""
    z = np.asarray(z_ref)
    t = np.asarray(served)
    return z.max(axis=-1) - z[np.arange(len(t)), t]


def wta_noise(keys, trials: int, vocab: int, sigma: float):
    """The comparator noise of the WTA sampler for per-token keys (n, 2):
    ``normal(key, (trials, vocab)) · sigma`` — what the engine draws for
    a request's output token ``i`` under ``fold_in(request key, i)``."""
    return jax.vmap(
        lambda k: jax.random.normal(k, (trials, vocab), jnp.float32)
    )(keys) * sigma


TOP_K = 16


@functools.partial(jax.jit, static_argnames=("trials", "sigma"))
def _wta_top(z, keys, served, *, trials, sigma):
    """Per trial, the reference's TOP_K highest comparator voltages (and
    their tokens) and the served token's voltage."""
    v = z[:, None, :] + wta_noise(keys, trials, z.shape[-1], sigma)
    top_v, top_i = jax.lax.top_k(v, TOP_K)
    v_t = jnp.take_along_axis(v, served[:, None, None], axis=-1)[..., 0]
    return top_v, top_i, v_t


@functools.partial(jax.jit, static_argnames=("trials", "sigma", "vth0"))
def wta_choice(z, keys, *, trials, sigma, vth0):
    """The WTA sampler's token for logits z (n, V): the most frequent
    trial winner, the lowest id on a tie."""
    v = z[:, None, :] + wta_noise(keys, trials, z.shape[-1], sigma)
    fired = v > vth0
    win = jnp.argmax(jnp.where(fired, v, -jnp.inf), axis=-1)
    hit = jnp.any(fired, axis=-1).astype(jnp.int32)
    counts = jnp.zeros(z.shape, jnp.int32)
    counts = counts.at[jnp.arange(z.shape[0])[:, None], win].add(hit)
    return jnp.argmax(counts, axis=-1)


def _wta_can_pick(top_v, top_i, v_t, t: int, vth0: float, eps: float) -> bool:
    """Whether logits within ``eps`` of the reference's can make the vote
    pick ``t``.  Each trial is settled on its own (a relaxation: in truth
    one logit shift serves all trials): t takes every trial it can win,
    a trial in which nothing need fire goes to nobody, and the rest must
    be shared among the tokens that can win them so that none reaches
    t's count (a lower id may not even tie it).  That sharing is a
    matching of trials to tokens with capacities, found by augmenting
    paths."""
    mine, rest = 0, []
    for k in range(len(v_t)):
        top = top_v[k, 0]
        if v_t[k] >= top - 2 * eps and v_t[k] > vth0 - eps:
            mine += 1
        elif top > vth0 + eps:
            rest.append([int(j) for v, j in zip(top_v[k], top_i[k])
                         if j != t and v >= top - 2 * eps and v > vth0 - eps])
    if mine == 0:
        return t == 0 and not rest
    cap = lambda j: mine - 1 if j < t else mine
    owner: dict[int, list[int]] = {}

    def place(k, seen) -> bool:
        for j in rest[k]:
            if j in seen:
                continue
            seen.add(j)
            held = owner.setdefault(j, [])
            if len(held) < cap(j):
                held.append(k)
                return True
            for k2 in list(held):
                if place(k2, seen):
                    held.remove(k2)
                    held.append(k)
                    return True
        return False

    return all(place(k, set()) for k in range(len(rest)))


def wta_gap_one(top_v, top_i, v_t, t: int, vth0: float) -> float:
    """Twice the least uniform logit error under which the WTA vote could
    pick ``t`` from the reference's logits and the engine's noise: the
    WTA counterpart of a greedy gap, ``max(z) - z[t]``."""
    if _wta_can_pick(top_v, top_i, v_t, t, vth0, 0.0):
        return 0.0
    lo = 0.0
    hi = max(float(np.max(top_v[:, 0] - v_t)) / 2,
             float(np.max(vth0 - v_t)), 0.0) + 1e-6
    for _ in range(30):
        mid = (lo + hi) / 2
        if _wta_can_pick(top_v, top_i, v_t, t, vth0, mid):
            hi = mid
        else:
            lo = mid
    return 2 * hi


def _blocks(n: int, block: int):
    """Row indices in blocks of exactly ``block`` (the last one padded with
    row 0), so that each jitted function compiles once: (rows, kept)."""
    for i in range(0, n, block):
        rows = np.zeros(block, np.int32)
        kept = min(block, n - i)
        rows[:kept] = np.arange(i, i + kept)
        yield rows, kept


def wta_gaps(z_ref, keys, served, wta: dict, block: int = 16) -> np.ndarray:
    """The WTA gap of each served token; zero, with no search, where the
    reference's own vote picks it."""
    served = np.asarray(served)
    kw = dict(trials=int(wta["trials"]), sigma=float(wta["sigma_z"]))
    vth0 = float(wta["vth0"])
    vote = control_choice(z_ref, keys, wta, block)
    out = np.zeros(len(served))
    off = np.flatnonzero(vote != served)
    for idx, kept in _blocks(len(off), block):
        rows = off[idx]
        top_v, top_i, v_t = (np.asarray(a) for a in _wta_top(
            z_ref[rows], keys[rows], jnp.asarray(served[rows]), **kw))
        for j, r in enumerate(rows[:kept]):
            out[r] = wta_gap_one(top_v[j], top_i[j], v_t[j], int(served[r]),
                                 vth0)
    return out


def control_choice(z_ctrl, keys, wta: dict | None, block: int = 16):
    """The token the control's own sampler puts first at each row."""
    if wta is None:
        return np.asarray(jnp.argmax(z_ctrl, axis=-1))
    kw = dict(trials=int(wta["trials"]), sigma=float(wta["sigma_z"]),
              vth0=float(wta["vth0"]))
    return np.concatenate([
        np.asarray(wta_choice(z_ctrl[rows], keys[rows], **kw))[:kept]
        for rows, kept in _blocks(z_ctrl.shape[0], block)
    ])


def gaps(z_ref, keys, served, wta: dict | None) -> np.ndarray:
    """The gap of each served token under the reference logits."""
    if wta is None:
        return greedy_gaps(z_ref, served)
    return wta_gaps(z_ref, keys, served, wta)
