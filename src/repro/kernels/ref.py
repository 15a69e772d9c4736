"""Pure-jnp oracles for every Pallas kernel.

Each function reproduces the corresponding kernel *bit-exactly* (same
counter-based PRNG, same quantization, same accumulation order up to f32
matmul reassociation) so tests can assert_allclose with tight tolerances
even on the stochastic paths.  These are also the implementations used
inside the 512-device dry-run compile (core/analog.py falls back here off
TPU), so kernel and reference must stay semantically identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import prng


def _quantize(w, qstep, w_min, w_max):
    w = jnp.clip(w, w_min, w_max)
    return jnp.round((w - w_min) * jnp.float32(1.0 / qstep)) * qstep + w_min


def crossbar_mac_ref(
    x: jax.Array,
    w: jax.Array,
    seed: jax.Array,
    *,
    binarize: bool = True,
    physical_noise: bool = False,
    sigma_z: jax.Array | float = 1.702,
    noise_params: tuple = (0.0, 1.0, 0.0, 1.0, 0),
    quantize: bool = True,
    qstep: float = 2.0 / 31,
    w_min: float = -1.0,
    w_max: float = 1.0,
    valid_k: int | None = None,
) -> jax.Array:
    """Oracle for crossbar_mac_pallas on already-padded (M,K)x(K,N) inputs."""
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if quantize:
        wf = _quantize(wf, qstep, w_min, w_max)
    if valid_k is not None and valid_k != wf.shape[0]:
        krow = jax.lax.broadcasted_iota(jnp.int32, wf.shape, 0)
        wf = jnp.where(krow < valid_k, wf, 0.0)
    z = xf @ wf
    if physical_noise:
        four_ktdf, g0, g_ref, v_read, k_rows = noise_params
        sum_g = g0 * wf.sum(axis=0, keepdims=True) + 2.0 * k_rows * g_ref
        sigma = jnp.sqrt(four_ktdf * sum_g) / (v_read * g0)
    else:
        sigma = jnp.float32(sigma_z)
    m, n = z.shape
    rows = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 1)
    gidx = rows * jnp.uint32(n) + cols
    noise = prng.gaussian(gidx, jnp.asarray(seed).astype(jnp.uint32)) * sigma
    v = z + noise
    return (v > 0.0).astype(jnp.float32) if binarize else v


def wta_counts_ref(
    z: jax.Array,
    seed: jax.Array,
    *,
    n_trials: int,
    vth0: float,
    sigma_z: float,
    valid_c: int | None = None,
    bm: int = 128,
) -> jax.Array:
    """Oracle for wta_counts_pallas.  Reproduces the kernel's counter layout
    (per-block row indices, trial stride) exactly."""
    b, c = z.shape
    if valid_c is None:
        valid_c = c
    zf = z.astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (b, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (b, c), 1)
    base_idx = rows * jnp.uint32(c) + cols
    pad_mask = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1) < valid_c
    seed_u = jnp.asarray(seed).astype(jnp.uint32)
    neg_inf = jnp.float32(jnp.finfo(jnp.float32).min)

    def trial(t, counts):
        idx = base_idx + jnp.uint32(t) * jnp.uint32(bm * c) * jnp.uint32(4096)
        v = zf + prng.gaussian(idx, seed_u) * jnp.float32(sigma_z)
        fired = (v > jnp.float32(vth0)) & pad_mask
        any_fired = jnp.any(fired, axis=-1, keepdims=True)
        v_masked = jnp.where(fired, v, neg_inf)
        vmax = jnp.max(v_masked, axis=-1, keepdims=True)
        winner = (v_masked == vmax) & any_fired
        return counts + winner.astype(jnp.float32)

    return jax.lax.fori_loop(
        0, n_trials, trial, jnp.zeros((b, c), jnp.float32)
    )


def paged_attention_ref(
    q: jax.Array,        # (B, H, Dh)
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) — cache dtype or int8 codes
    v_pages: jax.Array,
    table: jax.Array,    # (B, W) int32 page ids; <0 treated as page 0
    pos: jax.Array,      # (B,) int32 last valid key position
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Oracle for paged_attention_pallas: gather the table's blocks into a
    contiguous (W·bs) window, then masked full-softmax attention.  Block i
    holds logical positions [i·bs, (i+1)·bs); positions beyond ``pos`` (and
    outside the local window) get NEG_INF scores — exactly zero probability
    in f32.

    With int8 pools the per-(page, slot-in-page, head) scale planes are
    gathered through the same table and folded into scores / softmax
    weights (never into the cache): scores pick up ``k_scale/127`` and the
    value reduction weights pick up ``v_scale/127`` — the same ordering as
    the dense int8 trick in models.attention.attend_one_token."""
    neg_inf = jnp.float32(-2.0e38)
    b, h, dh = q.shape
    _, bs, hkv, _ = k_pages.shape
    g = h // hkv
    pages = jnp.maximum(table, 0)
    # page rows may be zero-padded past the head (whole-lane pools)
    kb = k_pages[pages][..., :dh].reshape(b, -1, hkv, dh)
    vb = v_pages[pages][..., :dh].reshape(b, -1, hkv, dh)
    t = kb.shape[1]
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32) * jnp.float32(
        dh**-0.5
    )
    sc = jnp.einsum(
        "bkgd,btkd->bkgt", qg, kb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if k_scale is not None:
        ks = k_scale[pages].reshape(b, t, hkv)
        sc = sc * (ks.transpose(0, 2, 1) / 127.0)[:, :, None, :]
    if softcap > 0.0:
        sc = jnp.tanh(sc / jnp.float32(softcap)) * jnp.float32(softcap)
    kpos = jnp.arange(t)[None]
    ok = kpos <= pos[:, None]
    if kind == "local":
        ok &= kpos > (pos[:, None] - local_window)
    sc = sc + jnp.where(ok, 0.0, neg_inf)[:, None, None, :]
    w = jax.nn.softmax(sc, axis=-1)
    if v_scale is not None:
        vs = v_scale[pages].reshape(b, t, hkv)
        w = w * (vs.transpose(0, 2, 1) / 127.0)[:, :, None, :]
    out = jnp.einsum(
        "bkgt,btkd->bkgd", w, vb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, h, dh)


def prefill_attention_ref(
    q: jax.Array,        # (S, H, Dh) — one request's suffix-chunk queries
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) — cache dtype or int8 codes
    v_pages: jax.Array,
    table: jax.Array,    # (W,) int32 page ids; <0 treated as page 0
    q0: jax.Array,       # () int32 absolute position of the first query
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Oracle for prefill_attention_pallas: gather the request's blocks
    into a contiguous (W·bs) window, then masked full-softmax attention
    for every suffix query at once.  Query i sits at absolute position
    ``q0 + i`` and key t of block w at ``w·bs + t``, so the causal / local
    mask is exact even though the query tile starts mid-prompt (the whole
    point: suffix queries attend into shared prefix pages).

    int8 pools fold the gathered scale planes into scores / softmax
    weights exactly like :func:`paged_attention_ref` (scores pick up
    ``k_scale/127``, value-reduction weights ``v_scale/127`` — the cache
    itself is never dequantized)."""
    neg_inf = jnp.float32(-2.0e38)
    s, h, dh = q.shape
    _, bs, hkv, _ = k_pages.shape
    g = h // hkv
    pages = jnp.maximum(table, 0)
    kb = k_pages[pages][..., :dh].reshape(-1, hkv, dh)
    vb = v_pages[pages][..., :dh].reshape(-1, hkv, dh)
    t = kb.shape[0]
    qg = q.reshape(s, hkv, g, dh).astype(jnp.float32) * jnp.float32(
        dh**-0.5
    )
    sc = jnp.einsum(
        "skgd,tkd->kgst", qg, kb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if k_scale is not None:
        ks = k_scale[pages].reshape(t, hkv)
        sc = sc * (ks.transpose(1, 0) / 127.0)[:, None, None, :]
    if softcap > 0.0:
        sc = jnp.tanh(sc / jnp.float32(softcap)) * jnp.float32(softcap)
    qpos = q0 + jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    ok = kpos <= qpos
    if kind == "local":
        ok &= kpos > (qpos - local_window)
    sc = sc + jnp.where(ok, 0.0, neg_inf)[None, None, :, :]
    w = jax.nn.softmax(sc, axis=-1)
    if v_scale is not None:
        vs = v_scale[pages].reshape(t, hkv)
        w = w * (vs.transpose(1, 0) / 127.0)[:, None, None, :]
    out = jnp.einsum(
        "kgst,tkd->skgd", w, vb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(s, h, dh)


def stoch_round_ref(
    x: jax.Array,
    seed: jax.Array,
    *,
    step: float,
    lo: float,
    hi: float,
) -> jax.Array:
    """Oracle for stoch_round_pallas on padded (M, N) input."""
    m, n = x.shape
    xf = jnp.clip(x.astype(jnp.float32), lo, hi)
    t = (xf - lo) * jnp.float32(1.0 / step)  # see stoch_round.py note
    floor = jnp.floor(t)
    frac = t - floor
    rows = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (m, n), 1)
    idx = rows * jnp.uint32(n) + cols
    u = prng.uniform(idx, jnp.asarray(seed).astype(jnp.uint32))
    q = floor + (u < frac).astype(jnp.float32)
    return q * jnp.float32(step) + jnp.float32(lo)
