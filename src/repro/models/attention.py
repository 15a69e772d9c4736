"""Grouped-query attention with RoPE, local/global masking, soft-capping,
KV caches, and memory-efficient (online-softmax) chunked computation.

The chunked path never materializes the full (S, T) score matrix: queries
attend to KV chunks under a lax.scan carrying running (max, denom, acc) —
the standard flash-attention recurrence expressed in pure JAX so that GSPMD
can shard it (the Pallas kernel budget of this repo belongs to the paper's
crossbar pipeline, not attention).

Attention softmax is intentionally digital: the paper's WTA neuron emits
one-hot *samples*, not the weighted average attention requires (DESIGN.md
§5).  QKV/O projections do route through core.analog (linear readout) in
analog modes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import parallel
from repro.core import analog as A
from repro.kernels import ops as KOPS
from .config import ModelConfig
from .layers import apply_rope, dtype_of, softcap

NEG_INF = -2.0e38


class KVCache(NamedTuple):
    k: jax.Array      # (B, Smax, Hkv, Dh)
    v: jax.Array      # (B, Smax, Hkv, Dh)
    length: jax.Array  # (B,) int32 — tokens currently valid


def init_attn(key, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    hd, h, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    init = lambda k, shape, fan: (
        jax.random.normal(k, shape, jnp.float32) * fan**-0.5
    ).astype(dt)
    return {
        "wq": init(ks[0], (d, h * hd), d),
        "wk": init(ks[1], (d, hkv * hd), d),
        "wv": init(ks[2], (d, hkv * hd), d),
        "wo": init(ks[3], (h * hd, d), h * hd),
    }


def _proj_cfg(cfg: ModelConfig) -> A.AnalogConfig:
    a = cfg.analog
    return a.with_mode("analog_linear") if a.mode == "analog_stochastic" else a


def qkv(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    key: Optional[jax.Array] = None,
):
    b, s, _ = x.shape
    acfg = _proj_cfg(cfg)
    keys = (None,) * 3 if key is None else jax.random.split(key, 3)
    q = A.analog_matmul(acfg, keys[0], x, p["wq"])
    k = A.analog_matmul(acfg, keys[1], x, p["wk"])
    v = A.analog_matmul(acfg, keys[2], x, p["wv"])
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = parallel.shard(q, ("batch", "seq", "heads", None))
    k = parallel.shard(k, ("batch", "seq", "kv_heads", None))
    v = parallel.shard(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def _group(q: jax.Array, hkv: int) -> jax.Array:
    """(B,S,H,Dh) -> (B,S,Hkv,G,Dh) grouped query heads."""
    b, s, h, dh = q.shape
    return q.reshape(b, s, hkv, h // hkv, dh)


def _maybe_expand(q, k, v, cfg: ModelConfig):
    """Perf transforms (§Perf): pad query heads to a shardable count and/or
    repeat KV heads to the full head count.  Both are numerically identity
    for the used heads; padded heads' outputs are sliced away by the caller
    (the w_o projection only consumes the real heads)."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    # head padding with grouped KV would scramble the q->kv grouping, so
    # padding implies the repeated-KV (MHA) layout
    repeat = cfg.gqa_repeat_kv or (
        cfg.attn_pad_heads and cfg.attn_pad_heads > h
    )
    if repeat and hkv < h:
        # repeat at the ORIGINAL head count (preserves q-head → kv-head
        # grouping), before any padding
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
        hkv = h
    if cfg.attn_pad_heads and cfg.attn_pad_heads > h:
        pad = cfg.attn_pad_heads - h
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if hkv == h:  # repeated layout: pad kv alongside q
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        h = cfg.attn_pad_heads
    if repeat:
        k = parallel.shard(k, ("batch", "seq", "heads", None))
        v = parallel.shard(v, ("batch", "seq", "heads", None))
    q = parallel.shard(q, ("batch", "seq", "heads", None))
    return q, k, v


def attend_full(
    q: jax.Array,  # (B,S,H,Dh)
    k: jax.Array,  # (B,T,Hkv,Dh)
    v: jax.Array,
    qpos: jax.Array,  # (S,) query positions
    kpos: jax.Array,  # (T,) key positions
    kind: str,        # global | local | none
    cfg: ModelConfig,
) -> jax.Array:
    """Online-softmax attention over KV chunks; returns (B,S,H,Dh).

    The mask is computed per KV chunk from positions — the full (S,T) score
    or bias matrix is never materialized (O(S·chunk) temporaries).  Probs
    dtype and chunk length are perf knobs (EXPERIMENTS.md §Perf)."""
    h_orig = q.shape[2]
    q, k, v = _maybe_expand(q, k, v, cfg)
    b, s, h, dh = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    pdt = jnp.dtype(cfg.attn_probs_dtype)
    qg = _group(q, hkv).astype(pdt) * jnp.asarray(dh**-0.5, pdt)
    kf = k.astype(pdt)
    vf = v.astype(pdt)
    kv_chunk = cfg.attn_kv_chunk
    nchunks = max(t // kv_chunk, 1)
    cs = t // nchunks
    assert t % cs == 0, (t, cs)

    kc = kf.reshape(b, nchunks, cs, hkv, dh).transpose(1, 0, 2, 3, 4)
    vc = vf.reshape(b, nchunks, cs, hkv, dh).transpose(1, 0, 2, 3, 4)
    kposc = kpos.reshape(nchunks, cs)

    def step(carry, inp):
        m, l, acc = carry
        kci, vci, kpi = inp
        # scores: (B, Hkv, G, S, cs); accumulate in f32 on the MXU even for
        # bf16 operands
        sc = jnp.einsum(
            "bskgd,bckd->bkgsc", qg, kci,
            preferred_element_type=jnp.float32,
        )
        if cfg.attn_softcap > 0.0:
            sc = softcap(sc, cfg.attn_softcap)
        d = qpos[:, None] - kpi[None, :]  # (S, cs)
        if kind == "none":
            ok = jnp.ones(d.shape, bool)
        elif kind == "local":
            ok = (d >= 0) & (d < cfg.local_window)
        else:
            ok = d >= 0
        sc = sc + jnp.where(ok, 0.0, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        scale = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None]).astype(pdt)
        l_new = l * scale + p.sum(axis=-1).astype(jnp.float32)
        acc_new = acc * scale[..., None] + jnp.einsum(
            "bkgsc,bckd->bkgsd", p, vci,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, h // hkv, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, h // hkv, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, h // hkv, s, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc, vc, kposc),
        unroll=True if cfg.cost_exact else 1,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)
    return out[:, :, :h_orig, :].astype(q.dtype)


def self_attention(
    p: dict,
    x: jax.Array,
    positions: jax.Array,  # (B, S)
    cfg: ModelConfig,
    kind: str = "global",
    key: Optional[jax.Array] = None,
    use_rope: bool = True,
) -> jax.Array:
    b, s, _ = x.shape
    kq = ko = None
    if key is not None:
        kq, ko = jax.random.split(key)
    q, k, v = qkv(p, x, cfg, kq)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    qpos = positions[0] if positions.ndim == 2 else positions
    out = attend_full(q, k, v, qpos, qpos, kind, cfg)
    out = out.reshape(b, s, -1)
    o = A.analog_matmul(_proj_cfg(cfg), ko, out, p["wo"])
    return parallel.shard(o, ("batch", "seq", "embed"))


def cross_attention(
    p: dict,
    x: jax.Array,
    enc_out: jax.Array,
    cfg: ModelConfig,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Decoder cross-attention over encoder outputs (no mask, no RoPE)."""
    b, s, _ = x.shape
    t = enc_out.shape[1]
    acfg = _proj_cfg(cfg)
    keys = (None,) * 4 if key is None else tuple(jax.random.split(key, 4))
    q = A.analog_matmul(acfg, keys[0], x, p["wq"]).reshape(
        b, s, cfg.n_heads, cfg.head_dim
    )
    k = A.analog_matmul(acfg, keys[1], enc_out, p["wk"]).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim
    )
    v = A.analog_matmul(acfg, keys[2], enc_out, p["wv"]).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim
    )
    qpos = jnp.arange(s)
    kpos = jnp.arange(t)
    out = attend_full(q, k, v, qpos, kpos, "none", cfg).reshape(b, s, -1)
    o = A.analog_matmul(acfg, keys[3], out, p["wo"])
    return parallel.shard(o, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache).
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int):
    dt = dtype_of(cfg)
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dt),
        v=jnp.zeros(shape, dt),
        length=jnp.zeros((batch,), jnp.int32),
    )


def cache_spec():
    """Logical axes for a stacked KV cache (leading layer axis)."""
    return ("layers", "batch", "seq", "kv_heads", None)


def _write_at(buf: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """buf: (B, Smax, Hkv, Dh); new: (B, 1, Hkv, Dh); pos: (B,) int32."""

    def one(b, n, p):
        return jax.lax.dynamic_update_slice(b, n, (p, 0, 0))

    return jax.vmap(one)(buf, new, pos)


def quantize_kv(x: jax.Array):
    """Symmetric per-(batch, pos, head) int8 quantization of K/V rows.

    The scale factors out of the head_dim contraction, so scoring against an
    int8 cache multiplies *scores* (not the cache) by scale/127 — no
    dequantized cache is ever materialized.  Conceptually this is the
    paper's conductance-grid programming applied to the cache (the
    stochastic-rounding variant runs through kernels/stoch_round on TPU)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (..., Hkv)
    scale = jnp.maximum(scale, 1e-6)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None] * 127.0)
    return q.astype(jnp.int8), scale


def attend_one_token(
    q: jax.Array,        # (B, 1, H, Dh), RoPE already applied
    k_buf: jax.Array,    # (B, T, Hkv, Dh)  bf16/f32 or int8
    v_buf: jax.Array,
    pos: jax.Array,      # (B,) int32 — last valid key position
    cfg: ModelConfig,
    kind: str = "global",
    k_scale: Optional[jax.Array] = None,  # (B, T, Hkv) for int8 buffers
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-token attention readout over a contiguous KV window.

    Shared by the dense decode path (k_buf = the per-slot max_len cache) and
    the paged decode path (k_buf = the blocks gathered through the block
    table) — using the *same* einsum/softmax computation on both is what
    makes dense-vs-paged greedy decode byte-identical.  Key positions beyond
    ``pos`` contribute exactly-zero probability (NEG_INF scores underflow to
    0 in the softmax), so a longer window only appends exact zeros.

    Returns the (B, 1, H*Dh) attention output before the w_o projection.
    """
    b = q.shape[0]
    int8_cache = k_buf.dtype == jnp.int8
    t = k_buf.shape[1]
    hkv = cfg.n_kv_heads
    cdt = (
        jnp.bfloat16 if int8_cache else jnp.dtype(cfg.attn_probs_dtype)
    )
    qg = _group(q, hkv).astype(cdt) * jnp.asarray(cfg.head_dim**-0.5, cdt)
    sc = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k_buf.astype(cdt),
        preferred_element_type=jnp.float32,
    )
    if int8_cache:
        sc = sc * (k_scale.transpose(0, 2, 1) / 127.0)[:, :, None, None, :]
    if cfg.attn_softcap > 0.0:
        sc = softcap(sc, cfg.attn_softcap)
    kpos = jnp.arange(t)[None]
    ok = kpos <= pos[:, None]
    if kind == "local":
        ok &= kpos > (pos[:, None] - cfg.local_window)
    sc = sc + jnp.where(ok, 0.0, NEG_INF)[:, None, None, None, :]
    w = jax.nn.softmax(sc, axis=-1)
    if int8_cache:
        w = w * (v_scale.transpose(0, 2, 1) / 127.0)[:, :, None, None, :]
    out = jnp.einsum(
        "bkgst,btkd->bskgd", w.astype(cdt), v_buf.astype(cdt),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, -1)


def decode_self_attention(
    p: dict,
    x: jax.Array,            # (B, 1, D)
    k_cache: jax.Array,      # (B, Smax, Hkv, Dh)  bf16 or int8
    v_cache: jax.Array,
    pos: jax.Array,          # (B,) current position (0-based write index)
    cfg: ModelConfig,
    kind: str = "global",
    use_rope: bool = True,
    k_scale: Optional[jax.Array] = None,  # (B, Smax, Hkv) for int8 caches
    v_scale: Optional[jax.Array] = None,
):
    """One-token attention against the cache.

    Returns (out, k_cache, v_cache[, k_scale, v_scale]).  Cache reads use
    mixed-precision einsums (operands stay in cache dtype, f32 MXU
    accumulation) — no full-cache f32 casts."""
    int8_cache = k_cache.dtype == jnp.int8
    q, k, v = qkv(p, x, cfg, None)
    if use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    if int8_cache:
        k8, ks = quantize_kv(k)
        v8, vs = quantize_kv(v)
        k_cache = _write_at(k_cache, k8, pos)
        v_cache = _write_at(v_cache, v8, pos)
        k_scale = jax.vmap(
            lambda bscale, n, p_: jax.lax.dynamic_update_slice(
                bscale, n, (p_, 0)
            )
        )(k_scale, ks[:, 0:1], pos)
        v_scale = jax.vmap(
            lambda bscale, n, p_: jax.lax.dynamic_update_slice(
                bscale, n, (p_, 0)
            )
        )(v_scale, vs[:, 0:1], pos)
    else:
        k_cache = _write_at(k_cache, k, pos)
        v_cache = _write_at(v_cache, v, pos)
    out = attend_one_token(
        q, k_cache, v_cache, pos, cfg, kind,
        k_scale=k_scale, v_scale=v_scale,
    ).astype(x.dtype)
    o = A.analog_matmul(_proj_cfg(cfg), None, out, p["wo"])
    if int8_cache:
        return o, k_cache, v_cache, k_scale, v_scale
    return o, k_cache, v_cache


# ---------------------------------------------------------------------------
# Paged decode path (block-table KV cache).
# ---------------------------------------------------------------------------


def _fit_rows(new: jax.Array, pages: jax.Array) -> jax.Array:
    """``new`` in the pool's dtype, its last axis zero-padded to the
    pool's (K/V rows hold the head dim padded to whole 128-lane rows,
    models.transformer.paged_head_dim; scale planes are not padded)."""
    pad = pages.shape[-1] - new.shape[-1]
    if pad:
        new = jnp.pad(new, [(0, 0)] * (new.ndim - 1) + [(0, pad)])
    return new.astype(pages.dtype)


def paged_write(
    pages: jax.Array,   # (P, bs, ...) block pool (K/V or scale planes)
    new: jax.Array,     # (B, 1, ...) this step's K/V rows or scales
    table: jax.Array,   # (B, W) int32 block table (page ids)
    pos: jax.Array,     # (B,) int32 logical write position per slot
) -> jax.Array:
    """Scatter one token's K/V rows (or their scales) into each slot's
    current block.

    The target page is ``table[b, pos[b] // bs]``; the engine guarantees
    every slot's *current* page is exclusively owned, so the scatter never
    collides.  With prefix sharing, a page may appear in several slots'
    tables (aliased READS are fine — the gather is pure), but a shared
    page is never a write target: the engine's host-side copy-on-write
    pass forks (or deregisters) any still-shared page at ``pos // bs``
    before the decode step runs, which is what keeps this scatter
    collision-free.  Slots whose table row is all-trash (page 0, the
    engine's reserved scratch block) write into page 0, which no live
    request ever reads.  ``pos // bs`` is clamped into the table width
    so evicted slots whose ``pos`` keeps advancing stay in bounds.
    """
    bs = pages.shape[1]
    blk = jnp.clip(pos // bs, 0, table.shape[1] - 1)
    page_ids = jnp.take_along_axis(table, blk[:, None], axis=1)[:, 0]
    page_ids = jnp.maximum(page_ids, 0)  # unassigned (-1) → trash page 0
    return pages.at[page_ids, pos % bs].set(_fit_rows(new[:, 0], pages))


def paged_gather(pages: jax.Array, table: jax.Array) -> jax.Array:
    """(P, bs, ...), (B, W) → (B, W·bs, ...) contiguous window.

    Block i of a slot's table holds logical positions [i·bs, (i+1)·bs), so
    the gathered window is exactly the prefix of the dense per-slot cache —
    the invariant the dense-vs-paged equivalence tests pin down.  Several
    table rows may name the same page (prefix sharing): the gather
    replicates it per slot, so shared and private layouts read
    identically.  Works for K/V pools (trailing (Hkv, Dh)) and their
    scale planes (trailing (Hkv,)).
    """
    b, w = table.shape
    bs = pages.shape[1]
    return pages[jnp.maximum(table, 0)].reshape(
        (b, w * bs) + pages.shape[2:]
    )


def paged_write_chunk(
    pages: jax.Array,   # (P, bs, ...) block pool (K/V or scale planes)
    new: jax.Array,     # (nbc, bs, ...) block-shaped chunk rows
    table_row: jax.Array,  # (Wp,) int32 — ONE request's block-table row
    b0: jax.Array,      # () int32 first block index the chunk covers
) -> jax.Array:
    """Scatter a suffix chunk's K/V rows (or scales) into its own pages.

    The chunk covers blocks ``[b0, b0 + nbc)`` of the request's table; the
    engine guarantees the chunk starts block-aligned (resume points and
    ``prefill_chunk`` are block multiples), so whole blocks scatter at
    once.  A ragged final block carries zero-padded rows beyond the prompt
    — those positions are masked out of every read until the decode step
    overwrites them row by row.  Page ids and ``b0`` are traced: one
    compile per (bucket, chunk shape) serves every page set."""
    nbc = new.shape[0]
    ids = jax.lax.dynamic_slice(table_row, (b0,), (nbc,))
    return pages.at[jnp.maximum(ids, 0)].set(_fit_rows(new, pages))


def _chunk_to_blocks(x: jax.Array, bs: int) -> jax.Array:
    """(1, c, ...) chunk rows → (nbc, bs, ...) zero-padded whole blocks."""
    c = x.shape[1]
    nbc = -(-c // bs)
    pad = [(0, 0), (0, nbc * bs - c)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[0].reshape((nbc, bs) + x.shape[2:])


def paged_prefill_self_attention(
    p: dict,
    x: jax.Array,        # (1, c, D) — one request's suffix chunk
    k_pages: jax.Array,  # (L·P, bs, Hkv, Dp) — every layer's block pool
    v_pages: jax.Array,
    table_row: jax.Array,  # (Wp,) int32 — blocks covering the prompt bucket
    q0: jax.Array,       # () int32 absolute position of the chunk's start
    bucket: int,         # static padded prompt length (table covers it)
    cfg: ModelConfig,
    kind: str = "global",
    k_scale_pages: Optional[jax.Array] = None,  # (L·P, bs, Hkv) int8 pools
    v_scale_pages: Optional[jax.Array] = None,
    quant_seeds: Optional[jax.Array] = None,    # (nbc,) uint32, int8 pools
    page_base: jax.Array | int = 0,  # this layer's first page in the pools
    layer_pages: Optional[int] = None,  # P (default: the whole pool)
):
    """Suffix-chunk attention against the paged pool (the chunked-prefill
    building block).  Writes the chunk's K/V into its own pages, then the
    chunk's queries attend over the WHOLE prompt window ``[0, bucket)`` —
    shared prefix pages and the chunk's fresh pages alike — with absolute
    position offsets, so a suffix that starts mid-prompt masks exactly as
    if the full prompt had been prefilled monolithically.

    As in :func:`paged_decode_self_attention`, the pools are the stacked
    pools of every attention layer, flattened over their leading axes;
    this layer's pages are ``[page_base, page_base + layer_pages)``.  The
    chunk's rows land in the stack in place; attention reads a slice of
    this layer's pages.

    On TPU the gather+attend runs as the fused Pallas chunked-prefill
    kernel (kernels/prefill_attention.py).  Off TPU the bf16 path is the
    jnp gather + the same :func:`attend_full` used by the monolithic dense
    prefill — per-query online-softmax values are independent of which
    other queries share the tile, which is what makes suffix-only prefill
    byte-identical to prefilling the whole prompt (the dense-vs-paged and
    sharing-on-vs-off equivalence contracts).  int8 pools quantize each
    chunk block under its content-derived ``quant_seeds`` (shared blocks
    stay bit-identical across writers) and run the fused-dequant oracle.

    Returns (out (1, c, D) after w_o, k_pages, v_pages) — plus the scale
    planes for int8 pools.
    """
    int8_pool = k_pages.dtype == jnp.int8
    b, c, _ = x.shape
    bs = k_pages.shape[1]
    rows = jnp.maximum(table_row, 0) + page_base   # pages in the stack
    layer = functools.partial(
        jax.lax.dynamic_slice_in_dim, start_index=page_base,
        slice_size=layer_pages or k_pages.shape[0],
    )
    positions = jnp.broadcast_to(q0 + jnp.arange(c)[None], (b, c))
    q, k, v = qkv(p, x, cfg, None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    b0 = q0 // bs
    with jax.named_scope("engine.kv_write"):
        kb = _chunk_to_blocks(k, bs)   # (nbc, bs, Hkv, Dh)
        vb = _chunk_to_blocks(v, bs)
        if int8_pool:
            kc, ks, vc, vs = [], [], [], []
            for i in range(kb.shape[0]):
                # per-block quantization under content-derived seeds: any
                # writer of the same block content produces bit-identical
                # codes, which is what keeps int8 blocks shareable
                k8, ksc, v8, vsc = KOPS.quantize_kv_pair_int8(
                    kb[i], vb[i], quant_seeds[i]
                )
                kc.append(k8)
                ks.append(ksc)
                vc.append(v8)
                vs.append(vsc)
            k_pages = paged_write_chunk(k_pages, jnp.stack(kc), rows, b0)
            v_pages = paged_write_chunk(v_pages, jnp.stack(vc), rows, b0)
            k_scale_pages = paged_write_chunk(
                k_scale_pages, jnp.stack(ks), rows, b0
            )
            v_scale_pages = paged_write_chunk(
                v_scale_pages, jnp.stack(vs), rows, b0
            )
        else:
            k_pages = paged_write_chunk(k_pages, kb, rows, b0)
            v_pages = paged_write_chunk(v_pages, vb, rows, b0)
    if KOPS.tpu_kernels() or int8_pool:
        out = KOPS.paged_prefill_attention(
            q[0], layer(k_pages), layer(v_pages), table_row, q0,
            kind=kind,
            local_window=cfg.local_window,
            softcap=cfg.attn_softcap,
            k_scale=layer(k_scale_pages) if int8_pool else None,
            v_scale=layer(v_scale_pages) if int8_pool else None,
        )[None].astype(x.dtype)          # (1, c, H, Dh)
    else:
        # bit-parity route with the monolithic prefill: gather the window,
        # slice it to exactly the bucket length (same key chunking as
        # attend_full over the full prompt), same online-softmax helper
        dh = cfg.head_dim
        k_buf = paged_gather(layer(k_pages), table_row[None])[:, :bucket]
        v_buf = paged_gather(layer(v_pages), table_row[None])[:, :bucket]
        k_buf, v_buf = k_buf[..., :dh], v_buf[..., :dh]
        qpos = q0 + jnp.arange(c)
        kpos = jnp.arange(bucket)
        out = attend_full(q, k_buf, v_buf, qpos, kpos, kind, cfg)
    # w_o through the same direct matmul as the monolithic lm_prefill (the
    # byte-identity oracle), not the decode path's analog projection
    o = out.reshape(b, c, -1) @ p["wo"].astype(x.dtype)
    if int8_pool:
        return o, k_pages, v_pages, k_scale_pages, v_scale_pages
    return o, k_pages, v_pages


def paged_decode_self_attention(
    p: dict,
    x: jax.Array,        # (B, 1, D)
    k_pages: jax.Array,  # (L·P, bs, Hkv, Dp) — every layer's block pool
    v_pages: jax.Array,
    table: jax.Array,    # (B, W) int32 block table (W·bs covers max(pos)+1)
    pos: jax.Array,      # (B,) int32
    cfg: ModelConfig,
    kind: str = "global",
    use_rope: bool = True,
    k_scale_pages: Optional[jax.Array] = None,  # (L·P, bs, Hkv) int8 pools
    v_scale_pages: Optional[jax.Array] = None,
    quant_seed: Optional[jax.Array] = None,     # uint32 scalar, int8 pools
    write: bool = True,
    page_base: jax.Array | int = 0,  # this layer's first page in the pools
):
    """One-token attention against a paged (block-table) KV cache.

    The pools are the stacked pools of every attention layer, flattened
    over their leading (unit, sublayer, page) axes; this layer's pages are
    ``[page_base, page_base + P)``, so table entry ``t`` names page
    ``max(t, 0) + page_base``.  Reads and writes address that stack in
    place: no layer's pool is ever sliced out, cast, or stacked back.

    Writes this step's K/V into each slot's current block, then attends over
    the W gathered blocks only — O(W·bs) work per token instead of
    O(max_len).  On TPU the gather+attend runs as the fused Pallas
    paged-attention kernel (kernels/paged_attention.py); elsewhere it is the
    pure-jnp gather + the shared :func:`attend_one_token` (bit-identical to
    the dense path over the valid prefix).

    With an int8 pool (``k_pages.dtype == int8``; scale planes + a
    ``quant_seed`` provided) the new K/V row is quantized with unbiased
    stochastic rounding (kernels.ops.quantize_kv_int8 — the paper's
    conductance-programming primitive applied to cache writes) and the
    per-(page, slot-in-page, head) scales ride through the same block
    table; dequantization is fused into the attention math on both
    backends (scores × k_scale/127, weights × v_scale/127 — the cache is
    never dequantized in memory).

    Returns (out, k_pages, v_pages) — plus (k_scale_pages, v_scale_pages)
    for int8 pools.
    """
    int8_pool = k_pages.dtype == jnp.int8
    table = jnp.maximum(table, 0) + page_base
    q, k, v = qkv(p, x, cfg, None)
    if use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    # ``write=False`` is the speculative-verify re-read: the draft step
    # already wrote this position's K/V (bit-identical rows — same inputs,
    # same seed trajectory), so the verifier attends the pages as they
    # are.  Skipping the write keeps the pool untouched (int8 pools would
    # otherwise re-quantize under a different quant_step and change bits)
    # and lets the caller drop the pool from its scan carry.
    if write:
        with jax.named_scope("engine.kv_write"):
            if int8_pool:
                k8, ks, v8, vs = KOPS.quantize_kv_pair_int8(k, v, quant_seed)
                k_pages = paged_write(k_pages, k8, table, pos)
                v_pages = paged_write(v_pages, v8, table, pos)
                k_scale_pages = paged_write(k_scale_pages, ks, table, pos)
                v_scale_pages = paged_write(v_scale_pages, vs, table, pos)
            else:
                k_pages = paged_write(k_pages, k, table, pos)
                v_pages = paged_write(v_pages, v, table, pos)
    if KOPS.tpu_kernels():
        out = KOPS.paged_attention(
            q[:, 0], k_pages, v_pages, table, pos,
            kind=kind,
            local_window=cfg.local_window,
            softcap=cfg.attn_softcap,
            k_scale=k_scale_pages if int8_pool else None,
            v_scale=v_scale_pages if int8_pool else None,
        )[:, None].reshape(x.shape[0], 1, -1)
    else:
        dh = cfg.head_dim
        k_buf = paged_gather(k_pages, table)[..., :dh]
        v_buf = paged_gather(v_pages, table)[..., :dh]
        out = attend_one_token(
            q, k_buf, v_buf, pos, cfg, kind,
            k_scale=paged_gather(k_scale_pages, table)
            if int8_pool else None,
            v_scale=paged_gather(v_scale_pages, table)
            if int8_pool else None,
        )
    out = out.astype(x.dtype)
    o = A.analog_matmul(_proj_cfg(cfg), None, out, p["wo"])
    if int8_pool:
        return o, k_pages, v_pages, k_scale_pages, v_scale_pages
    return o, k_pages, v_pages
