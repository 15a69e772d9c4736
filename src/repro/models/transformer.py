"""Decoder-style LM stack covering decoder_lm / moe_lm / ssm / hybrid / vlm
families, with scan-over-units + remat (compile-time and memory bounded),
prefill/decode paths, and vocab-sharded cross-entropy.

The repeating "unit" is cfg.layer_pattern (e.g. gemma2 ("local","global"),
recurrentgemma ("rec","rec","attn"), mamba2 ("ssm",)); units are identical
pytrees so the whole depth is a single lax.scan over stacked params — the
HLO holds ONE unit body regardless of depth, which keeps 512-device GSPMD
compiles tractable and is itself a production requirement (MaxText-style).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import parallel
from . import attention as ATT
from . import mamba2 as M2
from . import moe as MOE
from . import rglru as RG
from .config import ModelConfig
from .layers import (
    dtype_of,
    embed,
    init_embedding,
    init_lm_head,
    init_mlp,
    init_rmsnorm,
    logits_out,
    mlp_apply,
    rmsnorm,
)

# ---------------------------------------------------------------------------
# Init.
# ---------------------------------------------------------------------------


def _init_sublayer(key, kind: str, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    if kind in ("global", "local"):
        p = {
            "ln1": init_rmsnorm(cfg.d_model),
            "attn": ATT.init_attn(ks[0], cfg),
            "ln2": init_rmsnorm(cfg.d_model),
        }
        if cfg.n_experts > 0:
            p["moe"] = MOE.init_moe(ks[1], cfg)
        else:
            p["ffn"] = init_mlp(ks[1], cfg)
        if cfg.post_norms:
            p["post_ln1"] = init_rmsnorm(cfg.d_model)
            p["post_ln2"] = init_rmsnorm(cfg.d_model)
        return p
    if kind == "rec":
        return {
            "ln1": init_rmsnorm(cfg.d_model),
            "rec": RG.init_rglru_block(ks[0], cfg),
            "ln2": init_rmsnorm(cfg.d_model),
            "ffn": init_mlp(ks[1], cfg),
        }
    if kind == "ssm":
        return {
            "ln1": init_rmsnorm(cfg.d_model),
            "mixer": M2.init_mamba(ks[0], cfg),
        }
    raise ValueError(kind)


def init_unit(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, len(cfg.layer_pattern))
    return {
        f"l{i}": _init_sublayer(ks[i], kind, cfg)
        for i, kind in enumerate(cfg.layer_pattern)
    }


def init_lm(key, cfg: ModelConfig) -> dict:
    ke, kh, ku = jax.random.split(key, 3)
    params: dict = {
        "embed": init_embedding(ke, cfg.vocab, cfg.d_model, dtype_of(cfg)),
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = init_lm_head(kh, cfg.d_model, cfg.vocab, dtype_of(cfg))
    unit_keys = jax.random.split(ku, cfg.n_units)
    params["units"] = jax.vmap(lambda k: init_unit(k, cfg))(unit_keys)
    return params


# ---------------------------------------------------------------------------
# Forward (full-sequence).
# ---------------------------------------------------------------------------


def _unit_fwd(
    x: jax.Array,
    up: dict,
    positions: jax.Array,
    cfg: ModelConfig,
    key: Optional[jax.Array],
) -> tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.layer_pattern):
        sub = up[f"l{i}"]
        ki = None if key is None else jax.random.fold_in(key, i)
        if kind in ("global", "local"):
            a = ATT.self_attention(
                sub["attn"],
                rmsnorm(sub["ln1"], x, cfg.norm_eps),
                positions,
                cfg,
                kind=kind,
                key=None if ki is None else jax.random.fold_in(ki, 0),
            )
            if cfg.post_norms:
                a = rmsnorm(sub["post_ln1"], a, cfg.norm_eps)
            x = x + a
            h = rmsnorm(sub["ln2"], x, cfg.norm_eps)
            if cfg.n_experts > 0:
                f, aux_i = MOE.moe_apply(
                    sub["moe"], h, cfg,
                    key=None if ki is None else jax.random.fold_in(ki, 1),
                )
                aux = aux + aux_i
            else:
                f = mlp_apply(
                    sub["ffn"], h, cfg,
                    key=None if ki is None else jax.random.fold_in(ki, 1),
                )
            if cfg.post_norms:
                f = rmsnorm(sub["post_ln2"], f, cfg.norm_eps)
            x = x + f
        elif kind == "rec":
            x = x + RG.rglru_block_apply(
                sub["rec"], rmsnorm(sub["ln1"], x, cfg.norm_eps), cfg, ki
            )
            x = x + mlp_apply(
                sub["ffn"], rmsnorm(sub["ln2"], x, cfg.norm_eps), cfg,
                None if ki is None else jax.random.fold_in(ki, 1),
            )
        elif kind == "ssm":
            x = x + M2.mamba_apply(
                sub["mixer"], rmsnorm(sub["ln1"], x, cfg.norm_eps), cfg, ki
            )
        x = parallel.shard(x, ("batch", "seq", "embed"))
    return x, aux


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "full":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


def backbone(
    params: dict,
    x: jax.Array,           # (B,S,D) already embedded
    positions: jax.Array,   # (B,S)
    cfg: ModelConfig,
    key: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Run all units; returns (hidden states, aux loss)."""

    def body(carry, xs):
        h, aux = carry
        up, uidx = xs
        ku = None if key is None else jax.random.fold_in(key, uidx)
        h, aux_u = _unit_fwd(h, up, positions, cfg, ku)
        return (h, aux + aux_u), None

    body = _remat(body, cfg)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(
            body,
            (x, jnp.zeros((), jnp.float32)),
            (params["units"], jnp.arange(cfg.n_units)),
            unroll=True if cfg.cost_exact else cfg.scan_unroll,
        )
    else:
        aux = jnp.zeros((), jnp.float32)
        for u in range(cfg.n_units):
            up = jax.tree.map(lambda a: a[u], params["units"])
            (x, aux), _ = body((x, aux), (up, jnp.asarray(u)))
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def lm_forward(
    params: dict,
    tokens: jax.Array,  # (B,S)
    cfg: ModelConfig,
    key: Optional[jax.Array] = None,
    prefix_embeds: Optional[jax.Array] = None,  # (B,P,D) VLM patch embeds
) -> tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S',V), aux); S' = P + S with a VLM prefix."""
    x = embed(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x, aux = backbone(params, x, positions, cfg, key)
    logits = logits_out(params["embed"], params.get("head"), x, cfg)
    return logits, aux


# ---------------------------------------------------------------------------
# Loss (vocab-sharded cross-entropy with distributed LSE).
# ---------------------------------------------------------------------------


def cross_entropy(
    logits: jax.Array,  # (B,S,V) — V may be model-sharded
    labels: jax.Array,  # (B,S) int32
    mask: Optional[jax.Array] = None,
    z_loss: float = 1e-4,
) -> tuple[jax.Array, dict]:
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    sumexp = jnp.sum(jnp.exp(lf - m), axis=-1)
    lse = m[..., 0] + jnp.log(sumexp)
    # label logit via masked reduction — no gather across the sharded vocab
    v = lf.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    ll = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - ll
    zl = z_loss * jnp.square(lse)
    per_tok = nll + zl
    if mask is not None:
        w = mask.astype(jnp.float32)
        loss = jnp.sum(per_tok * w) / jnp.maximum(w.sum(), 1.0)
    else:
        loss = per_tok.mean()
    return loss, {"nll": loss, "lse_mean": lse.mean()}


def lm_loss(
    params: dict,
    batch: dict,  # {"tokens": (B,S), "labels": (B,S), optional "mask", "patches"}
    cfg: ModelConfig,
    key: Optional[jax.Array] = None,
) -> tuple[jax.Array, dict]:
    logits, aux = lm_forward(
        params, batch["tokens"], cfg, key, batch.get("patches")
    )
    labels = batch["labels"]
    if batch.get("patches") is not None:
        logits = logits[:, -labels.shape[1] :, :]  # loss on text positions
    loss, metrics = cross_entropy(logits, labels, batch.get("mask"))
    total = loss + aux
    metrics["aux"] = aux
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Prefill / decode.
# ---------------------------------------------------------------------------


def _state_cache_leaves(cfg: ModelConfig, batch: int) -> dict:
    """Recurrent/SSM per-slot states — O(1) per token, so they stay dense
    (slot-addressable) in both the dense and the paged cache layouts."""
    dt = dtype_of(cfg)
    nu = cfg.n_units
    pat = cfg.layer_pattern
    cache: dict = {}
    n_rec = sum(1 for k in pat if k == "rec")
    if n_rec:
        w = cfg.lru_width or cfg.d_model
        cache["rec_conv"] = jnp.zeros((nu, n_rec, batch, 3, w), dt)
        cache["rec_h"] = jnp.zeros((nu, n_rec, batch, w), jnp.float32)
    n_ssm = sum(1 for k in pat if k == "ssm")
    if n_ssm:
        ch = cfg.d_inner + 2 * cfg.ssm_state
        cache["ssm_conv"] = jnp.zeros(
            (nu, n_ssm, batch, cfg.ssm_conv - 1, ch), dt
        )
        cache["ssm_state"] = jnp.zeros(
            (nu, n_ssm, batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state),
            jnp.float32,
        )
    return cache


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Family-appropriate cache pytree with a leading n_units axis."""
    dt = dtype_of(cfg)
    nu = cfg.n_units
    cache: dict = {"pos": jnp.zeros((batch,), jnp.int32)}
    pat = cfg.layer_pattern
    n_attn = sum(1 for k in pat if k in ("global", "local"))
    if n_attn:
        shape = (nu, n_attn, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        if cfg.kv_cache_dtype == "int8":
            cache["k"] = jnp.zeros(shape, jnp.int8)
            cache["v"] = jnp.zeros(shape, jnp.int8)
            cache["k_scale"] = jnp.ones(shape[:-1], jnp.float32)
            cache["v_scale"] = jnp.ones(shape[:-1], jnp.float32)
        else:
            cache["k"] = jnp.zeros(shape, dt)
            cache["v"] = jnp.zeros(shape, dt)
    cache.update(_state_cache_leaves(cfg, batch))
    return cache


def paged_head_dim(head_dim: int) -> int:
    """The last axis of a K/V page pool: ``head_dim`` rounded up to whole
    128-lane rows (see :func:`init_paged_decode_cache`)."""
    return -(-head_dim // 128) * 128


def init_paged_decode_cache(
    cfg: ModelConfig, batch: int, n_pages: int, block_size: int
) -> dict:
    """Paged-layout cache: a shared pool of fixed-size KV blocks.

    Attention K/V live in (nu, n_attn, n_pages, block_size, Hkv, Dp) pools
    shared by ALL slots; which pages a slot owns is the engine's block
    table (host state, passed to the decode step each tick).  Capacity is
    pooled: n_pages · block_size tokens total, instead of the dense
    batch · max_len per-slot reservation.  A page may even back SEVERAL
    slots' tables at once (prefix sharing): prompt blocks are read-only
    for their whole shared lifetime, and the engine copy-on-write forks a
    shared page before any slot writes into it, so nothing in this layout
    (or the decode step) distinguishes shared from private pages.
    Recurrent/SSM states keep the dense slot layout (they are O(1) per
    slot and never shared — they are inserted per admission, from the
    prefill or from the prefix index's stored payload).

    With ``cfg.kv_cache_dtype == "int8"`` the K/V pools hold int8 codes
    (half the HBM bytes per page) plus per-(page, slot-in-page, head) f32
    scale planes; writes quantize with unbiased stochastic rounding and
    reads fold the scales into the attention math (see
    attention.paged_decode_self_attention).

    A K/V row holds the head dim padded with zeros to whole 128-lane
    vregs (:func:`paged_head_dim`); readers take the first ``head_dim``
    lanes.  The TPU lays a row-padded pool out row-major, page by page,
    which is what lets the paged kernels DMA a page straight out of the
    stacked pool.  For a head dim that is not a multiple of 128 (80 in
    stablelm-3b) its default layout would put the page axis minor
    instead, the layout that pads least, and every program would convert
    the whole pool on entry and back on exit.  The padding costs no HBM
    over a row-major pool, which pads to whole vregs anyway.
    """
    dt = dtype_of(cfg)
    nu = cfg.n_units
    cache: dict = {"pos": jnp.zeros((batch,), jnp.int32)}
    pat = cfg.layer_pattern
    n_attn = sum(1 for k in pat if k in ("global", "local"))
    if n_attn:
        shape = (
            nu, n_attn, n_pages, block_size, cfg.n_kv_heads,
            paged_head_dim(cfg.head_dim),
        )
        if cfg.kv_cache_dtype == "int8":
            cache["k_pages"] = jnp.zeros(shape, jnp.int8)
            cache["v_pages"] = jnp.zeros(shape, jnp.int8)
            cache["k_scale_pages"] = jnp.ones(shape[:-1], jnp.float32)
            cache["v_scale_pages"] = jnp.ones(shape[:-1], jnp.float32)
            # monotonic decode-step counter seeding the stochastic rounding:
            # +1 per lm_decode_step, never reset by inserts/eviction, so a
            # cache write's rounding draw is never replayed over the
            # engine's lifetime (a pos-derived seed would repeat after slot
            # turnover)
            cache["quant_step"] = jnp.zeros((), jnp.int32)
        else:
            cache["k_pages"] = jnp.zeros(shape, dt)
            cache["v_pages"] = jnp.zeros(shape, dt)
    cache.update(_state_cache_leaves(cfg, batch))
    return cache


# shared-pool cache leaves (block-table addressed); everything else in a
# paged cache is dense per-slot state.  launch.specs re-exports this as
# PAGE_POOL_LEAVES — keep the two in sync.
PAGE_POOL_LEAVES = (
    "k_pages", "v_pages", "k_scale_pages", "v_scale_pages"
)


def _unit_decode(
    x: jax.Array,         # (B,1,D)
    up: dict,
    ucache: dict,         # this unit's dense per-slot leaves
    pool: dict,           # flattened stacked page pools (paged cache)
    pos: jax.Array,       # (B,)
    cfg: ModelConfig,
    table: Optional[jax.Array] = None,  # (B, W) block table (paged cache)
    uidx: jax.Array | int = 0,          # unit index (seeds int8 rounding)
    quant_base: Optional[jax.Array] = None,  # engine-wide decode counter
    kv_write: bool = True,
) -> tuple[jax.Array, dict, dict]:
    # The paged pools arrive whole, as (nu·n_attn·P, bs, ...) views of
    # the (nu, n_attn, P, bs, ...) stack: each sublayer reads and writes
    # its own pages at offset (uidx·n_attn + i_attn)·P, so no layer's
    # pool is ever sliced out or stacked back.  kv_write=False is the
    # speculative-verify cell: identical math, but the pool is read-only
    # (the draft steps already wrote these positions).
    paged = "k_pages" in pool
    int8_pool = "k_scale_pages" in pool
    if paged:
        n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
        n_pages = pool["k_pages"].shape[0] // (cfg.n_units * n_attn)
    new_cache = dict(ucache)
    i_attn = i_rec = i_ssm = 0
    for i, kind in enumerate(cfg.layer_pattern):
        sub = up[f"l{i}"]
        if kind in ("global", "local"):
            # attention + cache write is the only paged/dense divergence;
            # the norm/FFN tail below is shared so the layouts cannot drift
            if paged:
                scale_kw = {}
                if int8_pool:
                    scale_kw = dict(
                        k_scale_pages=pool["k_scale_pages"],
                        v_scale_pages=pool["v_scale_pages"],
                    )
                if int8_pool and kv_write:
                    # per-(decode step, unit, sublayer) counter-PRNG seed:
                    # quant_base ticks monotonically per lm_decode_step, so
                    # every cache write draws fresh unbiased rounding noise
                    # over the engine's lifetime; the per-element counter
                    # inside stoch_round decorrelates slots/heads within
                    # one write
                    scale_kw["quant_seed"] = (
                        quant_base.astype(jnp.uint32)
                        * jnp.uint32(2654435761)
                        + jnp.asarray(uidx).astype(jnp.uint32)
                        * jnp.uint32(40503)
                        + jnp.uint32(i * 1299721)
                    )
                res = ATT.paged_decode_self_attention(
                    sub["attn"],
                    rmsnorm(sub["ln1"], x, cfg.norm_eps),
                    pool["k_pages"],
                    pool["v_pages"],
                    table,
                    pos,
                    cfg,
                    kind=kind,
                    write=kv_write,
                    page_base=(uidx * n_attn + i_attn) * n_pages,
                    **scale_kw,
                )
                a = res[0]
                if kv_write:
                    pool = dict(zip(PAGE_POOL_LEAVES, res[1:]))
            else:
                int8 = cfg.kv_cache_dtype == "int8"
                res = ATT.decode_self_attention(
                    sub["attn"],
                    rmsnorm(sub["ln1"], x, cfg.norm_eps),
                    ucache["k"][i_attn],
                    ucache["v"][i_attn],
                    pos,
                    cfg,
                    kind=kind,
                    k_scale=ucache["k_scale"][i_attn] if int8 else None,
                    v_scale=ucache["v_scale"][i_attn] if int8 else None,
                )
                a, kc, vc = res[:3]
                new_cache["k"] = new_cache["k"].at[i_attn].set(kc)
                new_cache["v"] = new_cache["v"].at[i_attn].set(vc)
                if int8:
                    new_cache["k_scale"] = (
                        new_cache["k_scale"].at[i_attn].set(res[3])
                    )
                    new_cache["v_scale"] = (
                        new_cache["v_scale"].at[i_attn].set(res[4])
                    )
            i_attn += 1
            if cfg.post_norms:
                a = rmsnorm(sub["post_ln1"], a, cfg.norm_eps)
            x = x + a
            h = rmsnorm(sub["ln2"], x, cfg.norm_eps)
            if cfg.n_experts > 0:
                f, _ = MOE.moe_apply(sub["moe"], h, cfg, None)
            else:
                f = mlp_apply(sub["ffn"], h, cfg, None)
            if cfg.post_norms:
                f = rmsnorm(sub["post_ln2"], f, cfg.norm_eps)
            x = x + f
        elif kind == "rec":
            o, conv, hst = RG.rglru_decode_step(
                sub["rec"],
                rmsnorm(sub["ln1"], x, cfg.norm_eps),
                ucache["rec_conv"][i_rec],
                ucache["rec_h"][i_rec],
                cfg,
            )
            new_cache["rec_conv"] = new_cache["rec_conv"].at[i_rec].set(conv)
            new_cache["rec_h"] = new_cache["rec_h"].at[i_rec].set(hst)
            i_rec += 1
            x = x + o
            x = x + mlp_apply(
                sub["ffn"], rmsnorm(sub["ln2"], x, cfg.norm_eps), cfg, None
            )
        elif kind == "ssm":
            o, conv, st = M2.mamba_decode_step(
                sub["mixer"],
                rmsnorm(sub["ln1"], x, cfg.norm_eps),
                ucache["ssm_conv"][i_ssm],
                ucache["ssm_state"][i_ssm],
                cfg,
            )
            new_cache["ssm_conv"] = new_cache["ssm_conv"].at[i_ssm].set(conv)
            new_cache["ssm_state"] = new_cache["ssm_state"].at[i_ssm].set(st)
            i_ssm += 1
            x = x + o
    return x, new_cache, pool


def lm_decode_step(
    params: dict,
    cache: dict,
    token: jax.Array,  # (B,) int32 — last emitted token
    cfg: ModelConfig,
    table: Optional[jax.Array] = None,  # (B, W) block table (paged cache)
    kv_write: bool = True,
) -> tuple[dict, jax.Array]:
    """One decode step; returns (new cache, logits (B,V)).

    With a paged cache (``k_pages`` leaves + a block ``table``) attention
    reads/writes go through the block pool; the recurrence is otherwise
    identical to the dense path.

    ``kv_write=False`` is the speculative-verify mode: byte-for-byte the
    same math, but attention treats the pool as read-only (the draft
    already wrote these rows) and the returned cache carries only the
    dense per-slot leaves — no pool leaves, no ``quant_step`` tick."""
    pos = cache["pos"]
    qstep = cache.get("quant_step")  # int8 paged pools only
    x = embed(params["embed"], token[:, None], cfg)

    def body(carry, xs):
        h, pl = carry
        up, uc, uidx = xs
        h, uc_new, pl = _unit_decode(
            h, up, uc, pl, pos, cfg, table, uidx, qstep, kv_write
        )
        return (h, pl), uc_new

    # The page pools ride the carry whole, viewed as (nu·n_attn·P, bs, ...)
    # (a merge of leading axes, a bitcast of a row-major pool): the scan
    # slices and restacks only the dense per-slot leaves.
    pool = {
        k: cache[k].reshape((-1,) + cache[k].shape[3:])
        for k in PAGE_POOL_LEAVES if k in cache
    }
    layer_cache = {
        k: v for k, v in cache.items()
        if k not in pool and k not in ("pos", "quant_step")
    }
    if cfg.scan_layers:
        (x, pool), new_layer_cache = jax.lax.scan(
            body, (x, pool),
            (params["units"], layer_cache, jnp.arange(cfg.n_units)),
            unroll=True if cfg.cost_exact else 1,
        )
    else:
        ys = []
        for u in range(cfg.n_units):
            up = jax.tree.map(lambda a: a[u], params["units"])
            uc = jax.tree.map(lambda a: a[u], layer_cache)
            (x, pool), uc_new = body((x, pool), (up, uc, u))
            ys.append(uc_new)
        new_layer_cache = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], params.get("head"), x, cfg)
    new_cache = dict(new_layer_cache)
    new_cache["pos"] = pos + 1
    if kv_write:
        new_cache.update({k: pool[k].reshape(cache[k].shape) for k in pool})
        if qstep is not None:
            new_cache["quant_step"] = qstep + 1
    return new_cache, logits[:, 0, :]


def init_prefill_state(cfg: ModelConfig) -> dict:
    """Zeroed B=1 per-slot state leaves entering a chunked prefill.

    ``pos`` plus the recurrent/SSM leaves — exactly the leaves
    :func:`lm_prefill_chunk` threads between chunks and the engine's
    state insert writes at the slot on completion."""
    state = {"pos": jnp.zeros((1,), jnp.int32)}
    state.update(_state_cache_leaves(cfg, 1))
    return state


def lm_prefill_chunk(
    params: dict,
    tokens: jax.Array,     # (1, c) — one request's suffix chunk
    cfg: ModelConfig,
    pool: dict,            # page-pool leaves (k_pages/v_pages[/scales])
    state: dict,           # B=1 per-slot leaves incl. "pos" (see above)
    table_row: jax.Array,  # (Wp,) int32 blocks covering the prompt bucket
    q0: jax.Array,         # () int32 absolute position of the chunk start
    bucket: int,           # static padded prompt length
    quant_seeds: Optional[jax.Array] = None,  # (nbc,) uint32, int8 pools
    all_logits: bool = False,
) -> tuple[dict, dict, jax.Array]:
    """One chunk of a resumable paged prefill.

    The chunked analogue of :func:`lm_prefill` for the paged layout:
    attention layers write the chunk's K/V into the request's own pages
    and attend over the whole prompt window (shared prefix pages
    included) at absolute positions; recurrent/SSM layers advance their
    state from the carried ``state`` leaves.  A single chunk covering the
    whole bucket from zeroed state reproduces the monolithic prefill
    bit-for-bit — the equivalence anchor for the dense-vs-paged and
    sharing-on-vs-off byte-identity contracts.  int8 pools quantize each
    chunk block under its content-derived seed (folded with the unit and
    sublayer index), so shared blocks stay bit-identical across writers.

    Returns (pool', state', last-token logits (1, V)); ``state'`` is the
    boundary snapshot the engine stashes in the prefix index so a later
    partial-prefix hit can resume exactly here.  With ``all_logits`` the
    logits output is (1, c, V) — every chunk row, not just the last: the
    multi-token-logits variant that lets a k-token chunk act as a
    one-call verifier/oracle over k decode positions (row ``i`` is the
    next-token distribution after absolute position ``q0 + i``).
    """
    b, c = tokens.shape
    x = embed(params["embed"], tokens, cfg)
    positions = jnp.broadcast_to(q0 + jnp.arange(c)[None], (b, c))
    int8_pool = "k_scale_pages" in pool
    layer_state = {k: v for k, v in state.items() if k != "pos"}
    n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
    n_pages = pool["k_pages"].shape[2] if "k_pages" in pool else 0

    def body(carry, xs):
        h, pl = carry
        up, us, uidx = xs
        new_us = dict(us)
        ia = ir = ism = 0
        for i, kind in enumerate(cfg.layer_pattern):
            sub = up[f"l{i}"]
            if kind in ("global", "local"):
                hin = rmsnorm(sub["ln1"], h, cfg.norm_eps)
                kw = {}
                if int8_pool:
                    # content seed folded with (unit, sublayer): rounding
                    # draws decorrelate across layers while staying a pure
                    # function of (block content, block position, layer) —
                    # the property that keeps int8 blocks shareable
                    kw = dict(
                        k_scale_pages=pl["k_scale_pages"],
                        v_scale_pages=pl["v_scale_pages"],
                        quant_seeds=(
                            quant_seeds
                            + jnp.asarray(uidx).astype(jnp.uint32)
                            * jnp.uint32(40503)
                            + jnp.uint32(i * 1299721)
                        ),
                    )
                res = ATT.paged_prefill_self_attention(
                    sub["attn"], hin, pl["k_pages"], pl["v_pages"],
                    table_row, q0, bucket, cfg, kind=kind,
                    page_base=(uidx * n_attn + ia) * n_pages,
                    layer_pages=n_pages, **kw,
                )
                o = res[0]
                pl = dict(zip(PAGE_POOL_LEAVES, res[1:]))
                if cfg.post_norms:
                    o = rmsnorm(sub["post_ln1"], o, cfg.norm_eps)
                h = h + o
                hm = rmsnorm(sub["ln2"], h, cfg.norm_eps)
                if cfg.n_experts > 0:
                    f, _ = MOE.moe_apply(sub["moe"], hm, cfg, None)
                else:
                    f = mlp_apply(sub["ffn"], hm, cfg, None)
                if cfg.post_norms:
                    f = rmsnorm(sub["post_ln2"], f, cfg.norm_eps)
                h = h + f
                ia += 1
            elif kind == "rec":
                hin = rmsnorm(sub["ln1"], h, cfg.norm_eps)
                o, conv, hl = RG.rglru_prefill_chunk(
                    sub["rec"], hin,
                    us["rec_conv"][ir], us["rec_h"][ir], cfg,
                )
                new_us["rec_conv"] = new_us["rec_conv"].at[ir].set(conv)
                new_us["rec_h"] = new_us["rec_h"].at[ir].set(hl)
                h = h + o
                h = h + mlp_apply(
                    sub["ffn"], rmsnorm(sub["ln2"], h, cfg.norm_eps),
                    cfg, None,
                )
                ir += 1
            elif kind == "ssm":
                hin = rmsnorm(sub["ln1"], h, cfg.norm_eps)
                o, conv, st = M2.mamba_prefill_chunk(
                    sub["mixer"], hin,
                    us["ssm_conv"][ism], us["ssm_state"][ism], cfg,
                )
                new_us["ssm_conv"] = new_us["ssm_conv"].at[ism].set(conv)
                new_us["ssm_state"] = new_us["ssm_state"].at[ism].set(st)
                h = h + o
                ism += 1
        return (h, pl), new_us

    # always scan over units — :func:`lm_prefill` scans unconditionally
    # (unlike the decode step, which branches on ``scan_layers``), and the
    # bit-identity anchor requires the exact same HLO structure.  The
    # pools ride the carry whole, as in :func:`lm_decode_step`: the chunk
    # writes its rows into the stack in place, and nothing re-stacks it.
    flat = {k: v.reshape((-1,) + v.shape[3:]) for k, v in pool.items()}
    (x, flat), new_layer_state = jax.lax.scan(
        body, (x, flat),
        (params["units"], layer_state, jnp.arange(cfg.n_units)),
        unroll=True if cfg.cost_exact else 1,
    )
    new_pool = {k: flat[k].reshape(pool[k].shape) for k in pool}
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    rows = x if all_logits else x[:, -1:, :]
    logits = logits_out(params["embed"], params.get("head"), rows, cfg)
    new_state = dict(new_layer_state)
    new_state["pos"] = jnp.full((b,), q0 + c, jnp.int32)
    return new_pool, new_state, logits if all_logits else logits[:, 0, :]


def lm_prefill(
    params: dict,
    tokens: jax.Array,  # (B,S)
    cfg: ModelConfig,
    max_len: int,
    prefix_embeds: Optional[jax.Array] = None,
) -> tuple[dict, jax.Array]:
    """Run the full prompt, building a decode cache.  For attention layers
    this recomputes K/V into the cache buffer; recurrent/SSM layers keep
    their O(1) states.  Returns (cache, last-token logits)."""
    b, s = tokens.shape[0], tokens.shape[1]
    if prefix_embeds is not None:
        s = s + prefix_embeds.shape[1]
    cache = init_decode_cache(cfg, b, max_len)
    x = embed(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
    n_rec = sum(1 for k in cfg.layer_pattern if k == "rec")
    n_ssm = sum(1 for k in cfg.layer_pattern if k == "ssm")

    def body(carry, xs):
        h = carry
        up, uidx = xs
        outs: dict = {}
        ia = ir = ism = 0
        for i, kind in enumerate(cfg.layer_pattern):
            sub = up[f"l{i}"]
            if kind in ("global", "local"):
                hin = rmsnorm(sub["ln1"], h, cfg.norm_eps)
                q, k, v = ATT.qkv(sub["attn"], hin, cfg, None)
                from .layers import apply_rope

                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
                o = ATT.attend_full(
                    q, k, v, positions[0], positions[0], kind, cfg
                )
                o = o.reshape(b, s, -1) @ sub["attn"]["wo"].astype(h.dtype)
                if cfg.post_norms:
                    o = rmsnorm(sub["post_ln1"], o, cfg.norm_eps)
                h = h + o
                hm = rmsnorm(sub["ln2"], h, cfg.norm_eps)
                if cfg.n_experts > 0:
                    f, _ = MOE.moe_apply(sub["moe"], hm, cfg, None)
                else:
                    f = mlp_apply(sub["ffn"], hm, cfg, None)
                if cfg.post_norms:
                    f = rmsnorm(sub["post_ln2"], f, cfg.norm_eps)
                h = h + f
                pad = max_len - s
                kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                if cfg.kv_cache_dtype == "int8":
                    k8, ks = ATT.quantize_kv(kp)
                    v8, vs = ATT.quantize_kv(vp)
                    outs.setdefault("k", []).append(k8)
                    outs.setdefault("v", []).append(v8)
                    outs.setdefault("k_scale", []).append(ks)
                    outs.setdefault("v_scale", []).append(vs)
                else:
                    outs.setdefault("k", []).append(kp)
                    outs.setdefault("v", []).append(vp)
                ia += 1
            elif kind == "rec":
                hin = rmsnorm(sub["ln1"], h, cfg.norm_eps)
                o, conv_tail, h_last = RG.rglru_prefill(sub["rec"], hin, cfg)
                h = h + o
                h = h + mlp_apply(
                    sub["ffn"], rmsnorm(sub["ln2"], h, cfg.norm_eps), cfg, None
                )
                outs.setdefault("rec_conv", []).append(conv_tail)
                outs.setdefault("rec_h", []).append(h_last)
                ir += 1
            elif kind == "ssm":
                hin = rmsnorm(sub["ln1"], h, cfg.norm_eps)
                o, conv_tail, st = M2.mamba_prefill(sub["mixer"], hin, cfg)
                h = h + o
                outs.setdefault("ssm_conv", []).append(conv_tail)
                outs.setdefault("ssm_state", []).append(st)
                ism += 1
        outs = {k2: jnp.stack(v2) for k2, v2 in outs.items()}
        return h, outs

    x, per_unit = jax.lax.scan(
        body, x, (params["units"], jnp.arange(cfg.n_units)),
        unroll=True if cfg.cost_exact else 1,
    )
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], params.get("head"), x[:, -1:, :], cfg)
    for k2, v2 in per_unit.items():
        cache[k2] = v2.astype(cache[k2].dtype)
    cache["pos"] = jnp.full((b,), s, jnp.int32)
    return cache, logits[:, 0, :]
