"""Prefix-aware chunked-prefill attention kernel.

The partial-prefix serving path computes only a prompt's *suffix* (the
blocks not already resident in the paged pool) and lets those suffix
queries attend into the shared prefix pages directly — the prefill-side
analogue of the paper's thesis: delete work whose result is already
materialized in the array (here: K/V of a shared system prompt) instead of
regenerating it through the full pipeline.

Shape contract: ONE request per call.  The wrapper lays the suffix
chunk's queries out head-major, (Hkv, G, S, Dh), and the grid tiles them
over S in power-of-two tiles of at most ``DEF_TQ`` rows.  Inside the
kernel a tile is one (Hkv, G·tq, Dh) operand — the same batched-over-
KV-heads contraction the decode kernel runs, with G·tq query rows per
head instead of G — so no 4-D transpose of the query chunk is ever
built in VMEM.  (A resident (S, H, Dh) chunk with in-kernel 4-D
transposes took minutes to compile at S = 256; the tiled layout
compiles in seconds.)  Keys/values are gathered block by block from the
paged pool:

  * the request's block table (scalar-prefetched into SMEM) drives the
    BlockSpec index map, so each grid step DMAs ONE (block_size,) K/V page
    from HBM — shared prefix pages and the chunk's own freshly written
    pages go through the same path;
  * queries carry their ABSOLUTE positions (``q0 + i``), so the causal /
    local mask is exact even though the tile starts mid-prompt;
  * the flash-attention recurrence (running max / denom / accumulator)
    lives in VMEM scratch across the sequential block axis;
  * blocks entirely beyond the last query position skip their compute
    AND accumulator update via pl.when (the page DMA itself still runs —
    the grid covers the full table width).

int8 pools ride the same fused-dequant scheme as the decode kernel
(kernels/paged_attention.py): pages DMA int8 codes plus per-(page,
slot-in-page, head) f32 scale planes, scores pick up ``k_scale/127`` and
softmax weights ``v_scale/127`` inside VMEM — a dequantized page never
exists anywhere.

Grid: (S / tq, W).  W is innermost and sequential — the accumulator
carries across the request's blocks; each query tile starts it afresh.
The pure-jnp oracle is kernels/ref.py:prefill_attention_ref; CPU tests
run this kernel in interpret mode, and off TPU the serving engine's bf16
path uses the gather + attend_full jnp route in models/attention.py
(bit-identical to the dense monolithic prefill).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
# query rows per grid step (the tile is rounded down to the chunk's
# power-of-two cover, so short chunks do not pad up to it)
DEF_TQ = 128


def _kernel(
    tbl_ref,   # (W,) int32 SMEM (scalar prefetch): the request's block table
    q0_ref,    # (1,) int32 SMEM (scalar prefetch): first query position
    q_ref,     # (Hkv, G, tq, Dh) f32 — one tile of head-major queries
    k_ref,     # (1, bs, Hkv, Dp) f32 (or int8 codes) — page tbl[w]
    v_ref,     # (1, bs, Hkv, Dp) f32 (or int8 codes); Dp ≥ Dh
    *rest,     # int8: ks_ref, vs_ref (1, bs, Hkv) f32, then o/m/l/acc refs
    nw: int,
    bs: int,
    s: int,
    kind: str,
    local_window: int,
    softcap: float,
    int8: bool,
):
    if int8:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hkv, g, tq, dh = q_ref.shape
    r = g * tq
    row0 = q0_ref[0] + qi * tq   # absolute position of the tile's row 0

    # A block whose first position is beyond the tile's last REAL query
    # holds no attendable keys for it: skip its DMA'd page entirely.
    @pl.when(w * bs <= q0_ref[0] + jnp.minimum(qi * tq + tq, s) - 1)
    def _block():
        q = q_ref[...].reshape(hkv, r, dh) * jnp.float32(dh**-0.5)
        # the head's lanes of the page (rows may be padded past it)
        k = k_ref[0, :, :, :dh].astype(jnp.float32)    # (bs, Hkv, Dh)
        v = v_ref[0, :, :, :dh].astype(jnp.float32)
        sc = jnp.einsum(
            "krd,tkd->krt", q, k, preferred_element_type=jnp.float32
        )                                   # (Hkv, G·tq, bs)
        if int8:
            # fused dequant: int8 codes crossed HBM; the scale multiplies
            # the SCORES in VMEM (factors out of the Dh contraction)
            ks = ks_ref[0].astype(jnp.float32) * jnp.float32(1.0 / 127.0)
            sc = sc * ks.transpose(1, 0)[:, None, :]
        if softcap > 0.0:
            sc = jnp.tanh(sc / jnp.float32(softcap)) * jnp.float32(softcap)
        # absolute positions: row g·tq + i holds query i (tq is a power of
        # two, so i is the row's low bits), key t of block w sits at w·bs + t
        rows = jax.lax.broadcasted_iota(jnp.int32, (1, r, bs), 1)
        qpos = (rows & (tq - 1)) + row0
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, r, bs), 2) + w * bs
        ok = kpos <= qpos
        if kind == "local":
            ok &= kpos > (qpos - local_window)
        sc = sc + jnp.where(ok, 0.0, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(sc - m_new[..., None])
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1)
        if int8:
            # v-side dequant folds into the softmax numerator weights; the
            # denominator keeps the raw pexp sums (scaled numerator /
            # unscaled denominator, same as the decode kernel)
            vs = vs_ref[0].astype(jnp.float32) * jnp.float32(1.0 / 127.0)
            pv = pexp * vs.transpose(1, 0)[:, None, :]
        else:
            pv = pexp
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
            "krt,tkd->krd", pv, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(w == nw - 1)
    def _readout():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[...] = out.reshape(hkv, g, tq, dh)


def _query_tile(s: int) -> int:
    """Rows per query tile: the chunk's power-of-two cover (at least 8,
    the f32 sublane count), capped at ``DEF_TQ``."""
    t = 8
    while t < s and t < DEF_TQ:
        t *= 2
    return t


def paged_prefill_attention_pallas(
    q: jax.Array,        # (S, H, Dh) f32 — the suffix chunk's queries
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) pool dtype (or int8 codes)
    v_pages: jax.Array,
    table: jax.Array,    # (W,) int32 page ids; <0 treated as page 0
    q0: jax.Array,       # () int32 absolute position of the first query
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
    interpret: bool | object = False,
) -> jax.Array:
    """Returns the (S, H, Dh) attention readout of a suffix chunk over the
    request's blocks (shared prefix pages + its own freshly written pages).

    Pass int8 ``k_pages``/``v_pages`` together with ``k_scale``/``v_scale``
    planes to run the fused-dequant path (int8 page DMA, scaling in VMEM).
    Page rows may be wider than the head (``Dp``, zero-padded to whole
    lanes as the engine's pools are): a whole row crosses HBM and the
    kernel computes on the head's ``Dh`` lanes of it.
    """
    s, h, dh = q.shape
    n_pages, bs, hkv, dp = k_pages.shape
    assert dh <= dp and h % hkv == 0, (q.shape, k_pages.shape)
    int8 = k_scale is not None
    if int8:
        assert v_scale is not None
        assert k_scale.shape == (n_pages, bs, hkv), k_scale.shape
    g = h // hkv
    tq = _query_tile(s)
    nq = -(-s // tq)
    nw = table.shape[0]
    # head-major queries, zero-padded to whole tiles (padded rows are
    # sliced away below and never gate a block: see the kernel's skip)
    qh = q.astype(jnp.float32).reshape(s, hkv, g, dh).transpose(1, 2, 0, 3)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, nq * tq - s), (0, 0)))
    kern = functools.partial(
        _kernel,
        nw=nw,
        bs=bs,
        s=s,
        kind=kind,
        local_window=local_window,
        softcap=softcap,
        int8=int8,
    )
    q_map = lambda qi, wi, tbl, p0: (0, 0, qi, 0)
    page_map = lambda qi, wi, tbl, p0: (jnp.maximum(tbl[wi], 0), 0, 0, 0)
    scale_map = lambda qi, wi, tbl, p0: (jnp.maximum(tbl[wi], 0), 0, 0)
    in_specs = [
        pl.BlockSpec((hkv, g, tq, dh), q_map),
        pl.BlockSpec((1, bs, hkv, dp), page_map),
        pl.BlockSpec((1, bs, hkv, dp), page_map),
    ]
    # keep int8 codes int8 on the wire — halving the page DMA bytes is the
    # point; everything else is normalized to f32 before the call
    operands = [
        table.astype(jnp.int32),
        jnp.asarray(q0, jnp.int32).reshape((1,)),
        qh,
    ]
    with jax.named_scope("engine.pool_cast"):
        operands += [
            k_pages if int8 else k_pages.astype(jnp.float32),
            v_pages if int8 else v_pages.astype(jnp.float32),
        ]
    if int8:
        in_specs += [
            pl.BlockSpec((1, bs, hkv), scale_map),
            pl.BlockSpec((1, bs, hkv), scale_map),
        ]
        operands += [
            k_scale.astype(jnp.float32),
            v_scale.astype(jnp.float32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq, nw),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((hkv, g, tq, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g * tq), jnp.float32),
            pltpu.VMEM((hkv, g * tq), jnp.float32),
            pltpu.VMEM((hkv, g * tq, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, g, nq * tq, dh), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # W must stay sequential: the scratch accumulator carries the
            # online-softmax state across the request's blocks, and a
            # query tile revisits scratch only after a full W sweep.
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(*operands)
    return out[:, :, :s].transpose(2, 0, 1, 3).reshape(s, h, dh)
