"""Paged-attention decode kernel: block-table gather + online-softmax.

Serving-side analogue of the paper's memory-path restructuring (removing
the DAC/ADC round-trips): the decode hot spot is not the MAC but the HBM
traffic of re-reading a dense (max_len,) KV window per emitted token.  This
kernel attends over exactly the blocks a request has filled:

  * the block table (scalar-prefetched into SMEM) drives the BlockSpec
    index_map, so each grid step DMAs ONE (block_size,) KV page from HBM —
    pages the request never touched are never fetched;
  * the flash-attention recurrence (running max / denom / accumulator)
    lives in VMEM scratch across the sequential block axis;
  * blocks entirely beyond the request's position are skipped via pl.when;
  * a page crosses HBM in the pool's own dtype (bf16 pools move bf16) and
    is upcast to f32 in VMEM, so no f32 copy of the pool ever exists.

The pool is whatever the table's page ids index.  The decode step passes
every layer's pool at once, as the (units·sublayers·P, bs, Hkv, Dp) view
of its stack, with this layer's offset already added to the table: the
kernel reads the layer's pages in place, and nothing slices them out.
The engine's rows are the head zero-padded to whole 128-lane rows (Dp),
the width the TPU lays such a pool out row-major for; the kernel DMAs
whole rows and computes on the head's Dh lanes.

int8 pools halve that HBM traffic again: K/V pages hold int8 codes plus a
per-(page, slot-in-page, head) f32 scale plane, and dequantization is fused
into the kernel — the DMA moves int8 bytes, scores are multiplied by
``k_scale/127`` and softmax weights by ``v_scale/127`` inside VMEM (the
same scores-not-cache trick as the dense int8 path in models/attention.py),
so a dequantized page never exists anywhere.

Grid: (B, W) with W = table width (blocks per slot), W innermost and
sequential — the accumulator carries across a slot's blocks.

The pure-jnp oracle is kernels/ref.py:paged_attention_ref; CPU tests run
this kernel in interpret mode and the serving engine off
TPU uses the gather + shared-attend jnp path in models/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(
    tbl_ref,   # (B, W) int32 SMEM (scalar prefetch): block table
    pos_ref,   # (B,) int32 SMEM (scalar prefetch): last valid position
    q_ref,     # (1, H, Dh) f32
    k_ref,     # (1, bs, Hkv, Dp) pool dtype or int8 — page tbl[b, w]
    v_ref,     # (1, bs, Hkv, Dp) pool dtype or int8; Dp ≥ Dh
    *rest,     # int8: ks_ref, vs_ref (1, bs, Hkv) f32, then o/m/l/acc refs
    nw: int,
    bs: int,
    hkv: int,
    kind: str,
    local_window: int,
    softcap: float,
    int8: bool,
):
    if int8:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = pos_ref[b]

    # A block whose first position is beyond ``pos`` holds no valid keys:
    # skip its DMA'd page entirely (compute AND accumulator update).
    @pl.when(w * bs <= p)
    def _block():
        q = q_ref[0]                       # (H, Dh)
        h, dh = q.shape
        g = h // hkv
        qg = q.reshape(hkv, g, dh).astype(jnp.float32) * jnp.float32(
            dh**-0.5
        )
        # the head's lanes of the page (rows may be padded past it)
        k = k_ref[0, :, :, :dh].astype(jnp.float32)   # (bs, Hkv, Dh)
        v = v_ref[0, :, :, :dh].astype(jnp.float32)
        sc = jnp.einsum(
            "kgd,tkd->kgt", qg, k, preferred_element_type=jnp.float32
        )
        if int8:
            # fused dequant: int8 codes crossed HBM; the scale multiplies
            # the SCORES in VMEM (factors out of the Dh contraction)
            ks = ks_ref[0].astype(jnp.float32) * jnp.float32(1.0 / 127.0)
            sc = sc * ks.transpose(1, 0)[:, None, :]   # (Hkv, 1, bs)
        if softcap > 0.0:
            sc = jnp.tanh(sc / jnp.float32(softcap)) * jnp.float32(softcap)
        kpos = (
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
            + w * bs
        )
        ok = kpos <= p
        if kind == "local":
            ok &= kpos > (p - local_window)
        sc = sc + jnp.where(ok, 0.0, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(sc - m_new[..., None])
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1)
        if int8:
            # v-side dequant folds into the softmax numerator weights; the
            # denominator keeps the raw pexp sums, exactly like the dense
            # int8 path (scaled numerator / unscaled denominator)
            vs = vs_ref[0].astype(jnp.float32) * jnp.float32(1.0 / 127.0)
            pv = pexp * vs.transpose(1, 0)[:, None, :]
        else:
            pv = pexp
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
            "kgt,tkd->kgd", pv, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(w == nw - 1)
    def _readout():
        _, h, dh = o_ref.shape
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(h, dh)


def paged_attention_pallas(
    q: jax.Array,        # (B, H, Dh) f32 — one query token per slot
    k_pages: jax.Array,  # (P, bs, Hkv, Dp≥Dh) pool dtype (or int8 codes)
    v_pages: jax.Array,
    table: jax.Array,    # (B, W) int32 page ids; <0 treated as page 0
    pos: jax.Array,      # (B,) int32 last valid key position per slot
    *,
    kind: str = "global",
    local_window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # (P, bs, Hkv) f32 for int8 pools
    v_scale: jax.Array | None = None,
    interpret: bool | object = False,
) -> jax.Array:
    """Returns the (B, H, Dh) attention readout over each slot's blocks.

    Pass int8 ``k_pages``/``v_pages`` together with ``k_scale``/``v_scale``
    planes to run the fused-dequant path (int8 page DMA, scaling in VMEM).
    Page rows may be wider than the head (``Dp``, zero-padded to whole
    lanes as the engine's pools are): a whole row crosses HBM and the
    kernel computes on the head's ``Dh`` lanes of it.
    """
    b, h, dh = q.shape
    n_pages, bs, hkv, dp = k_pages.shape
    assert dh <= dp and h % hkv == 0, (q.shape, k_pages.shape)
    int8 = k_scale is not None
    if int8:
        assert v_scale is not None
        assert k_scale.shape == (n_pages, bs, hkv), k_scale.shape
    nw = table.shape[1]
    kern = functools.partial(
        _kernel,
        nw=nw,
        bs=bs,
        hkv=hkv,
        kind=kind,
        local_window=local_window,
        softcap=softcap,
        int8=int8,
    )
    page_map = lambda bi, wi, tbl, ps: (jnp.maximum(tbl[bi, wi], 0), 0, 0, 0)
    scale_map = lambda bi, wi, tbl, ps: (jnp.maximum(tbl[bi, wi], 0), 0, 0)
    in_specs = [
        pl.BlockSpec((1, h, dh), lambda bi, wi, tbl, ps: (bi, 0, 0)),
        pl.BlockSpec((1, bs, hkv, dp), page_map),
        pl.BlockSpec((1, bs, hkv, dp), page_map),
    ]
    # pages cross HBM in the pool's own dtype (bf16, or int8 codes) and
    # are upcast a page at a time in VMEM: a cast of the pool in front of
    # the call would copy every page, live or not, each step
    operands = [
        table.astype(jnp.int32),
        pos.astype(jnp.int32),
        q.astype(jnp.float32),
        k_pages,
        v_pages,
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
        grid=(b, nw),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, h, dh), lambda bi, wi, tbl, ps: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, h // hkv), jnp.float32),
            pltpu.VMEM((hkv, h // hkv), jnp.float32),
            pltpu.VMEM((hkv, h // hkv, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # W must stay sequential (the scratch accumulator carries across
            # a slot's blocks); B revisits scratch only after a full W sweep.
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(*operands)
