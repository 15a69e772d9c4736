"""Fused RACA crossbar kernel: quantize → MAC → thermal noise → comparator.

This is the paper's compute hot spot as a single TPU kernel.  One pass over
the weights performs, entirely in VMEM:

  1. conductance-grid quantization of the weight tile (Eq. 4-7),
  2. the MXU matmul accumulation (the crossbar dot product, Eq. 9/12),
  3. per-column ΣG accumulation (the physical noise variance, Eq. 11/13),
  4. Gaussian thermal-noise synthesis (counter-based PRNG, see prng.py),
  5. the comparator: stochastic binarization (Eq. 8) or linear readout.

TPU adaptation of the paper's circuit: crossbar tiles map to MXU-aligned
(128-multiple) VMEM blocks; the analog current summing across row tiles
becomes the sequential K-grid accumulation in a f32 VMEM scratch; the
comparator bank is the VPU compare at the final K step.  HBM traffic is one
read of x and W and one write of the (binary) output — the fusion is the
kernel-level payoff of removing the "ADC" (no intermediate z round-trip).

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import prng

DEF_BM, DEF_BN, DEF_BK = 128, 128, 512


def _kernel(
    x_ref,      # (bm, bk) f32
    w_ref,      # (bk, bn) f32
    seed_ref,   # (2,) int32, SMEM: [seed, bitcast-f32 sigma_z]
    o_ref,      # (bm, bn) f32
    acc_ref,    # (bm, bn) f32 VMEM scratch: z accumulator
    wsum_ref,   # (1, bn)  f32 VMEM scratch: per-column Σ W_q
    *,
    nk: int,
    n_padded: int,
    valid_k: int,
    binarize: bool,
    physical_noise: bool,
    noise_params: tuple,  # (four_ktdf, g0, g_ref, v_read, k_rows)
    quantize: bool,
    qstep: float,
    w_min: float,
    w_max: float,
):
    # grid indices read at the top level: program_id inside a pl.when branch
    # is not substituted by interpret mode on older jax (cpu tests)
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        wsum_ref[...] = jnp.zeros_like(wsum_ref)

    w = w_ref[...]
    if quantize:
        # Round-to-nearest onto the conductance grid (in-VMEM, never
        # materialized in HBM).  Reciprocal-multiply keeps the level decision
        # bit-identical across backends (see stoch_round.py).
        w = jnp.clip(w, w_min, w_max)
        w = jnp.round((w - w_min) * jnp.float32(1.0 / qstep)) * qstep + w_min
    bk = w.shape[0]
    if valid_k % bk != 0:
        # Zero out K-padding rows: physical rows beyond the matrix must not
        # contribute to either the MAC or the ΣG noise variance.
        krow = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) + k * bk
        w = jnp.where(krow < valid_k, w, 0.0)

    acc_ref[...] += jnp.dot(
        x_ref[...], w, preferred_element_type=jnp.float32
    )
    if physical_noise:
        wsum_ref[...] += jnp.sum(w, axis=0, keepdims=True)

    @pl.when(k == nk - 1)
    def _readout():
        z = acc_ref[...]
        if physical_noise:
            four_ktdf, g0, g_ref, v_read, k_rows = noise_params
            sum_g = g0 * wsum_ref[...] + 2.0 * k_rows * g_ref
            sigma = jnp.sqrt(four_ktdf * sum_g) / (v_read * g0)
        else:
            # runtime sigma (depends on the traced dynamic-range scale)
            sigma = jax.lax.bitcast_convert_type(seed_ref[1], jnp.float32)
        # Globally-unique per-element counter -> reproducible thermal noise.
        bm, bn = z.shape
        rows = jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0)
        cols = jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1)
        gidx = (rows + jnp.uint32(i * bm)) * jnp.uint32(n_padded) + (
            cols + jnp.uint32(j * bn)
        )
        noise = prng.gaussian(gidx, seed_ref[0].astype(jnp.uint32)) * sigma
        v = z + noise
        if binarize:
            o_ref[...] = (v > 0.0).astype(jnp.float32)
        else:
            o_ref[...] = v


def crossbar_mac_pallas(
    x: jax.Array,
    w: jax.Array,
    seed: jax.Array,  # (2,) int32: [seed, bitcast-f32 sigma_z]
    *,
    binarize: bool = True,
    physical_noise: bool = False,
    noise_params: tuple = (0.0, 1.0, 0.0, 1.0, 0),
    quantize: bool = True,
    qstep: float = 2.0 / 31,
    w_min: float = -1.0,
    w_max: float = 1.0,
    bm: int = DEF_BM,
    bn: int = DEF_BN,
    bk: int = DEF_BK,
    valid_k: int | None = None,
    interpret: bool | object = False,
):
    """Raw pallas_call wrapper.  x: (M, K) f32, w: (K, N) f32 — M, K, N must
    already be multiples of (bm, bk, bn); use ops.crossbar_mac for padding,
    STE gradients and key handling."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, k, n, bm, bn, bk)
    )
    nk = k // bk
    kern = functools.partial(
        _kernel,
        nk=nk,
        n_padded=n,
        valid_k=k if valid_k is None else valid_k,
        binarize=binarize,
        physical_noise=physical_noise,
        noise_params=noise_params,
        quantize=quantize,
        qstep=qstep,
        w_min=w_min,
        w_max=w_max,
    )
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(x.astype(jnp.float32), w.astype(jnp.float32), seed)
