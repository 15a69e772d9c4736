"""Stochastic-rounding quantizer kernel.

The conductance-programming primitive of the paper (weights → discrete
device levels, §II-B) — unbiased: E[q(x)] = x.  Reused by the framework for
two distributed-optimization tricks:

  * bf16/int8 optimizer-state rounding (AdamW with low-precision moments),
  * int8 gradient compression with error feedback (optim/compress.py).

Elementwise over a 2-D grid of VMEM blocks; randomness from the same
counter-based PRNG as the other kernels, so results are independent of block
shape and sharding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import prng

DEF_BM, DEF_BN = 256, 512


def _kernel(
    x_ref,
    seed_ref,
    o_ref,
    *,
    n_padded: int,
    step: float,
    lo: float,
    hi: float,
):
    x = jnp.clip(x_ref[...], lo, hi)
    # Multiply by a precomputed f32 reciprocal: a single well-defined f32 op,
    # so the level decision is bit-identical across backends (a division may
    # be rewritten as reciprocal-multiply by some compilers, flipping
    # boundary cases).
    t = (x - lo) * jnp.float32(1.0 / step)
    floor = jnp.floor(t)
    frac = t - floor
    bm, bn = x.shape
    i = pl.program_id(0)
    j = pl.program_id(1)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 0) + jnp.uint32(
        i * bm
    )
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bm, bn), 1) + jnp.uint32(
        j * bn
    )
    idx = rows * jnp.uint32(n_padded) + cols
    u = prng.uniform(idx, seed_ref[0].astype(jnp.uint32))
    q = floor + (u < frac).astype(jnp.float32)
    o_ref[...] = q * jnp.float32(step) + jnp.float32(lo)


def stoch_round_pallas(
    x: jax.Array,
    seed: jax.Array,
    *,
    step: float,
    lo: float,
    hi: float,
    bm: int = DEF_BM,
    bn: int = DEF_BN,
    interpret: bool | object = False,
):
    """x: (M, N) f32 with M % bm == N % bn == 0 (pad in ops.py).
    Stochastically rounds onto the grid {lo + k·step} ∩ [lo, hi]."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    kern = functools.partial(
        _kernel, n_padded=n, step=step, lo=lo, hi=hi
    )
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(x.astype(jnp.float32), seed)
