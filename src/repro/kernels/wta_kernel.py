"""WTA binary stochastic SoftMax kernel (paper §III-B, Fig. 3/5).

Simulates T decision trials of the adaptive-threshold comparator bank for a
block of rows entirely in VMEM:

  per trial: V_j = z_j + n_j,  n_j ~ N(0, σ²)   (thermal noise)
             fired = V_j > V_th0                (comparator bank)
             winner = argmax over fired V_j     (threshold race)
             counts[winner] += 1 if any fired   (§III-C vote counter)

The trial loop is a fori_loop over on-chip state — z is read from HBM once
for all T trials instead of T times (the fusion win; a naive jnp
implementation materializes a (T, B, C) noise tensor in HBM).

Grid: (B/bm,); block (bm, C) with the class axis resident in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import prng

DEF_BM = 128


def _kernel(
    z_ref,     # (bm, C) f32
    seed_ref,  # (1,) int32 SMEM
    cnt_ref,   # (bm, C) f32 out: winner counts
    *,
    n_trials: int,
    vth0: float,
    sigma_z: float,
    c_padded: int,
    valid_c: int,
):
    z = z_ref[...]
    bm, c = z.shape
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bm, c), 0) + jnp.uint32(
        i * bm
    )
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bm, c), 1)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (bm, c), 1)
    pad_mask = col_ids < valid_c  # padded classes can never fire
    base_idx = rows * jnp.uint32(c_padded) + cols
    seed = seed_ref[0].astype(jnp.uint32)
    neg_inf = jnp.float32(jnp.finfo(jnp.float32).min)

    def trial(t, counts):
        idx = base_idx + jnp.uint32(t) * jnp.uint32(bm * c_padded) * jnp.uint32(
            4096
        )
        v = z + prng.gaussian(idx, seed) * jnp.float32(sigma_z)
        fired = (v > jnp.float32(vth0)) & pad_mask
        any_fired = jnp.any(fired, axis=-1, keepdims=True)
        v_masked = jnp.where(fired, v, neg_inf)
        vmax = jnp.max(v_masked, axis=-1, keepdims=True)
        # argmax as "equals max" one-hot; exact ties get split votes — a
        # measure-zero event for continuous noise.
        winner = (v_masked == vmax) & any_fired
        return counts + winner.astype(jnp.float32)

    cnt_ref[...] = jax.lax.fori_loop(
        0, n_trials, trial, jnp.zeros((bm, c), jnp.float32)
    )


def wta_counts_pallas(
    z: jax.Array,
    seed: jax.Array,
    *,
    n_trials: int,
    vth0: float,
    sigma_z: float,
    valid_c: int | None = None,
    bm: int = DEF_BM,
    interpret: bool | object = False,
):
    """z: (B, C) f32, B multiple of bm, C a multiple of 128 (pad in ops.py).
    Returns winner counts (B, C) f32."""
    b, c = z.shape
    assert b % bm == 0, (b, bm)
    kern = functools.partial(
        _kernel,
        n_trials=n_trials,
        vth0=vth0,
        sigma_z=sigma_z,
        c_padded=c,
        valid_c=c if valid_c is None else valid_c,
    )
    return pl.pallas_call(
        kern,
        grid=(b // bm,),
        in_specs=[
            pl.BlockSpec((bm, c), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(z.astype(jnp.float32), seed)
