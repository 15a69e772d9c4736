"""Portable counter-based PRNG used inside Pallas kernels and jnp oracles.

The RACA hardware gets its entropy for free from device thermal noise; the
TPU simulation must synthesize it.  `pltpu.prng_random_bits` has no CPU
interpret-mode implementation, so we use a stateless counter-based hash
(splitmix32 finalizer) built from plain uint32 jnp ops that lower identically
inside Pallas TPU kernels, Pallas interpret mode, and the pure-jnp reference
oracles — giving *bit-exact* kernel-vs-oracle tests even on the stochastic
paths.

Statistical quality is simulation-grade (passes mean/var/correlation checks
in tests), not cryptographic — the same standing as the physical noise it
models.  Noise is fully determined by (seed, element counter), so runs are
reproducible and restart-safe regardless of sharding or block shape.
"""

from __future__ import annotations

import jax.numpy as jnp

# numpy-uint32 scalar constants: these become jaxpr *Literals* (inlined), so
# Pallas kernels can use them — jnp array constants would be captured consts,
# which pallas_call rejects, and bare Python ints > 2^31-1 overflow the weak
# int32 type.
import numpy as _np

_GOLDEN = _np.uint32(0x9E3779B9)
_M1 = _np.uint32(0x7FEB352D)
_M2 = _np.uint32(0x846CA68B)


def hash_u32(x: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """splitmix32-style avalanche hash of a uint32 counter with a seed."""
    x = x.astype(jnp.uint32) + seed.astype(jnp.uint32) * _GOLDEN
    x = (x ^ (x >> 16)) * _M1
    x = (x ^ (x >> 15)) * _M2
    x = x ^ (x >> 16)
    return x


def uniform01(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 bits -> float32 uniform in the open interval (0, 1).

    Uses the top 24 bits (exact in f32) plus a half-ulp offset so log() is
    always finite.  The 24-bit value goes through int32 on its way to f32:
    Mosaic has no uint32 -> f32 conversion, and the value is the same."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24)
    ) + jnp.float32(1.0 / (1 << 25))


def gaussian(idx: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Standard normal per counter element via Box-Muller.

    ``idx`` is a uint32 counter array (globally unique per logical element),
    ``seed`` a uint32 scalar.  Two decorrelated streams come from hashing the
    same counter with offset seeds."""
    seed = seed.astype(jnp.uint32)
    b1 = hash_u32(idx, seed)
    b2 = hash_u32(idx, seed + _GOLDEN)
    u1 = uniform01(b1)
    u2 = uniform01(b2)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * 3.14159265358979) * u2)


def uniform(idx: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Uniform (0,1) per counter element."""
    return uniform01(hash_u32(idx, seed.astype(jnp.uint32)))


def key_to_seed(key) -> jnp.ndarray:
    """Fold a jax PRNG key into a uint32 kernel seed."""
    import jax

    data = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    s = data[0]
    for i in range(1, data.shape[0]):
        s = s * _GOLDEN + data[i]
    return s
