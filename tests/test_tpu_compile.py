"""Compile-only tests of the serving kernels for a TPU v5e chip.

Each test compiles one Pallas kernel of the paged serving path at
stablelm-3b widths (32 heads of 80, 16-token pages, a pool of 257 pages:
4 slots at max_len 1024 plus the trash page) for a described ``v5e:2x2``
topology — the TPU compiler is installed, the chip is not.  A compile
refuses what interpret mode accepts: tiles not aligned to the layout,
more VMEM than a kernel may use, unsupported casts.  Nothing runs, so
nothing here says anything about results or times.  The serve step's
compile also guards the device scopes that name its work in the trace.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H = HKV = 32          # stablelm-3b: multi-head attention
DH = 80
BS = 16               # engine default kv_block_size
PAGES = 4 * (1024 // BS) + 1
F32_POOL = PAGES * BS * HKV * DH * 4   # one layer's pool as f32 (K or V)
# A kernel compiles in a second or two; the bound leaves room for a
# loaded host without letting a minutes-long compile pass.
COMPILE_S = 30.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    secs = time.perf_counter() - t0
    assert "tpu_custom_call" in compiled.as_text()
    assert secs < COMPILE_S, f"compile took {secs:.1f}s"
    return compiled


def _pool_args(one_chip, dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pages = [sds((PAGES, BS, HKV, DH), dtype)] * 2
    scales = [sds((PAGES, BS, HKV), jnp.float32)] * 2
    return sds, pages, scales if dtype == jnp.int8 else []


def _temp_bound(dtype):
    # bf16 pools are cast to f32 in front of the kernel (a whole-pool K
    # and V copy per layer); int8 pages cross HBM as they are.  Anything
    # beyond that plus slack means the kernel grew a new HBM temporary.
    return (2 * F32_POOL if dtype == jnp.bfloat16 else 0) + (4 << 20)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_kernel_compiles(one_chip, dtype):
    from repro.kernels.paged_attention import paged_attention_pallas

    sds, pages, scales = _pool_args(one_chip, dtype)
    b, w = 4, 1024 // BS

    def f(q, kp, vp, table, pos, *sc):
        return paged_attention_pallas(
            q, kp, vp, table, pos,
            k_scale=sc[0] if sc else None, v_scale=sc[1] if sc else None,
        )

    ma = _compile(
        f, sds((b, H, DH), jnp.float32), *pages,
        sds((b, w), jnp.int32), sds((b,), jnp.int32), *scales,
    ).memory_analysis()
    assert ma.temp_size_in_bytes <= _temp_bound(dtype)


@pytest.mark.parametrize(
    "dtype,chunk",
    [
        (jnp.bfloat16, 64),     # the chunk chip_smoke.py serves with
        (jnp.int8, 64),
        (jnp.bfloat16, 1024),   # prefill_chunk=0: a whole 1024 bucket
    ],
)
def test_chunked_prefill_kernel_compiles(one_chip, dtype, chunk):
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas

    sds, pages, scales = _pool_args(one_chip, dtype)

    def f(q, kp, vp, table, q0, *sc):
        return paged_prefill_attention_pallas(
            q, kp, vp, table, q0,
            k_scale=sc[0] if sc else None, v_scale=sc[1] if sc else None,
        )

    ma = _compile(
        f, sds((chunk, H, DH), jnp.float32), *pages,
        sds((1024 // BS,), jnp.int32), sds((), jnp.int32), *scales,
    ).memory_analysis()
    assert ma.temp_size_in_bytes <= _temp_bound(dtype)


def test_stoch_round_kernel_compiles(one_chip):
    """The int8 KV write's rounding kernel at one prefill block's rows:
    (16 tokens · 32 heads, 80), padded to whole (256, 512) blocks."""
    from repro.kernels.stoch_round import stoch_round_pallas

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    ma = _compile(
        lambda x, seed: stoch_round_pallas(
            x, seed, step=1.0, lo=-127.0, hi=127.0
        ),
        sds((BS * HKV, 512), jnp.float32), sds((1,), jnp.int32),
    ).memory_analysis()
    assert ma.temp_size_in_bytes <= 4 << 20


@pytest.mark.parametrize("kv,wta", [("same", False), ("int8", True)])
def test_serve_step_names_its_device_scopes(one_chip, monkeypatch, kv, wta):
    """The paged serve step at stablelm-3b widths (2 layers), as the chip
    runs it: its optimised HLO keeps the scopes the trace reads in each
    op's ``op_name`` -- the sampler, the KV writes, and the f32 cast of
    the pool in front of the kernel, which int8 pages skip."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch import specs as SP

    monkeypatch.setattr(ops, "tpu_kernels", lambda: True)
    mc = dataclasses.replace(
        get_config("stablelm-3b"), n_layers=2, kv_cache_dtype=kv,
        wta_head=wta,
    )
    on = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree,
    )
    b, w = 4, 1024 // BS
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compile(
        SP.make_paged_serve_step(mc),
        on(SP.params_specs(mc)),
        on(SP.paged_decode_cache_specs(mc, b, PAGES, BS)),
        sds((b, w), jnp.int32), sds((b,), jnp.int32),
        sds((b, 2), jnp.uint32), sds((b,), jnp.int32),
    ).as_text()
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    assert "engine.sample/" in op_names
    assert "engine.kv_write/" in op_names
    assert ("engine.pool_cast/" in op_names) == (kv != "int8")
