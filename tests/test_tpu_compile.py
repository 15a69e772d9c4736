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
DP = 128              # a pool row: the head padded to whole lanes
BS = 16               # engine default kv_block_size
PAGES = 4 * (1024 // BS) + 1
F32_POOL = PAGES * BS * HKV * DP * 4   # one layer's pool as f32 (K or V)
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
    pages = [sds((PAGES, BS, HKV, DP), dtype)] * 2
    scales = [sds((PAGES, BS, HKV), jnp.float32)] * 2
    return sds, pages, scales if dtype == jnp.int8 else []


def _temp_bound(dtype, kernel):
    # The decode kernel DMAs pages in the pool's own dtype, so it needs
    # no HBM temporary.  The prefill kernel still casts a bf16 pool to f32
    # in front of the call (a whole-pool K and V copy per layer); int8
    # pages cross HBM as they are.  Anything beyond that plus slack means
    # the kernel grew a new HBM temporary.
    cast = kernel == "prefill" and dtype == jnp.bfloat16
    return (2 * F32_POOL if cast else 0) + (4 << 20)


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
    assert ma.temp_size_in_bytes <= _temp_bound(dtype, "decode")


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
    assert ma.temp_size_in_bytes <= _temp_bound(dtype, "prefill")


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
    op's ``op_name`` -- the sampler and the KV writes.  No pool is cast
    in front of the decode kernel, so ``engine.pool_cast`` is absent for
    either pool dtype."""
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
    assert "engine.pool_cast/" not in op_names


# an HLO instruction: name, result dtype and dims, opcode
_HLO_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w-]+)\("
)
# a computation's header, and the fusion an instruction calls
_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s+\(.*\{$")
_HLO_CALLS = re.compile(r"calls=%([\w.-]+)")
# ops that name a buffer without moving its bytes
_ALIASES = {"parameter", "get-tuple-element", "tuple", "bitcast"}
# XLA's memory-space assignment moves a buffer that fits on chip into
# VMEM and back with async slices and copies (joined by a ConcatBitcast).
# A 4-layer pool at these widths fits; at serving sizes (GBs) none does.
# They are placement, not ops of the program: a slice, cast or copy the
# program itself makes still shows as its own op, in any memory space.
_PLACEMENT = {"slice-start", "slice-done", "copy-start", "copy-done"}


def _pool_sized_ops(text, shapes, dtypes):
    """(name, opcode) of each instruction of an optimised HLO module whose
    result has one of ``shapes`` in one of ``dtypes``, other than the
    KV scatter (a ``scatter``, or a fusion rooted in one), the Pallas
    kernel's ``tpu_custom_call``, ops that only alias a buffer and the
    compiler's moves between HBM and VMEM."""
    roots, comp = {}, None
    for line in text.splitlines():
        head = _HLO_COMP.match(line)
        if head:
            comp = head.group(1)
        elif line.lstrip().startswith("ROOT"):
            op = _HLO_OP.match(line)
            if op:
                roots[comp] = op.group(4)
    found = []
    for line in text.splitlines():
        op = _HLO_OP.match(line)
        if not op:
            continue
        name, dt, dims, opcode = op.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        if shape not in shapes or dt not in dtypes:
            continue
        if opcode in _ALIASES or opcode in _PLACEMENT:
            continue
        calls = _HLO_CALLS.search(line)
        if opcode == "scatter" or (
            opcode == "fusion" and calls and roots.get(calls.group(1))
            == "scatter"
        ):
            continue
        if opcode == "custom-call" and re.search(
            r'custom_call_target="(tpu_custom_call|ConcatBitcast)"', line
        ):
            continue
        found.append((name, opcode))
    return found


@pytest.mark.parametrize("kv", ["same", "int8"])
def test_serve_step_reads_and_writes_the_pool_in_place(
    one_chip, monkeypatch, kv
):
    """The paged serve step at stablelm-3b widths (4 layers), jitted as
    the engine jits it (cache donated), for a described v5e.  The step
    writes this token's K/V rows into the stacked pool and the decode
    kernel reads its pages there: no op but the KV scatter and the
    kernel has a result the size of one layer's pool, in the pool's dtype
    or in f32 (no slice, cast or re-stack of a layer), nor of the whole
    K or V stack, and the step's temporaries stay below one layer's pool.

    The int8 scale planes, ``(…, pages, 16, 32)`` f32, are the exception
    at the stack's size: the TPU's default layout puts their page axis
    minor, so the step converts each plane to the row-major layout its
    scatter and kernel need on entry and back on exit (two ``copy`` ops
    a plane).  A layout pinned by the program would be lost by JAX's
    persistent compilation cache, which reloads such an executable with
    default layouts."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch import specs as SP

    monkeypatch.setattr(ops, "tpu_kernels", lambda: True)
    nl = 4
    mc = dataclasses.replace(
        get_config("stablelm-3b"), n_layers=nl, kv_cache_dtype=kv,
    )
    b, w = 4, 1024 // BS
    on = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree,
    )
    cache = on(SP.paged_decode_cache_specs(mc, b, PAGES, BS))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    t0 = time.perf_counter()
    compiled = jax.jit(
        SP.make_paged_serve_step(mc), donate_argnums=(1,)
    ).lower(
        on(SP.params_specs(mc)), cache,
        sds((b, w), jnp.int32), sds((b,), jnp.int32),
        sds((b, 2), jnp.uint32), sds((b,), jnp.int32),
    ).compile()
    assert time.perf_counter() - t0 < COMPILE_S
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layers, stacks, planes = set(), set(), set()
    for n in SP.PAGE_POOL_LEAVES:
        if n in cache:
            layer = cache[n].shape[2:]          # (P, bs, Hkv[, Dp])
            whole = {(nl, 1) + layer, (nl * PAGES,) + layer[1:]}
            layers.add(layer)
            (stacks if n in ("k_pages", "v_pages") else planes).update(whole)
    pool_dt = "s8" if kv == "int8" else "bf16"
    assert _pool_sized_ops(text, layers | stacks, {pool_dt, "f32"}) == []
    moves = _pool_sized_ops(text, planes, {"f32"})
    assert [op for _, op in moves] == ["copy"] * len(moves)
    assert len(moves) <= 4
    itemsize = jnp.dtype(cache["k_pages"].dtype).itemsize
    one_layer = PAGES * BS * HKV * DH * itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer
