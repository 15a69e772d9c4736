"""Smoke run of the serving main path on a TPU chip.

Serves stablelm-3b at its published widths (32 layers, d_model 2560,
32 heads of 80, d_ff 6912, vocab 50304) with seeded random bf16 weights,
through ``ServingEngine`` on the paged KV pool — the calls
``repro.launch.serve`` makes — and checks the results:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a (data=1, model=4) mesh vs one chip

One chip: each Pallas kernel of the path is compared with its oracle at
stablelm widths, the lowered serve and prefill steps are checked to hold
the kernels, and the engine serves a few requests in two configurations:
(a) a bf16 pool with greedy sampling, chunked prefill of 64 tokens and a
partial prefix hit, repeated with prefix sharing off (the streams must be
byte-identical); (b) an int8 pool with the WTA sampler.  Every request
must end on EOS or its budget.

Four chips: run (a) on a (1, 4) mesh, where each chip holds a quarter of
the pool (8 of the 32 KV heads), and on one chip; the greedy streams must
be byte-identical.  The kernels gather their per-head readouts before the
output projection, so no contraction is split across chips.

Each phase prints its wall time, compile time and compile count, and the
device's peak bytes.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import get_config  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.paged_attention import paged_attention_pallas  # noqa: E402
from repro.kernels.prefill_attention import (  # noqa: E402
    paged_prefill_attention_pallas,
)
from repro.kernels.stoch_round import stoch_round_pallas  # noqa: E402
from repro.launch import specs as SP  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import device_line, init_params  # noqa: E402
from repro.serving import ServeConfig, ServingEngine  # noqa: E402

ARCH = "stablelm-3b"
SLOTS, MAX_LEN, NEW_TOKENS, CHUNK, BLOCK = 4, 1024, 16, 64, 16
PAGES = SLOTS * (MAX_LEN // BLOCK) + 1   # the engine's default pool
# Kernel vs oracle.  q and the bf16 pages are bf16-exact and int8 codes
# are exact in bf16, so scores agree up to f32 summation order.  The
# probability-weighted sum of values may run as one bf16 MXU pass, which
# rounds each probability to 8 mantissa bits (relative 2^-9); the readout
# is a convex combination of values of magnitude below ~4, so it may move
# by 2^-9 * 4 ~ 8e-3.  The oracle runs at "highest" matmul precision.
ATTN_ATOL = 1e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_AND_LOWER = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Compiles and compile seconds, from JAX's monitoring events.  A
    backend-compile event covers a program compiled or loaded from the
    persistent cache; ``compiles`` counts only the former."""

    def __init__(self):
        self.requests = 0
        self.cache_hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits

    def _duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.requests += 1
            self.secs += secs
        elif event in _TRACE_AND_LOWER:
            self.secs += secs

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def peak_bytes() -> int:
    return max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    )


@contextlib.contextmanager
def phase(name: str, log: CompileLog):
    """Prints the phase's wall time (its work ends on a host sync),
    compile time and count, and the device's peak bytes so far."""
    n0, h0, s0 = log.compiles, log.cache_hits, log.secs
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    print(
        f"phase={name} wall_s={wall:.3f} compile_s={log.secs - s0:.3f} "
        f"compiles={log.compiles - n0} cache_hits={log.cache_hits - h0} "
        f"peak_bytes_in_use={peak_bytes()}",
        flush=True,
    )


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------


def _attn_inputs(key, cfg, lead: tuple, int8: bool):
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 5)
    bf16_exact = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    q = bf16_exact(jax.random.normal(ks[0], lead + (h, dh)))
    shape = (PAGES, BLOCK, hkv, dh)
    if int8:
        kp = jax.random.randint(ks[1], shape, -127, 128).astype(jnp.int8)
        vp = jax.random.randint(ks[2], shape, -127, 128).astype(jnp.int8)
        scales = dict(
            k_scale=jnp.abs(jax.random.normal(ks[3], shape[:3])) + 0.1,
            v_scale=jnp.abs(jax.random.normal(ks[4], shape[:3])) + 0.1,
        )
    else:
        kp = jax.random.normal(ks[1], shape).astype(jnp.bfloat16)
        vp = jax.random.normal(ks[2], shape).astype(jnp.bfloat16)
        scales = {}
    return q, kp, vp, scales


def _agree(name: str, got, want, atol: float) -> None:
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    print(f"kernel={name} max_abs_err={err:.3e} atol={atol:.0e}", flush=True)
    check(np.isfinite(err) and err <= atol, f"{name}: |err| {err} > {atol}")


def check_kernels(cfg, seed: int) -> None:
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    w = MAX_LEN // BLOCK
    rng = np.random.default_rng(seed)
    for i, int8 in enumerate((False, True)):
        tag = "int8" if int8 else "bf16"
        # decode: one query per slot over a 64-page table of random pages
        q, kp, vp, sc = _attn_inputs(keys[i], cfg, (SLOTS,), int8)
        table = jnp.asarray(
            rng.permutation(PAGES - 1)[: SLOTS * w].reshape(SLOTS, w) + 1,
            jnp.int32,
        )
        pos = jnp.asarray(rng.integers(0, w * BLOCK, SLOTS), jnp.int32)
        got = jax.jit(paged_attention_pallas)(q, kp, vp, table, pos, **sc)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.paged_attention_ref)(
                q, kp, vp, table, pos, **sc
            )
        _agree(f"paged_decode_{tag}", got, want, ATTN_ATOL)
        # chunked prefill: one request's chunk at a block-aligned resume
        # point, at the engine's chunk size and at two query tiles
        for s, q0 in ((CHUNK, 2 * CHUNK), (256, 3 * CHUNK)):
            q, kp, vp, sc = _attn_inputs(keys[2 + i], cfg, (s,), int8)
            row = table[0]
            got = jax.jit(paged_prefill_attention_pallas)(
                q, kp, vp, row, jnp.int32(q0), **sc
            )
            with jax.default_matmul_precision("highest"):
                want = jax.jit(ref.prefill_attention_ref)(
                    q, kp, vp, row, jnp.int32(q0), **sc
                )
            _agree(f"chunked_prefill_{tag}_s{s}", got, want, ATTN_ATOL)
    # stochastic rounding onto the int8 grid: bit-exact by design (the
    # kernel and the oracle share the counter PRNG and the f32 op order)
    x = jax.random.uniform(keys[6], (512, 512), minval=-130.0, maxval=130.0)
    seed_u = jnp.uint32(0x5EED + seed)
    seed_i = jax.lax.bitcast_convert_type(seed_u, jnp.int32).reshape(1)
    kw = dict(step=1.0, lo=-127.0, hi=127.0)
    rounded = jax.jit(lambda a, s: stoch_round_pallas(a, s, **kw))
    oracle = jax.jit(lambda a, s: ref.stoch_round_ref(a, s, **kw))
    got = np.asarray(rounded(x, seed_i))
    want = np.asarray(oracle(x, seed_u))
    n_diff = int(np.sum(got != want))
    print(f"kernel=stoch_round mismatches={n_diff} (bit-exact)", flush=True)
    check(n_diff == 0, f"stoch_round: {n_diff} elements differ")


def check_lowered(cfg) -> None:
    """The serve step and the prefill chunk, lowered as the engine jits
    them, hold the Pallas kernels (no jnp stand-in on the chip)."""
    sds = jax.ShapeDtypeStruct
    params = SP.params_specs(cfg)
    cache = SP.paged_decode_cache_specs(cfg, SLOTS, PAGES, BLOCK)
    w = MAX_LEN // BLOCK
    step = jax.jit(SP.make_paged_serve_step(cfg)).lower(
        params, cache, sds((SLOTS, w), jnp.int32), sds((SLOTS,), jnp.int32),
        sds((SLOTS, 2), jnp.uint32), sds((SLOTS,), jnp.int32),
    )
    state = jax.eval_shape(lambda: SP.init_prefill_state(cfg))
    args = [
        params, cache, state, sds((1, CHUNK), jnp.int32),
        sds((w,), jnp.int32), sds((), jnp.int32),
    ]
    if cfg.kv_cache_dtype == "int8":
        args.append(sds((CHUNK // BLOCK,), jnp.uint32))
    prefill = jax.jit(
        SP.make_paged_suffix_prefill(cfg), static_argnames=("bucket",)
    ).lower(*args, bucket=MAX_LEN)
    for name, low in (("serve_step", step), ("prefill_chunk", prefill)):
        n = low.as_text().count("tpu_custom_call")
        print(
            f"lowered={name} kv={cfg.kv_cache_dtype} tpu_custom_calls={n}",
            flush=True,
        )
        check(n > 0, f"{name}: no Pallas kernel in the lowered program")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_traffic(vocab: int, seed: int) -> list[list[int]]:
    """Prompts of 16-300 tokens; two 256-token prompts share a 128-token
    prefix (same length, so the same left padding: a partial hit)."""
    rng = np.random.default_rng(seed)
    tok = lambda n: rng.integers(0, vocab, n).tolist()
    prefix = tok(128)
    return [tok(16), prefix + tok(128), prefix + tok(128), tok(300)]


def serve(params, cfg, traffic, **serve_kw):
    """Serves ``traffic`` to completion; returns (streams, engine)."""
    eng = ServingEngine(
        params, cfg,
        ServeConfig(
            max_batch=SLOTS, max_new_tokens=NEW_TOKENS, max_len=MAX_LEN,
            kv_block_size=BLOCK, prefill_chunk=CHUNK, **serve_kw,
        ),
    )
    rids = [eng.submit(p) for p in traffic]
    eng.run()
    reqs = [eng.sched.request(r) for r in rids]
    reasons = [r.done_reason for r in reqs]
    m = eng.metrics()
    print(
        f"served={len(reqs)} reasons={reasons} tokens={m.total_tokens} "
        f"prefix_partial_hits={m.prefix_partial_hits} "
        f"prefill_tokens_saved={m.prefill_tokens_saved} "
        f"decode_steps={m.decode_steps} {m.row()}",
        flush=True,
    )
    check(
        all(r in ("eos", "length") for r in reasons),
        f"a request ended on {reasons}",
    )
    streams = [list(r.output) for r in reqs]
    check(
        all(len(s) == NEW_TOKENS for s in streams),
        f"stream lengths {[len(s) for s in streams]}",
    )
    check(
        all(0 <= t < cfg.vocab for s in streams for t in s),
        "token id out of range",
    )
    return streams, eng


def one_chip(cfg, seed: int, log: CompileLog) -> None:
    with phase("kernels_vs_oracles", log):
        check_kernels(cfg, seed)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8", wta_head=True)
    with phase("lowered_steps", log):
        check_lowered(cfg)
        check_lowered(cfg8)
    with phase("init_params", log):
        params = jax.block_until_ready(init_params(cfg, seed))
    traffic = make_traffic(cfg.vocab, seed)
    with phase("serve_bf16_greedy", log):
        shared, eng = serve(params, cfg, traffic)
        check(
            eng.metrics().prefix_partial_hits >= 1,
            "no partial prefix hit: the suffix prefill path did not run",
        )
    del eng
    gc.collect()
    with phase("serve_bf16_greedy_no_sharing", log):
        plain, eng = serve(params, cfg, traffic, enable_prefix_sharing=False)
    del eng
    gc.collect()
    check(shared == plain, "prefix sharing changed the greedy streams")
    print("greedy streams identical with prefix sharing on and off",
          flush=True)
    with phase("serve_int8_wta", log):
        _, eng = serve(params, cfg8, traffic)
    del eng


def four_chips(cfg, seed: int, log: CompileLog) -> None:
    check(len(jax.devices()) >= 4, f"needs 4 chips, have {len(jax.devices())}")
    traffic = make_traffic(cfg.vocab, seed)
    with phase("serve_bf16_one_chip", log):
        params = jax.block_until_ready(init_params(cfg, seed))
        single, eng = serve(params, cfg, traffic)
    del eng, params
    gc.collect()
    mesh = make_host_mesh(model=4, data=1)
    with phase("serve_bf16_mesh_1x4", log):
        # params built replicated on the mesh in one program, not copied
        # from one chip (two copies would crowd its memory)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        params = jax.block_until_ready(
            jax.jit(lambda: init_params(cfg, seed), out_shardings=rep)()
        )
        sharded, eng = serve(params, cfg, traffic, mesh=mesh)
    hkv = cfg.n_kv_heads
    quarters = [(i * hkv // 4, (i + 1) * hkv // 4) for i in range(4)]
    for leaf in ("k_pages", "v_pages"):
        arr = eng._cache[leaf]   # (units, n_attn, pages, block, Hkv, Dh)
        shards = arr.addressable_shards
        heads = sorted(s.index[4].indices(hkv)[:2] for s in shards)
        print(f"pool={leaf} shard_heads={heads}", flush=True)
        check(
            len(shards) == 4
            and all(s.data.shape[4] == hkv // 4 for s in shards)
            and heads == quarters,
            f"{leaf}: not split into quarters over the KV heads",
        )
    check(sharded == single, "the (1, 4) mesh changed the greedy streams")
    print("greedy streams identical on the (1, 4) mesh and on one chip",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4)-mesh phase and its one-chip "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the traffic and the kernel "
                         "inputs")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {dev.platform}")
    print(device_line(), flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(cfg, args.seed, log)
    print(f"total_wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))


if __name__ == "__main__":
    main()
