"""ShapeDtypeStruct stand-ins for every model input — the dry-run's inputs.

`input_specs(arch, shape)` returns weak-type-correct, shardable specs with
no device allocation, for the step function the shape's kind lowers:
  train   → train_step(state, batch)
  prefill → prefill_step(params, batch)
  decode  → serve_step(params, cache, token)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import parallel
from repro.configs import SHAPES, get_config
from repro.configs.shapes import ShapeSpec
from repro.models import ModelConfig, get_model_fns
from repro.models import transformer as TF
from repro.models import encdec as ED
from repro.train import TrainConfig, TrainState, init_train_state

WHISPER_DEC_PROMPT = 448  # decoder prompt length for encdec prefill cells

_i32 = jnp.int32


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def train_batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    b, s = shape.global_batch, shape.seq_len
    out = {"tokens": _sds((b, s), _i32), "labels": _sds((b, s), _i32)}
    if cfg.family == "vlm":
        out["patches"] = _sds((b, cfg.n_patches, cfg.d_model), cfg.dtype)
    if cfg.family == "encdec":
        out["frames"] = _sds((b, s, cfg.d_model), cfg.dtype)
    return out


def prefill_batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        # prefill_32k for whisper = encode S frames + short decoder prompt
        return {
            "frames": _sds((b, s, cfg.d_model), cfg.dtype),
            "tokens": _sds((b, WHISPER_DEC_PROMPT), _i32),
        }
    out = {"tokens": _sds((b, s), _i32)}
    if cfg.family == "vlm":
        out["patches"] = _sds((b, cfg.n_patches, cfg.d_model), cfg.dtype)
    return out


def decode_cache_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return jax.eval_shape(
            lambda: ED.init_encdec_cache(cfg, b, s, cfg.enc_seq)
        )
    return jax.eval_shape(lambda: TF.init_decode_cache(cfg, b, s))


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Concrete (allocated) decode cache for a live serving batch."""
    if cfg.family == "encdec":
        return ED.init_encdec_cache(cfg, batch, max_len, cfg.enc_seq)
    return TF.init_decode_cache(cfg, batch, max_len)


def init_paged_decode_cache(
    cfg: ModelConfig, batch: int, n_pages: int, block_size: int
) -> dict:
    """Concrete paged decode cache (shared block pool + per-slot states)."""
    if cfg.family == "encdec":
        raise ValueError("paged KV cache is token-LM only (no encdec)")
    return TF.init_paged_decode_cache(cfg, batch, n_pages, block_size)


def paged_decode_cache_specs(
    cfg: ModelConfig, batch: int, n_pages: int, block_size: int
) -> dict:
    return jax.eval_shape(
        lambda: init_paged_decode_cache(cfg, batch, n_pages, block_size)
    )


def cache_batch_axis(cfg: ModelConfig, leaf_name: str) -> int:
    """Which axis of a decode-cache leaf is the request/slot axis.

    ``pos`` is (B,); LM-family leaves are (n_units, n_per_unit, B, ...);
    encdec leaves are (n_layers, B, ...) — the layout contract that
    slot-addressable insertion below relies on.  Covered by
    tests/test_specs.py so cache-layout refactors fail loudly.
    """
    if leaf_name == "pos":
        return 0
    return 1 if cfg.family == "encdec" else 2


def make_cache_insert(cfg: ModelConfig):
    """Insert one request's prefill cache into a live batch cache at ``slot``.

    (batch_cache, one_cache(B=1), slot int32) -> batch_cache.  The slot index
    is a traced scalar, so one jit of this function serves every slot of a
    live batch without recompiling — the continuous-batching refill path.
    """

    def insert(batch_cache: dict, one_cache: dict, slot) -> dict:
        out = {}
        for name, leaf in batch_cache.items():
            upd = one_cache[name].astype(leaf.dtype)
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                leaf, upd, slot, axis=cache_batch_axis(cfg, name)
            )
        return out

    return insert


def init_prefill_state(cfg: ModelConfig) -> dict:
    """Zeroed B=1 state leaves entering a chunked paged prefill."""
    return TF.init_prefill_state(cfg)


def make_paged_suffix_prefill(cfg: ModelConfig):
    """One suffix chunk of a resumable, chunked paged prefill.

    (params, paged_cache, state{B=1}, tokens (1, c) int32, table_row
    (Wp,) int32, q0 int32 [, quant_seeds (nbc,) uint32], *, bucket) →
    (paged_cache, state', last-token logits (1, V)).

    THE paged prefill entry point — it subsumes the old monolithic
    per-request prefill + block scatter: a cold admission runs its whole
    bucket as chunks from zeroed state (:func:`init_prefill_state`), a
    partial-prefix hit runs only the suffix (``q0 > 0``) attending into
    the shared pages already mapped in ``table_row``, and the engine
    interleaves at most ``ServeConfig.prefill_chunk`` tokens per tick
    between decode steps.  Only the page-pool leaves of ``paged_cache``
    are touched — the per-slot leaves ride along untouched, so a chunked
    prefill in flight is never corrupted by the batched decode steps
    running for the OTHER slots (the engine threads ``state`` host-side
    and writes it at the slot once, on completion).

    With ``all_logits=True`` (static, default off) the logits output is
    ``(1, c, V)`` — one next-token row per chunk position, the
    multi-token-logits variant that makes a k-token chunk a one-call
    verifier over k decode positions (the cross-path oracle the
    speculative-decoding tests pin against the decode-cell verifier).

    Compile discipline: ``bucket`` is the only routinely-varying static
    argument (the attention window slice), so compiles are one per
    (bucket, chunk shape) pair; page ids, the start position, and the
    int8 rounding seeds are all traced.  int8 pools quantize each chunk block under its
    content-derived seed (chain hash → uint32, folded with the unit and
    sublayer index inside) — the canonical-seed contract that keeps
    shared int8 blocks bit-identical across writers.
    """
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")

    def suffix_chunk(
        params, cache: dict, state: dict, tokens, table_row, q0,
        quant_seeds=None, *, bucket: int, all_logits: bool = False,
    ):
        pool = {n: cache[n] for n in PAGE_POOL_LEAVES if n in cache}
        new_pool, new_state, logits = TF.lm_prefill_chunk(
            params, tokens, cfg, pool, state, table_row, q0, bucket,
            quant_seeds, all_logits=all_logits,
        )
        out = dict(cache)
        out.update(new_pool)
        return out, new_state, logits

    return suffix_chunk


# page-pool cache leaves (vs the dense per-slot leaves) — the split that
# prefix sharing relies on: pool leaves are mapped through block tables and
# may be shared across slots, per-slot leaves are always private
PAGE_POOL_LEAVES = (
    "k_pages", "v_pages", "k_scale_pages", "v_scale_pages"
)


def _spec_state_leaves(cache: dict) -> dict:
    """The per-slot leaves a speculative round snapshots / rolls back:
    everything except the shared page pool and the engine-wide int8
    ``quant_step`` counter (rewinding that would replay rounding draws)."""
    return {
        n: v for n, v in cache.items()
        if n not in PAGE_POOL_LEAVES and n != "quant_step"
    }


def make_paged_spec_round(cfg: ModelConfig, k: int):
    """One fused draft-k → verify-k speculative round over a paged cache.

    (params, cache, table (B, W), token (B,), keys (B, 2), steps (B,)) →
    (cache, dtoks (B, k), doks (B, k), vtoks (B, k), voks (B, k),
    vstates {state leaf: (k, ...)}).

    Draft phase: a ``lax.scan`` of ``k`` chained batched decode steps —
    each scan cell IS :func:`TF.lm_decode_step` + :func:`sample_tokens`
    with the slot's own ``(key, steps + j)``, so the drafted chain is
    bit-identical to ``k`` plain engine ticks (the greedy byte-identity
    contract holds by construction, not by tolerance).  Drafted K/V lands
    in the slots' reserved pages as it would under plain decode; the int8
    pool's ``quant_step`` advances one per draft step, exactly like the
    plain path.

    Verify phase: a read-only re-decode of the whole drafted run in the
    SAME dispatch — every (slot, step) pair verified in parallel as ONE
    ``k·B``-row batched decode call (the multi-token-logits pass).  Row
    ``(j, s)`` consumes input ``j`` of ``[token, dtoks[:-1]]`` for slot
    ``s`` at absolute position ``q0_s + j`` with ``kv_write=False``:
    identical per-row math attending the pages the draft just wrote
    (per-row decode logits are batch-size-invariant bitwise — the same
    property the batch-composition-invariance contract pins), resampled
    with the same ``(key, steps + j)`` the draft used.  In a fault-free
    run ``vtoks == dtoks`` bitwise and every draft accepts; when a draft
    diverged (noisy analog drafter, injected fault), the first mismatch
    index is simultaneously the rejection point AND the corrected
    resample.  ``vstates[leaf][j]`` — the per-slot state after consuming
    input ``j``, emitted by the draft scan (the verifier consumes the
    drafts themselves, so draft and verify states coincide bitwise on
    every row, matched or not) — is the rollback target for
    :func:`make_spec_rollback`.

    The wall-clock shape is the point: ``k`` sequential unit evals
    (draft, irreducibly autoregressive) plus ONE parallel verify eval
    per round, against ``k`` sequential evals plus ``k`` full host
    round-trips for the plain path — per-tick host overhead amortizes
    over the accepted run.

    ``doks``/``voks`` are the per-step finite-logits flags (the NaN guard
    at draft depth): the engine truncates a slot's usable drafts at the
    first non-finite draft step.  One compile per (window W, k) pair —
    same power-of-two window bucketing as the plain serve step.
    """
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")
    if k < 1:
        raise ValueError(f"speculate_k must be >= 1, got {k}")

    def spec_round(params, cache, table, token, keys, steps):
        snap = _spec_state_leaves(cache)

        def draft(carry, j):
            cch, tok = carry
            cch, logits = TF.lm_decode_step(params, cch, tok, cfg, table)
            nxt = sample_tokens(cfg, logits, keys, steps + j)
            ok = jnp.isfinite(logits.astype(jnp.float32)).all(axis=-1)
            return (cch, nxt), (nxt, ok, _spec_state_leaves(cch))

        (cache, _), (dtoks, doks, vstates) = jax.lax.scan(
            draft, (cache, token), jnp.arange(k, dtype=_i32)
        )

        # expanded-batch verify view: row (j, s) = slot s about to consume
        # input j, so its state is S_j (pre-draft snapshot for j=0, the
        # draft scan's post-step state otherwise)
        view = {n: cache[n] for n in cache if n not in vstates}
        for name, st in vstates.items():
            ax = cache_batch_axis(cfg, name)
            pre = jnp.concatenate([snap[name][None], st[:-1]], axis=0)
            arr = jnp.moveaxis(pre, 0, ax)  # (..., k, B, ...)
            view[name] = arr.reshape(
                arr.shape[:ax] + (-1,) + arr.shape[ax + 2:]
            )
        inputs = jnp.concatenate([token[None], dtoks[:-1]], axis=0)  # (k, B)
        xkeys = jnp.tile(keys, (k, 1))
        xsteps = (
            jnp.tile(steps, (k,))
            + jnp.repeat(jnp.arange(k, dtype=steps.dtype), steps.shape[0])
        )
        _, logits = TF.lm_decode_step(
            params, view, inputs.reshape(-1), cfg,
            jnp.tile(table, (k, 1)), kv_write=False,
        )
        vtoks = sample_tokens(cfg, logits, xkeys, xsteps).reshape(inputs.shape)
        voks = (
            jnp.isfinite(logits.astype(jnp.float32))
            .all(axis=-1).reshape(inputs.shape)
        )
        return cache, dtoks.T, doks.T, vtoks.T, voks.T, vstates

    return spec_round


def make_spec_rollback(cfg: ModelConfig):
    """Roll ONE slot back to the post-acceptance state of a rejected round.

    (paged_cache, vstates {leaf: (k, ...)}, idx int32, slot int32) →
    paged_cache.  ``vstates[leaf][idx]`` is the round's per-slot state
    after consuming the last accepted input (the verify inputs ARE the
    drafts, so the draft scan's post-step states are bitwise the states a
    plain engine would hold at that point; ``pos`` included, so the
    slot's position rewinds with its recurrent/SSM state in one shot).  Drafted
    K/V beyond the rollback position stays in the pages as dead rows:
    positions ≥ ``pos`` are masked to exact-zero attention weight and the
    rows are overwritten verbatim when decode reaches them again.  Both
    ``idx`` and ``slot`` are traced — ONE compile per engine lifetime
    (shapes are fixed by ``k``).
    """
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")

    def rollback(cache: dict, vstates: dict, idx, slot) -> dict:
        out = dict(cache)
        for name, st in vstates.items():
            leaf = cache[name]
            ax = cache_batch_axis(cfg, name)
            row = jax.lax.dynamic_index_in_dim(st, idx, axis=0, keepdims=False)
            row = jax.lax.dynamic_slice_in_dim(row, slot, 1, axis=ax)
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                leaf, row.astype(leaf.dtype), slot, axis=ax
            )
        return out

    return rollback


def make_paged_state_insert(cfg: ModelConfig):
    """Insert only the dense per-slot leaves of a one-request cache.

    (paged_cache, state_leaves{B=1}, slot int32) → paged_cache.  The
    prefix-sharing full-hit admission path: when every block covering a
    request's padded prompt is already resident (matched through the
    allocator's content-hash index), the engine maps the shared pages into
    the slot's table row and skips the prefill — but the per-slot leaves
    (``pos``, recurrent/SSM states) still need the stored values from the
    original prefill.  ``state_leaves`` holds exactly those leaves (no
    ``k``/``v``); their shapes are bucket-independent, so this compiles
    ONCE for the engine's whole lifetime.
    """

    def insert(batch_cache: dict, state_leaves: dict, slot) -> dict:
        out = dict(batch_cache)
        for name, upd in state_leaves.items():
            leaf = batch_cache[name]
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                leaf, upd.astype(leaf.dtype), slot,
                axis=cache_batch_axis(cfg, name),
            )
        return out

    return insert


def make_page_copy(cfg: ModelConfig):
    """Copy one pool page onto another across every page-pool leaf.

    (paged_cache, src int32, dst int32) → paged_cache.  The device half of
    a copy-on-write fork: the engine repoints the writer's table row at
    ``dst`` and the batched decode step then writes there, while the other
    owners keep reading the pristine ``src``.  int8 pools copy the scale
    planes alongside the code pages.  Page ids are traced — one compile
    serves every fork.
    """

    def copy(cache: dict, src, dst) -> dict:
        out = dict(cache)
        for name in PAGE_POOL_LEAVES:
            if name in cache:
                leaf = cache[name]  # (nu, n_attn, P, bs, ...)
                out[name] = leaf.at[:, :, dst].set(leaf[:, :, src])
        return out

    return copy


def paged_cache_shardings(
    cfg: ModelConfig, mesh, batch: int, n_pages: int, block_size: int
) -> dict:
    """NamedSharding per paged-cache leaf under ``mesh`` (page axis over
    "data", kv_heads over "model", per-slot leaves batch over "data" —
    every rule divisibility-guarded; see sharding.cache_partition_specs)."""
    from repro.launch import sharding as SH

    sds = paged_decode_cache_specs(cfg, batch, n_pages, block_size)
    return SH.cache_shardings(sds, mesh, cfg, batch)


def on_kernel_mesh(fn, mesh):
    """``fn`` traced with ``mesh`` installed as the kernel mesh, so each
    Pallas kernel it reaches runs under ``shard_map``
    (:func:`repro.kernels.ops.shard_over_kv_heads`)."""

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with parallel.kernel_mesh(mesh):
            return fn(*args, **kwargs)

    return run


def make_sharded_paged_entry_points(
    cfg: ModelConfig, mesh, *, batch: int, n_pages: int, block_size: int,
    speculate_k: int = 0, n_redundant: int = 1, sat_threshold: float = 1e6,
    entropy_floor: float = 0.0,
) -> dict:
    """The paged serving entry points, jitted mesh-aware.

    Each of the four device entry points the paged engine drives —
    :func:`make_paged_serve_step`, :func:`make_paged_suffix_prefill`,
    :func:`make_paged_state_insert`, :func:`make_page_copy` — gains
    ``in_shardings``/``out_shardings`` (``jax.jit`` + ``NamedSharding``)
    over a ``(data, model)`` mesh:

      * the paged pool shards its PAGE axis over ``data`` and ``kv_heads``
        over ``model`` (divisibility-guarded — a non-divisible dim
        replicates), so pool capacity scales with the data axis at
        constant per-device memory;
      * per-slot decode inputs — block table ``(B, W)``, tokens ``(B,)``,
        per-slot keys ``(B, 2)``, step counters ``(B,)`` — shard their
        slot axis over ``data`` (guarded on ``B``);
      * params are REPLICATED across the serving mesh: decode is
        memory-bound on the KV pool, and replicated weights keep every
        reduction order identical to the single-device engine (the
        byte-identity contract on a 1×1 mesh, token identity on wider
        meshes);
      * B=1 prefill-side arguments (suffix-chunk tokens, threaded state,
        table row, q0, quant seeds) and the chunk logits are replicated —
        one request's chunk is not worth sharding.

    The block table, ``BlockAllocator``, and the content-hash prefix
    index stay HOST-GLOBAL: any slot may map any page, so prefix sharing
    and copy-on-write work across shards unchanged; off TPU, GSPMD
    inserts the cross-shard page gathers of the jnp attention.  On TPU
    the steps trace under :func:`on_kernel_mesh`: each Pallas kernel
    runs in ``shard_map`` over the model axis (its chip's KV heads) and
    all-gathers its readout, and ``ServeConfig.validate`` refuses a data
    axis > 1 (the kernels read only their own chip's pages).

    Donation and compile discipline match the unsharded entry points
    (cache donated everywhere; ``bucket`` the only static argument of the
    suffix prefill), so the engine's recompile guards hold verbatim.

    Returns ``{"serve_step", "suffix_prefill", "state_insert",
    "page_copy", "page_spill", "page_restore", "state_gather",
    "shardings"}`` where ``shardings`` maps
    ``params/cache/table/slot_vec/slot_keys/replicated`` to the
    NamedShardings used — the engine places its host→device transfers
    (``jax.device_put``) with exactly these.  With ``speculate_k > 0``
    the dict also carries ``spec_round`` / ``spec_rollback``
    (:func:`make_paged_spec_round` / :func:`make_spec_rollback`): the
    round's per-slot inputs shard like the serve step's, the stacked
    per-step verifier states shard like their cache leaves with a
    replicated leading step axis, and the rollback donates the cache like
    every other admission-time mutation.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch import sharding as SH

    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")
    cache_sh = paged_cache_shardings(cfg, mesh, batch, n_pages, block_size)
    rep = NamedSharding(mesh, PartitionSpec())
    params_sh = jax.tree_util.tree_map(lambda _: rep, params_specs(cfg))
    bax = SH.batch_axes(mesh, batch)
    vec_sh = NamedSharding(mesh, PartitionSpec(bax))
    mat_sh = NamedSharding(mesh, PartitionSpec(bax, None))
    serve_step = jax.jit(
        on_kernel_mesh(
            make_paged_serve_step(
                cfg, n_redundant=n_redundant, sat_threshold=sat_threshold,
                entropy_floor=entropy_floor,
            ),
            mesh,
        ),
        donate_argnums=(1,),
        in_shardings=(params_sh, cache_sh, mat_sh, vec_sh, mat_sh, vec_sh),
        out_shardings=(cache_sh, vec_sh, vec_sh),
    )
    # (params, cache, state, tokens, table_row, q0[, quant_seeds])
    prefill_in = [params_sh, cache_sh, rep, rep, rep, rep]
    if cfg.kv_cache_dtype == "int8":
        prefill_in.append(rep)
    # pjit rejects kwargs once in_shardings is given, so the static
    # ``bucket`` rides as the LAST positional arg here; the thin kwarg
    # shim below keeps the engine's ``(*args, bucket=...)`` call site
    # layout-agnostic.  in_shardings covers only the dynamic args.
    base_prefill = on_kernel_mesh(make_paged_suffix_prefill(cfg), mesh)

    def _prefill_pos(*args):
        return base_prefill(*args[:-1], bucket=args[-1])

    prefill_jit = jax.jit(
        _prefill_pos,
        static_argnums=(len(prefill_in),),
        donate_argnums=(1,),
        in_shardings=tuple(prefill_in),
        out_shardings=(cache_sh, rep, rep),
    )

    def suffix_prefill(*args, bucket):
        return prefill_jit(*args, bucket)

    suffix_prefill._cache_size = prefill_jit._cache_size
    state_insert = jax.jit(
        make_paged_state_insert(cfg),
        donate_argnums=(0,),
        in_shardings=(cache_sh, rep, rep),
        out_shardings=cache_sh,
    )
    page_copy = jax.jit(
        make_page_copy(cfg),
        donate_argnums=(0,),
        in_shardings=(cache_sh, rep, rep),
        out_shardings=cache_sh,
    )
    # spill/restore/state-gather: the preemption path.  The spill gather
    # and the slot-state read produce REPLICATED payloads (they leave the
    # device for a host-side store); restore donates the cache like every
    # other admission-time mutation.
    page_spill = jax.jit(
        make_page_spill(cfg),
        in_shardings=(cache_sh, rep),
        out_shardings=rep,
    )
    page_restore = jax.jit(
        make_page_restore(cfg),
        donate_argnums=(0,),
        in_shardings=(cache_sh, rep, rep),
        out_shardings=cache_sh,
    )
    state_gather = jax.jit(
        make_slot_state_gather(cfg),
        in_shardings=(cache_sh, rep),
        out_shardings=rep,
    )
    out = {
        "serve_step": serve_step,
        "suffix_prefill": suffix_prefill,
        "state_insert": state_insert,
        "page_copy": page_copy,
        "page_spill": page_spill,
        "page_restore": page_restore,
        "state_gather": state_gather,
        "shardings": {
            "params": params_sh,
            "cache": cache_sh,
            "table": mat_sh,
            "slot_vec": vec_sh,
            "slot_keys": mat_sh,
            "replicated": rep,
        },
    }
    if speculate_k:
        sds = paged_decode_cache_specs(cfg, batch, n_pages, block_size)
        # stacked per-step verifier states: cache-leaf sharding with a
        # replicated leading step axis
        stacked_sh = {
            n: NamedSharding(
                mesh, PartitionSpec(None, *tuple(cache_sh[n].spec))
            )
            for n in sds
            if n not in PAGE_POOL_LEAVES and n != "quant_step"
        }
        out["spec_round"] = jax.jit(
            on_kernel_mesh(make_paged_spec_round(cfg, speculate_k), mesh),
            donate_argnums=(1,),
            in_shardings=(params_sh, cache_sh, mat_sh, vec_sh, mat_sh, vec_sh),
            out_shardings=(cache_sh, mat_sh, mat_sh, mat_sh, mat_sh, stacked_sh),
        )
        out["spec_rollback"] = jax.jit(
            make_spec_rollback(cfg),
            donate_argnums=(0,),
            in_shardings=(cache_sh, stacked_sh, rep, rep),
            out_shardings=cache_sh,
        )
    return out


def sample_tokens(
    cfg: ModelConfig, logits, key=None, steps=None, n_redundant: int = 1
):
    """Next-token selection shared by prefill and decode steps.

    ``logits`` is (B, V).  With ``key=None`` (or ``wta_head`` off) this is the
    digital argmax baseline.  With ``cfg.wta_head``:

      * ``key.ndim == 1`` — legacy whole-batch key: one WTA trial tensor for
        the batch (the static engine's behavior).
      * ``key.ndim == 2`` — per-slot keys (B, 2): each request votes with its
        own comparator-noise stream, so a request's sampled tokens are a
        function of (its key, its step counter, its logits) only — invariant
        to which other requests share the batch, which continuous batching
        requires.  ``steps`` (B,) int32, when given, is folded into each
        slot's key so every decode step draws fresh noise.

    The comparator operating point (threshold, noise sigma) is consulted
    from the ACTIVE device backend at trace time
    (``wta_readout_params`` — identity on healthy backends, perturbed by
    fault backends), so substrate faults reach the serving sampler.

    ``n_redundant = R > 1`` is the fault-mitigation re-read: the full WTA
    trial bank races R times (read 0 on the EXACT plain-path key, extra
    reads on a fold of the slot key by the read index) and the published
    token is the majority vote over the R reads (ties break to the lowest
    token id).  ``R = 1`` is byte-identical to the pre-knob trace.

    Its operations carry the device scope ``engine.sample``.
    """
    with jax.named_scope("engine.sample"):
        if not (cfg.wta_head and key is not None):
            return jnp.argmax(logits, axis=-1).astype(_i32)

        from repro.core import wta as W
        from repro.kernels import backend as BK

        vth0, sigma_z = BK.get_backend().wta_readout_params(
            cfg.analog.vth0, W.wta_sigma_z(cfg.analog.beta)
        )

        def counts_one(k, z):
            res = W.wta_trials(
                k,
                z.astype(jnp.float32),
                n_trials=cfg.analog.wta_trials,
                vth0=vth0,
                sigma_z=sigma_z,
            )
            return res.counts

        def sample_once(k):
            if k.ndim == 2:  # per-slot keys
                if steps is not None:
                    k = jax.vmap(jax.random.fold_in)(k, steps)
                counts = jax.vmap(counts_one)(k, logits)
            else:
                counts = counts_one(k, logits)
            return jnp.argmax(counts, axis=-1).astype(_i32)

        reads = max(int(n_redundant), 1)
        if reads == 1:
            return sample_once(key)
        votes = [sample_once(key)]
        for r in range(1, reads):
            if key.ndim == 2:
                kr = jax.vmap(jax.random.fold_in, in_axes=(0, None))(key, r)
            else:
                kr = jax.random.fold_in(key, r)
            votes.append(sample_once(kr))
        tally = jax.nn.one_hot(
            jnp.stack(votes, axis=0), logits.shape[-1], dtype=_i32
        ).sum(axis=0)
        return jnp.argmax(tally, axis=-1).astype(_i32)


def analog_call_profile(
    entry: str, *, tokens: int = 1, batch: int = 1, k: int = 0,
    redundant: int = 0,
) -> dict:
    """Analog-event multiplicities for ONE invocation of a serving entry
    point built in this module — the contract the energy accounting rides
    on (see kernels/backend.py and docs/serving.md §"Energy accounting").

    Each factory's device computation forwards a fixed number of token
    positions through the crossbar fabric; the returned dict states that
    number per kind, plus how many token-sampling decisions the call makes
    and how many of the forwarded tokens WRITE their K/V rows (int8 pools
    stochastically round exactly those):

    * ``suffix_prefill`` — one chunked-prefill step over ``tokens`` suffix
      positions (also the dense layout's monolithic prefill with
      ``tokens`` = the padded bucket).  No sampling: first-token sampling
      is the separate ``sample0`` call.
    * ``sample0`` — one first-token sampling decision from stored/terminal
      logits (prefill completion, full prefix hit, dense admission).
    * ``serve_step`` — one plain batched decode step: ``batch`` ACTIVE
      slots each forward + sample + write one token.  Padded idle slots
      compute against the trash page but serve no request; the Sim
      backend accounts logical work, which is what makes totals invariant
      to batch composition.
    * ``spec_round`` — one fused speculative round: per active slot,
      ``k`` drafted tokens (forwarded, sampled, K/V written) PLUS ``k``
      verify positions re-decoded read-only from the pre-draft snapshot
      (forwarded, resampled, ``kv_write=False`` — no rounding events).
      Rejected drafts burn this energy without emitting tokens; the bench
      reports gross vs per-published-token cost honestly.
    * page/state movement entry points (``page_copy``, ``page_spill``,
      ``page_restore``, ``state_gather``, ``state_insert``,
      ``spec_rollback``) — pure memory traffic, no crossbar events.

    ``redundant`` counts EXTRA comparator re-reads beyond the first
    (fault-mitigation majority voting): a serve step at
    ``n_redundant_reads = R`` passes ``redundant = (R-1)·batch``, each
    priced as one more per-sample comparator sweep
    (``cost_model.per_redundant_read_counts``) without adding sample
    events — the published stream is unchanged, only energy grows.
    """
    zero = dict(
        prefill=0, decode=0, draft=0, samples=0, kv_tokens=0, redundant=0
    )
    if entry == "suffix_prefill":
        return dict(zero, prefill=tokens, kv_tokens=tokens)
    if entry == "sample0":
        return dict(zero, samples=1)
    if entry == "serve_step":
        return dict(
            zero, decode=batch, samples=batch, kv_tokens=batch,
            redundant=redundant,
        )
    if entry == "spec_round":
        return dict(
            zero,
            draft=k * batch,
            decode=k * batch,
            samples=2 * k * batch,
            kv_tokens=k * batch,
        )
    if entry in (
        "page_copy", "page_spill", "page_restore", "state_gather",
        "state_insert", "spec_rollback",
    ):
        return zero
    raise ValueError(f"unknown serving entry point {entry!r}")


def params_specs(cfg: ModelConfig) -> Any:
    fns = get_model_fns(cfg)
    return jax.eval_shape(lambda k: fns.init(k, cfg), jax.random.PRNGKey(0))


def train_state_specs(cfg: ModelConfig, tcfg: TrainConfig) -> TrainState:
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg, tcfg), jax.random.PRNGKey(0)
    )


def make_serve_step(cfg: ModelConfig):
    """One decode step: (params, cache, token(B,)) -> (cache, token).

    With cfg.wta_head the next token comes from the paper's WTA stochastic
    SoftMax circuit (vote counts over noisy comparator trials) instead of a
    digital argmax — the serving-side integration of the technique.  ``key``
    may be a single PRNG key (whole-batch trials) or a (B, 2) stack of
    per-slot keys with an optional ``steps`` (B,) counter; see
    :func:`sample_tokens`."""
    fns = get_model_fns(cfg)

    def serve_step(params, cache, token, key=None, steps=None):
        cache, logits = fns.decode_step(params, cache, token, cfg)
        return cache, sample_tokens(cfg, logits, key, steps)

    return serve_step


# Per-slot logit-sanity codes emitted by the paged serve step (the third
# output).  0 is healthy; nonzero codes map to typed eviction reasons.
SANE_OK = 0
SANE_NAN = 1
SANE_SATURATED = 2
SANE_ENTROPY_COLLAPSE = 3
SANITY_REASONS = {
    SANE_NAN: "nan",
    SANE_SATURATED: "saturated",
    SANE_ENTROPY_COLLAPSE: "entropy_collapse",
}


def make_paged_serve_step(
    cfg: ModelConfig,
    *,
    n_redundant: int = 1,
    sat_threshold: float = 1e6,
    entropy_floor: float = 0.0,
):
    """One decode step over a paged cache:
    (params, cache, table(B,W), token(B,)) -> (cache, token, sane).

    ``table`` is the host scheduler's block table, sliced to the current
    window of W blocks — the only width the step touches, which is where
    the O(max_len) → O(valid blocks) decode saving comes from.  Each
    distinct W is one retrace of the same jit (the engine buckets W to a
    power of two, so compiles stay logarithmic in max_len).  ``key`` /
    ``steps`` follow the :func:`sample_tokens` contract, including the
    ``n_redundant`` majority-vote re-read knob.

    ``sane`` is a (B,) int32 logit-sanity code per slot (the detection
    half of the degraded-device loop, generalizing the old bool
    finite-logits flag):

    * ``SANE_NAN`` — a non-finite logit row (the original NaN/Inf guard);
    * ``SANE_SATURATED`` — finite but ``max|logit| > sat_threshold``: the
      analog range blew up (drift/stuck-at pushing pre-activations to the
      rail) without tripping the float limits yet;
    * ``SANE_ENTROPY_COLLAPSE`` — softmax entropy strictly below
      ``entropy_floor`` (only computed when the floor is positive, so the
      default trace is unchanged): the distribution pinned to one token,
      the classic stuck-column signature.

    The engine evicts a flagged slot with the matching typed reason
    instead of publishing a garbage token.  All checks ride on the logits
    the step already materializes — no extra device round trip."""
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")

    def serve_step(params, cache, table, token, key=None, steps=None):
        cache, logits = TF.lm_decode_step(params, cache, token, cfg, table)
        zf = logits.astype(jnp.float32)
        finite = jnp.isfinite(zf).all(axis=-1)
        sat = jnp.max(jnp.abs(zf), axis=-1) > jnp.float32(sat_threshold)
        sane = jnp.where(
            finite,
            jnp.where(sat, SANE_SATURATED, SANE_OK),
            SANE_NAN,
        ).astype(_i32)
        if entropy_floor > 0.0:  # static: off => identical trace
            p = jax.nn.softmax(zf, axis=-1)
            ent = -jnp.sum(p * jnp.log(jnp.clip(p, 1e-30, 1.0)), axis=-1)
            collapsed = finite & ~sat & (ent < jnp.float32(entropy_floor))
            sane = jnp.where(collapsed, SANE_ENTROPY_COLLAPSE, sane).astype(
                _i32
            )
        tok = sample_tokens(cfg, logits, key, steps, n_redundant=n_redundant)
        return cache, tok, sane

    return serve_step


def make_page_spill(cfg: ModelConfig):
    """Gather a request's pool pages into a host-transferable payload.

    (paged_cache, ids (W,) int32) → {pool leaf: (nu, n_attn, W, bs, ...)}.
    The device half of preemption: the engine collects the victim's mapped
    pages (padded with the trash page to a FIXED width W, so one compile
    serves every spill), pulls the gathered payload to host memory, and
    frees the pages — the block pool sees the capacity back immediately.
    Reads only; the cache is NOT donated (it stays live for the surviving
    slots).  int8 pools spill code pages and scale planes together, so a
    restore is bit-exact at any pool dtype.
    """
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")

    def spill(cache: dict, ids) -> dict:
        return {
            name: cache[name][:, :, ids]
            for name in PAGE_POOL_LEAVES
            if name in cache
        }

    return spill


def make_page_restore(cfg: ModelConfig):
    """Scatter a spilled payload back onto freshly reserved pool pages.

    (paged_cache, ids (W,) int32, payload) → paged_cache.  Inverse of
    :func:`make_page_spill`: position ``i`` of ``ids`` receives row ``i``
    of every payload leaf.  Slots the engine does not want written (prefix
    pages that came back as index hits, padding) point at the trash page —
    duplicate trash ids are fine, nothing ever reads that page.  The cache
    IS donated: restore happens at admission, when the engine owns the
    only reference.
    """
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")

    def restore(cache: dict, ids, payload: dict) -> dict:
        out = dict(cache)
        for name, rows in payload.items():
            leaf = cache[name]
            out[name] = leaf.at[:, :, ids].set(rows.astype(leaf.dtype))
        return out

    return restore


def make_slot_state_gather(cfg: ModelConfig):
    """Read one slot's dense per-slot leaves out of a live paged cache.

    (paged_cache, slot int32) → state_leaves{B=1}.  Inverse of
    :func:`make_paged_state_insert` and shaped exactly like its input, so
    a spill→restore round trip is gather → (later) insert with no
    reshaping in between.  Covers ``pos`` plus the recurrent/SSM state
    leaves — everything a preempted request needs beyond its KV pages.
    The slot index is traced; one compile for the engine's lifetime.
    """
    if cfg.family == "encdec":
        raise ValueError("paged serving is token-LM only (no encdec)")

    def gather(cache: dict, slot) -> dict:
        # leaves WITHOUT a slot axis (the int8 pool's global quant_step
        # counter) are engine-wide, not per-request — a spill must not
        # capture them and a restore must not rewind them (replaying the
        # counter would replay stochastic-rounding draws)
        return {
            name: jax.lax.dynamic_slice_in_dim(
                leaf, slot, 1, axis=cache_batch_axis(cfg, name)
            )
            for name, leaf in cache.items()
            if name not in PAGE_POOL_LEAVES
            and leaf.ndim > cache_batch_axis(cfg, name)
        }

    return gather


def make_prefill_step(cfg: ModelConfig, shape: ShapeSpec):
    fns = get_model_fns(cfg)
    max_len = shape.seq_len + (cfg.n_patches if cfg.family == "vlm" else 0)

    def prefill_step(params, batch):
        return fns.prefill(params, batch, cfg, max_len)

    return prefill_step


def input_specs(arch: str, shape_name: str, tcfg: TrainConfig | None = None):
    """The dry-run entry: (step_fn_kind, arg specs) for an (arch, shape)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        tcfg = tcfg or TrainConfig()
        return {
            "kind": "train",
            "cfg": cfg,
            "shape": shape,
            "state": train_state_specs(cfg, tcfg),
            "batch": train_batch_specs(cfg, shape),
            "tcfg": tcfg,
        }
    if shape.kind == "prefill":
        return {
            "kind": "prefill",
            "cfg": cfg,
            "shape": shape,
            "params": params_specs(cfg),
            "batch": prefill_batch_specs(cfg, shape),
        }
    return {
        "kind": "decode",
        "cfg": cfg,
        "shape": shape,
        "params": params_specs(cfg),
        "cache": decode_cache_specs(cfg, shape),
        "token": _sds((shape.global_batch,), _i32),
    }
