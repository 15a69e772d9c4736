"""Continuous-batching serving engine: scheduler lifecycle, block
allocator + paged-cache behavior (back-pressure, reclamation, dense-vs-
paged byte identity, recompile guards), engine equivalence with the static
reference, and the WTA vote-concentration property (paper Fig. 6) at the
serving layer."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch import specs as SP
from repro.models import get_model_fns
from repro.serving import (
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    BlockAllocator,
    RequestState,
    Scheduler,
    ServeConfig,
    ServingEngine,
    StaticServingEngine,
    left_pad,
)

# ---------------------------------------------------------------------------
# Scheduler (pure host logic, no model)
# ---------------------------------------------------------------------------


def test_fifo_admission_order():
    s = Scheduler(n_slots=2)
    rids = [s.submit([1], 4).rid for _ in range(4)]
    admitted = s.admit()
    assert [r.rid for r in admitted] == rids[:2]
    assert [r.slot for r in admitted] == [0, 1]
    assert all(r.state is RequestState.PREFILL for r in admitted)
    assert s.queued() == 2
    # no free slot -> nothing admitted
    assert s.admit() == []
    # free slot 1 -> the NEXT queued rid goes there (FIFO, not LIFO)
    admitted[1].state = RequestState.DECODE
    s.evict(admitted[1], "length")
    refill = s.admit()
    assert [r.rid for r in refill] == [rids[2]]
    assert refill[0].slot == 1


def test_slot_refill_after_eos_eviction():
    s = Scheduler(n_slots=1)
    a = s.submit([1, 2], max_new_tokens=8)
    b = s.submit([3], max_new_tokens=8)
    (req,) = s.admit()
    assert req is a
    s.start_decode(req)
    assert s.record_token(req, 5, eos_token=5) is True
    assert a.state is RequestState.DONE
    assert a.done_reason == "eos"
    assert a.output == [5]
    # the freed slot is immediately refillable by the next queued request
    (req2,) = s.admit()
    assert req2 is b and req2.slot == 0
    assert s.occupancy() == 1.0


def test_left_pad_alignment():
    assert left_pad([1, 2], 5) == [0, 0, 0, 1, 2]
    assert left_pad([1, 2, 3], 3) == [1, 2, 3]
    assert left_pad([], 2) == [0, 0]
    with pytest.raises(ValueError):
        left_pad([1, 2, 3], 2)


def test_eos_negative_never_stops_early():
    """eos_token=-1 (the default) must never match a real token id —
    including token 0, the pad id."""
    s = Scheduler(n_slots=1)
    req = s.submit([1], max_new_tokens=4)
    s.admit()
    s.start_decode(req)
    for tok in (0, -0, 7, 0):
        done = s.record_token(req, tok, eos_token=-1)
    assert done is True
    assert req.done_reason == "length"
    assert req.output == [0, 0, 7, 0]


def test_scheduler_views():
    s = Scheduler(n_slots=4)
    assert not s.has_work()
    r = s.submit([1], 2)
    assert s.has_work() and s.occupancy() == 0.0
    s.admit()
    s.start_decode(r)
    assert s.occupancy() == 0.25
    assert s.active() == [r]
    s.record_token(r, 1, eos_token=-1)
    s.record_token(r, 1, eos_token=-1)
    assert not s.has_work()
    assert s.all_requests() == [r]


# ---------------------------------------------------------------------------
# Block allocator (pure host logic, no model)
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(8, n_reserved=1)
    assert a.capacity == 7 and a.available == 7
    p1 = a.alloc(0, 3)
    p2 = a.alloc(1, 2)
    assert len(p1) == 3 and len(p2) == 2
    assert 0 not in p1 + p2  # page 0 is the reserved trash page
    assert len(set(p1) & set(p2)) == 0
    assert a.available == 2
    assert a.free(0) == 3
    assert a.available == 5
    # freed pages are re-allocatable
    p3 = a.alloc(2, 5)
    assert set(p1) <= set(p3)


def test_allocator_exhaustion_and_misuse():
    a = BlockAllocator(4, n_reserved=1)
    a.alloc(0, 2)
    assert not a.can_alloc(2)
    with pytest.raises(ValueError, match="exhausted"):
        a.alloc(1, 2)
    with pytest.raises(ValueError, match="already holds"):
        a.alloc(0, 1)
    with pytest.raises(KeyError):
        a.free(99)
    with pytest.raises(ValueError):
        BlockAllocator(1, n_reserved=1)  # nothing allocatable


def test_scheduler_admission_gate_preserves_fifo():
    """A gated-out queue head blocks admission entirely — later requests
    must not jump it (that would starve large requests)."""
    s = Scheduler(n_slots=2)
    big = s.submit([1] * 8, 4)
    small = s.submit([2], 4)
    assert s.admit(gate=lambda r: len(r.prompt) < 4) == []
    assert big.state is RequestState.QUEUED
    assert small.state is RequestState.QUEUED
    assert [r.rid for r in s.admit()] == [big.rid, small.rid]


# ---------------------------------------------------------------------------
# ServeConfig validation
# ---------------------------------------------------------------------------


def test_buckets_all_above_max_len_is_loud():
    """Regression: buckets entirely above max_len used to silently filter
    to () and fail obscurely at bucket selection time."""
    cfg = ServeConfig(max_len=32, prefill_buckets=(64, 128))
    with pytest.raises(ValueError, match="max_len"):
        cfg.buckets()


def test_buckets_dedupe_and_partial_filter():
    cfg = ServeConfig(max_len=32, prefill_buckets=(16, 8, 16, 64, 8))
    assert cfg.buckets() == (8, 16)
    with pytest.raises(ValueError):
        ServeConfig(max_len=32, prefill_buckets=(0, 8)).buckets()


def test_engine_validates_buckets_eagerly(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="max_len"):
        ServingEngine(
            params, cfg, ServeConfig(max_len=16, prefill_buckets=(32,))
        )


# ---------------------------------------------------------------------------
# Engine (smoke model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("stablelm-3b")
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_static_vs_continuous_byte_identical(smoke):
    """With matching padded prompt windows (prompt lengths on the single
    prefill bucket boundary == the static batch max), greedy decoding must
    be byte-identical between the old static path and the scheduler."""
    cfg, params = smoke
    prompts = [
        [5, 6, 7, 1, 2, 3, 4, 9],
        [1, 2, 3],          # mixed length: both engines left-pad to 8
        [9, 8, 7, 6, 5, 4, 3, 2],
    ]
    sc = ServeConfig(
        max_batch=3, max_new_tokens=6, max_len=64, prefill_buckets=(8,)
    )
    cont = ServingEngine(params, cfg, sc)
    stat = StaticServingEngine(params, cfg, sc)
    for p in prompts:
        cont.submit(p)
        stat.submit(p)
    assert cont.step() == stat.step()


def test_mid_flight_slot_refill(smoke):
    """More requests than slots: the queue drains through freed slots and
    every request still completes with its full budget."""
    cfg, params = smoke
    eng = ServingEngine(
        params, cfg, ServeConfig(max_batch=2, max_new_tokens=3, max_len=32)
    )
    rids = [eng.submit([3 + i, 7], max_new_tokens=3) for i in range(5)]
    outs = eng.run()
    assert sorted(outs) == rids
    assert all(len(outs[r]) == 3 for r in rids)
    m = eng.metrics()
    assert m.completed == 5
    assert m.prefills == 5
    assert 0.0 < m.occupancy_mean <= 1.0
    assert m.tokens_per_s > 0
    assert m.ttft_mean > 0


def test_engine_eos_never_stops_early(smoke):
    cfg, params = smoke
    eng = ServingEngine(
        params, cfg,
        ServeConfig(max_batch=2, max_new_tokens=4, max_len=32, eos_token=-1),
    )
    eng.submit([5, 6, 7])
    (out,) = eng.step()
    assert len(out) == 4


def test_engine_eos_evicts_and_truncates(smoke):
    """Learn what the model emits greedily, then declare that token EOS —
    the request must stop at it and the engine must stay healthy."""
    cfg, params = smoke
    probe = ServingEngine(
        params, cfg, ServeConfig(max_batch=1, max_new_tokens=4, max_len=32)
    )
    probe.submit([5, 6, 7])
    (ref,) = probe.step()
    eos = ref[1]  # stop on the second emitted token
    eng = ServingEngine(
        params, cfg,
        ServeConfig(max_batch=1, max_new_tokens=4, max_len=32, eos_token=eos),
    )
    eng.submit([5, 6, 7])
    eng.submit([5, 6, 7])  # refills the slot after the eviction
    outs = eng.step()
    assert len(outs) == 2
    for out in outs:
        assert out == ref[: ref.index(eos) + 1]
    done = eng.sched.all_requests()
    assert all(r.done_reason == "eos" for r in done)


def test_per_request_sampling_invariant_to_batch_composition(smoke):
    """Per-slot PRNG keys: a WTA-sampled request emits the same tokens
    whether it runs alone or alongside other requests."""
    cfg, params = smoke
    wcfg = dataclasses.replace(cfg, wta_head=True)
    sc = ServeConfig(max_batch=3, max_new_tokens=4, max_len=32, seed=11)
    solo = ServingEngine(params, wcfg, sc)
    rid_solo = solo.submit([5, 6, 7])
    out_solo = solo.run()[rid_solo]

    crowd = ServingEngine(params, wcfg, sc)
    rid = crowd.submit([5, 6, 7])  # same rid 0 -> same per-request key
    crowd.submit([1, 2, 3, 4])
    crowd.submit([9])
    out_crowd = crowd.run()[rid]
    assert out_solo == out_crowd


# ---------------------------------------------------------------------------
# Paged KV cache (block pool + block table)
# ---------------------------------------------------------------------------

MIXED_PROMPTS = [
    [5, 6, 7, 1, 2, 3, 4, 9],
    [1, 2, 3],
    [9, 8, 7, 6, 5, 4, 3, 2],
    [4] * 20,
    [11, 12],
    [7] * 13,
]
MIXED_BUDGETS = [6, 9, 3, 12, 5, 7]


def _run_layout(params, cfg, layout, serve_kw=None):
    sc = ServeConfig(
        max_batch=3, max_new_tokens=8, max_len=64, kv_block_size=8,
        kv_layout=layout, **(serve_kw or {}),
    )
    eng = ServingEngine(params, cfg, sc)
    for p, b in zip(MIXED_PROMPTS, MIXED_BUDGETS):
        eng.submit(p, b)
    return eng, eng.run()


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_dense_vs_paged_greedy_byte_identical(arch):
    """The acceptance contract: greedy decode over a mixed-length trace
    (with mid-flight slot refill) must be byte-identical between the dense
    oracle layout and the paged engine — for pure-attention and hybrid
    (attention + recurrent state) families."""
    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    _, out_dense = _run_layout(params, cfg, "dense")
    _, out_paged = _run_layout(params, cfg, "paged")
    assert out_dense == out_paged


def test_paged_identity_under_page_recycling(smoke):
    """A pool barely larger than the working set forces freed pages to be
    re-handed to later requests mid-flight; decode must stay byte-identical
    to dense (stale page contents never leak into a live window)."""
    cfg, params = smoke
    _, out_dense = _run_layout(params, cfg, "dense")
    # 3 slots x ceil((8+12)/8)=3 pages + trash, with zero slack for the
    # widest co-resident mix -> constant recycling
    _, out_paged = _run_layout(
        params, cfg, "paged", {"num_kv_blocks": 12}
    )
    assert out_dense == out_paged


def test_pool_exhaustion_backpressures_admission(smoke):
    """With a pool that fits one request at a time, admission must hold
    the second request QUEUED (no crash, no slot leak) until the first
    evicts and frees its pages."""
    cfg, params = smoke
    sc = ServeConfig(
        max_batch=2, max_new_tokens=8, max_len=64, kv_block_size=8,
        kv_layout="paged", num_kv_blocks=4,  # capacity 3 = one request
    )
    eng = ServingEngine(params, cfg, sc)
    r1 = eng.submit([1, 2, 3], 8)   # bucket 8 + 8 -> 2 pages
    r2 = eng.submit([4, 5, 6], 8)
    eng.tick()
    reqs = {r.rid: r for r in eng.sched.all_requests()}
    assert reqs[r1].state is RequestState.DECODE
    assert reqs[r2].state is RequestState.QUEUED  # gated, not crashed
    assert not eng.blocks.can_alloc(2)
    outs = eng.run()  # r1 finishes -> pages freed -> r2 admitted
    assert sorted(outs) == [r1, r2]
    assert len(outs[r1]) == len(outs[r2]) == 8
    assert outs[r1] != [] and eng.blocks.available == eng.blocks.capacity


def test_submit_rejects_request_larger_than_pool(smoke):
    cfg, params = smoke
    sc = ServeConfig(
        max_batch=2, max_new_tokens=8, max_len=64, kv_block_size=8,
        kv_layout="paged", num_kv_blocks=3,  # capacity 2: smallest req fits
    )
    eng = ServingEngine(params, cfg, sc)
    with pytest.raises(ValueError, match="pool"):
        eng.submit([1] * 9, 8)  # bucket 16 + 8 -> 3 pages, capacity is 2


def test_eviction_reclaims_blocks(smoke):
    """Every eviction path (EOS at the engine level is covered elsewhere;
    here budget/length) returns pages: after a drained trace the free list
    holds the full capacity and the table rows all point at trash."""
    cfg, params = smoke
    eng, outs = _run_layout(params, cfg, "paged")
    assert len(outs) == len(MIXED_PROMPTS)
    assert eng.blocks.available == eng.blocks.capacity
    np.testing.assert_array_equal(eng._table, 0)


def test_paged_engine_no_unused_donation_warnings(smoke):
    """serve_step/insert donate the cache buffers so the per-tick update is
    in-place; a layout regression that breaks aliasing shows up as jax's
    'donated buffers were not usable' warning — fail on it."""
    cfg, params = smoke
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*[Dd]onat.*", category=UserWarning
        )
        for layout in ("paged", "dense"):
            _run_layout(params, cfg, layout)


def test_paged_recompile_guard(smoke):
    """Driving a full mixed-length trace costs one compile per (bucket,
    suffix-chunk shape) pair for the chunked-prefill entry point — with
    the default whole-bucket chunks and no prefix overlap, one per
    bucket — and one per decode window width (serve_step); a SECOND
    identical trace through the same engine costs zero new compiles.  No
    per-tick / per-slot / per-page-set / per-start-position recompiles."""
    cfg, params = smoke
    eng, _ = _run_layout(params, cfg, "paged")
    counts = eng.compile_counts()
    buckets_used = {eng._bucket(len(p)) for p in MIXED_PROMPTS}
    assert counts["suffix_prefill"] == len(buckets_used)
    assert counts["state_insert"] == 1  # every completion, one compile
    assert counts["sample0"] == 1
    # window widths are power-of-two bucketed: far fewer than decode steps
    m = eng.metrics()
    assert counts["serve_step"] <= 4
    assert m.decode_steps > counts["serve_step"]
    for p, b in zip(MIXED_PROMPTS, MIXED_BUDGETS):
        eng.submit(p, b)
    eng.run()
    assert eng.compile_counts() == counts, "steady-state trace recompiled"


def test_bad_kv_layout_is_loud(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="kv_layout"):
        ServingEngine(params, cfg, ServeConfig(kv_layout="flat"))


def test_bad_kv_cache_dtype_is_loud(smoke):
    cfg, params = smoke
    bad = dataclasses.replace(cfg, kv_cache_dtype="fp4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ServingEngine(params, bad, ServeConfig())


def test_bad_kv_block_size_is_loud(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="kv_block_size"):
        ServingEngine(params, cfg, ServeConfig(kv_block_size=0))


def test_pool_too_small_for_any_request_is_loud(smoke):
    """A num_kv_blocks that could never admit even the smallest request
    (shortest bucket + 1 token) must fail at engine construction, not hang
    the admission gate forever."""
    cfg, params = smoke
    sc = ServeConfig(
        max_len=64, kv_block_size=8, num_kv_blocks=2,
        prefill_buckets=(32,),  # min request needs ceil(33/8)=5 blocks
    )
    with pytest.raises(ValueError, match="admitted"):
        ServingEngine(params, cfg, sc)
    # same pool is fine once the buckets shrink the smallest request
    ServingEngine(
        params, cfg,
        ServeConfig(max_len=64, kv_block_size=8, num_kv_blocks=2,
                    prefill_buckets=(4, 32)),
    )


# ---------------------------------------------------------------------------
# int8 paged KV pool (stochastic-rounded quantized cache)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_int8_paged_matches_bf16_paged_greedy(arch):
    """Acceptance contract: with kv_cache_dtype='int8' the paged engine's
    greedy decode must agree with the bf16 paged path within tolerance —
    on the smoke models the quantization error never flips an argmax, so
    the token streams agree exactly (attention-only and hybrid families)."""
    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    _, out_bf16 = _run_layout(params, cfg, "paged")
    _, out_int8 = _run_layout(params, icfg, "paged")
    assert sorted(out_bf16) == sorted(out_int8)
    total = agree = 0
    for rid in out_bf16:
        assert len(out_bf16[rid]) == len(out_int8[rid])
        total += len(out_bf16[rid])
        agree += sum(a == b for a, b in zip(out_bf16[rid], out_int8[rid]))
    assert agree / total >= 0.95, (agree, total)


def test_int8_paged_matches_int8_dense(smoke):
    """Dense-int8 (deterministic nearest rounding) and paged-int8
    (stochastic rounding) are different quantizers of the same cache, so
    token streams agree within tolerance, not byte-for-byte."""
    cfg, params = smoke
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    _, out_dense = _run_layout(params, icfg, "dense")
    _, out_paged = _run_layout(params, icfg, "paged")
    total = agree = 0
    for rid in out_dense:
        total += len(out_dense[rid])
        agree += sum(a == b for a, b in zip(out_dense[rid], out_paged[rid]))
    assert agree / total >= 0.95, (agree, total)


def test_int8_paged_identity_under_page_recycling(smoke):
    """Forced page recycling (pool with zero slack) must not leak stale
    codes or stale SCALES into a live window — agreement with the dense
    int8 oracle holds while freed pages are re-handed mid-flight.
    num_kv_blocks=7 is a bf16-block budget → 13 int8 pages, exactly the
    widest co-resident working set of the mixed trace."""
    cfg, params = smoke
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    _, out_dense = _run_layout(params, icfg, "dense")
    eng, out_paged = _run_layout(
        params, icfg, "paged", {"num_kv_blocks": 7}
    )
    assert eng.blocks.n_blocks == 13  # doubled budget, trash counted once
    total = agree = 0
    for rid in out_dense:
        total += len(out_dense[rid])
        agree += sum(a == b for a, b in zip(out_dense[rid], out_paged[rid]))
    assert agree / total >= 0.95, (agree, total)
    assert eng.blocks.available == eng.blocks.capacity


def test_int8_pool_doubles_admission_capacity(smoke):
    """At equal num_kv_blocks (a native-dtype memory budget) the int8 pool
    holds twice the pages, so admission takes ~2x the requests — the
    capacity half of the quantization win, visible to BlockAllocator."""
    cfg, params = smoke
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")

    def admitted(mcfg):
        sc = ServeConfig(
            max_batch=8, max_new_tokens=8, max_len=64, kv_block_size=8,
            kv_layout="paged", num_kv_blocks=5,
            # identical prompts would ALSO share pages — disable sharing to
            # isolate the dtype-driven capacity factor being pinned here
            enable_prefix_sharing=False,
        )
        eng = ServingEngine(params, mcfg, sc)
        for _ in range(8):
            eng.submit([1, 2, 3], 8)  # 2 blocks each
        eng.tick()
        return sum(
            1 for r in eng.sched.all_requests()
            if r.state is not RequestState.QUEUED
        )

    n16, n8 = admitted(cfg), admitted(icfg)
    assert n16 == 2 and n8 == 4  # capacity 4 vs 9 blocks, 2 per request


def test_int8_paged_recompile_guard(smoke):
    """The int8 layout keeps the compile discipline: one compile per
    (bucket, chunk shape) pair for the chunked prefill (the per-block
    rounding-seed vector is traced) and one per decode window bucket,
    zero new compiles on a repeat trace."""
    cfg, params = smoke
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    eng, _ = _run_layout(params, icfg, "paged")
    counts = eng.compile_counts()
    buckets_used = {eng._bucket(len(p)) for p in MIXED_PROMPTS}
    assert counts["suffix_prefill"] == len(buckets_used)
    m = eng.metrics()
    assert counts["serve_step"] <= 4
    assert m.decode_steps > counts["serve_step"]
    for p, b in zip(MIXED_PROMPTS, MIXED_BUDGETS):
        eng.submit(p, b)
    eng.run()
    assert eng.compile_counts() == counts, "steady-state trace recompiled"


def test_int8_paged_no_unused_donation_warnings(smoke):
    """The scale planes must stay donation-aliasable like the code pools."""
    cfg, params = smoke
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*[Dd]onat.*", category=UserWarning
        )
        _run_layout(params, icfg, "paged")


# ---------------------------------------------------------------------------
# Prefix sharing + copy-on-write (content-hash block dedup in the paged pool)
# ---------------------------------------------------------------------------

# repeated-prefix trace: the first/second/fourth prompts are identical, the
# third differs, mixed budgets — sharing must dedup the repeats only
SHARED_PROMPTS = [
    [1, 2, 3, 4, 5, 6, 7, 8],
    [1, 2, 3, 4, 5, 6, 7, 8],
    [9, 9, 9],
    [1, 2, 3, 4, 5, 6, 7, 8],
    [9, 9, 9],
]
SHARED_BUDGETS = [6, 4, 6, 3, 5]


def _run_sharing(params, cfg, share, serve_kw=None):
    kw = dict(
        max_batch=3, max_new_tokens=8, max_len=64, kv_block_size=8,
        kv_layout="paged", enable_prefix_sharing=share,
    )
    kw.update(serve_kw or {})
    sc = ServeConfig(**kw)
    eng = ServingEngine(params, cfg, sc)
    for p, b in zip(SHARED_PROMPTS, SHARED_BUDGETS):
        eng.submit(p, b)
    return eng, eng.run()


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_prefix_sharing_byte_identical(arch):
    """The acceptance contract: greedy decode over a repeated-prefix trace
    must be byte-identical with prefix sharing on vs off — full-hit
    admissions replay the stored last-token logits and state leaves of the
    original prefill, which are bit-equal to what their own prefill would
    have produced.  Covers attention-only and hybrid (recurrent-state)
    families; sharing must actually fire (prefill skipped at least once)."""
    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng_on, out_on = _run_sharing(params, cfg, True)
    eng_off, out_off = _run_sharing(params, cfg, False)
    assert out_on == out_off
    m_on, m_off = eng_on.metrics(), eng_off.metrics()
    assert m_off.prefix_hits == 0
    assert m_on.prefix_hits >= 1
    assert m_on.prefills == m_off.prefills - m_on.prefix_hits


def test_prefix_sharing_wta_sampling_stays_per_request(smoke):
    """A full-hit admission samples its first token from STORED logits with
    its OWN per-request key — WTA vote noise must stay a function of
    (rid, step), not of whether the prefill was shared."""
    cfg, params = smoke
    wcfg = dataclasses.replace(cfg, wta_head=True)
    eng_on, out_on = _run_sharing(params, wcfg, True, {"seed": 11})
    _, out_off = _run_sharing(params, wcfg, False, {"seed": 11})
    assert out_on == out_off
    assert eng_on.metrics().prefix_hits >= 1


def test_prefix_sharing_cow_fork_mid_decode(smoke):
    """An unaligned bucket (8-token prompts, 16-token blocks) leaves the
    boundary block partially filled; identical prompts admitted in the
    same tick share it, and the first decode write must copy-on-write fork
    every sharer onto its reserved spare page — with decode staying
    byte-identical to the sharing-off engine."""
    cfg, params = smoke
    kw = {"kv_block_size": 16, "prefill_buckets": (8, 32)}
    eng_on, out_on = _run_sharing(params, cfg, True, kw)
    eng_off, out_off = _run_sharing(params, cfg, False, kw)
    assert out_on == out_off
    m = eng_on.metrics()
    assert m.cow_forks >= 1
    assert m.prefix_hits >= 1
    assert eng_off.metrics().cow_forks == 0
    # every spare was either spent on a fork or returned at eviction
    assert eng_on.blocks.available == eng_on.blocks.capacity


def test_prefix_sharing_page_recycling_of_formerly_shared_block(smoke):
    """Once every owner of a shared block is evicted the page returns to
    the free list AND its index entry dies with it: a later request with a
    different prompt recycles the physical page, and a later request with
    the ORIGINAL prompt must re-prefill (a stale hit would hand it the
    recycled content).  Byte-identity against sharing-off pins that no
    stale content leaks through either path."""
    cfg, params = smoke
    shared = [1, 2, 3, 4, 5, 6, 7, 8]

    def drive(share):
        sc = ServeConfig(
            max_batch=2, max_new_tokens=8, max_len=64, kv_block_size=8,
            kv_layout="paged", num_kv_blocks=7,  # zero-slack working set
            enable_prefix_sharing=share,
        )
        eng = ServingEngine(params, cfg, sc)
        rids = [eng.submit(shared, 4), eng.submit(shared, 4)]
        while eng.sched.has_work():
            eng.tick()
        # both owners gone: the pool must be fully reclaimed, index empty
        assert eng.blocks.available == eng.blocks.capacity
        assert not eng.blocks.registered_pages()
        rids.append(eng.submit([4] * 12, 6))   # recycles the freed pages
        rids.append(eng.submit(shared, 4))     # the formerly shared prompt
        outs = eng.run()
        return eng, [outs[r] for r in rids]

    eng_on, out_on = drive(True)
    _, out_off = drive(False)
    assert out_on == out_off
    m = eng_on.metrics()
    assert m.prefix_hits == 1           # only the co-resident pair shared
    assert m.prefills == len(out_on) - 1


@pytest.mark.parametrize("dtype", ["same", "int8"])
def test_prefix_sharing_partial_hit_shares_leading_blocks(smoke, dtype):
    """Two same-length prompts agreeing on their first block (but not the
    second) share exactly that block: the sharer prefills ONLY its suffix
    (no full hit, but a partial hit that skips the matched block's
    tokens) and maps the resident page — its table row aliases the
    original's at block 0 and diverges at block 1 — with decode
    byte-identical to sharing-off.  Works for int8 pools because block
    seeds are content-derived, so the sharer's own prefill would have
    written the identical codes it is instead aliasing."""
    cfg, params = smoke
    if dtype == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=dtype)
    a = list(range(1, 17))
    b = list(range(1, 9)) + [20, 21, 22, 23, 24, 25, 26, 27]

    def drive(share):
        eng = ServingEngine(
            params, cfg,
            ServeConfig(
                max_batch=2, max_new_tokens=6, max_len=64, kv_block_size=8,
                enable_prefix_sharing=share,
            ),
        )
        rids = [eng.submit(a, 6), eng.submit(b, 6)]
        # chunked prefill interleaves: tick until both jobs published
        # their table rows (request b's job runs a tick after a's)
        while not all(
            r.state is RequestState.DECODE for r in eng.sched.all_requests()
        ):
            eng.tick()
        tables = eng._table.copy()
        outs = eng.run()
        return eng, tables, [outs[r] for r in rids]

    eng_on, t_on, out_on = drive(True)
    _, t_off, out_off = drive(False)
    assert out_on == out_off
    m = eng_on.metrics()
    assert m.prefix_hits == 0           # partial ≠ full hit
    assert m.prefix_partial_hits == 1   # request b mapped block 0
    # the attention-only smoke family resumes at the full matched depth:
    # request b computed only its 8-token suffix
    assert m.prefill_tokens_saved == 8
    assert m.prefill_tokens == 16 + 8
    assert t_on[0, 0] == t_on[1, 0], "leading block not shared"
    assert t_on[0, 1] != t_on[1, 1], "diverging block wrongly shared"
    assert t_off[0, 0] != t_off[1, 0]


def test_prefix_sharing_int8_within_quant_tolerance(smoke):
    """int8 pools: block quantization seeds derive from block CONTENT
    (chain hash), not the request id, so a shared block's codes are
    bit-identical to what the sharer's own prefill would have written —
    sharing on vs off stays within quantization tolerance (on this smoke
    trace the schedules coincide, so the streams agree exactly)."""
    cfg, params = smoke
    icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    eng_on, out_on = _run_sharing(params, icfg, True)
    _, out_off = _run_sharing(params, icfg, False)
    assert sorted(out_on) == sorted(out_off)
    assert eng_on.metrics().prefix_hits >= 1
    total = agree = 0
    for rid in out_off:
        assert len(out_on[rid]) == len(out_off[rid])
        total += len(out_off[rid])
        agree += sum(a == b for a, b in zip(out_on[rid], out_off[rid]))
    assert agree / total >= 0.95, (agree, total)


def test_prefix_sharing_recompile_guard(smoke):
    """Shared-prefix admission and COW forks add ZERO compilations beyond
    the existing per-bucket/per-window set plus the three one-time
    sharing entry points (state insert, page copy, stored-logits
    sampler) — and a repeat trace through the same engine compiles
    nothing new at all."""
    cfg, params = smoke
    kw = {"kv_block_size": 16, "prefill_buckets": (8, 32)}  # forces a fork
    eng, _ = _run_sharing(params, cfg, True, kw)
    counts = eng.compile_counts()
    buckets_used = {eng._bucket(len(p)) for p in SHARED_PROMPTS}
    assert counts["suffix_prefill"] == len(buckets_used)
    assert counts["serve_step"] <= 4
    assert counts["state_insert"] == 1  # bucket-independent, one compile
    assert counts["page_copy"] == 1     # at least one fork, one compile
    assert counts["sample0"] == 1
    for p, b in zip(SHARED_PROMPTS, SHARED_BUDGETS):
        eng.submit(p, b)
    eng.run()
    assert eng.compile_counts() == counts, "steady-state trace recompiled"


def test_prefix_sharing_raises_admission_capacity(smoke):
    """Acceptance contract, capacity half: at equal num_kv_blocks a
    repeated-prefix burst admits strictly more requests with sharing on —
    each repeat maps the resident prompt blocks and only allocates its
    decode-budget pages."""
    cfg, params = smoke

    def admitted(share):
        sc = ServeConfig(
            max_batch=8, max_new_tokens=8, max_len=64, kv_block_size=8,
            kv_layout="paged", num_kv_blocks=8,
            enable_prefix_sharing=share,
        )
        eng = ServingEngine(params, cfg, sc)
        for _ in range(8):
            eng.submit(list(range(1, 17)), 8)  # bucket 16 + 8 → 3 blocks
        eng.tick()
        return sum(
            1 for r in eng.sched.all_requests()
            if r.state is not RequestState.QUEUED
        )

    # capacity 7: off fits floor(7/3)=2 requests; on fits the original (3
    # pages) + 4 repeats (1 fresh decode page each) = 5
    assert admitted(False) == 2
    assert admitted(True) == 5


def test_prefix_sharing_validation_is_loud(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="enable_prefix_sharing"):
        ServingEngine(
            params, cfg, ServeConfig(enable_prefix_sharing="off")
        )


def test_prefix_sharing_random_trace_equivalence(smoke):
    """Engine-level property check: random repeated-prefix traces under a
    tight pool must decode byte-identically with sharing on vs off (greedy
    outputs are schedule-invariant, so even admission-order divergence
    from the capacity win cannot change them), with allocator invariants
    re-checked after every tick."""
    import random as _random

    from test_prefix_sharing import check_invariants

    cfg, params = smoke
    templates = [
        [1, 2, 3, 4, 5, 6, 7, 8], [4] * 12, [9, 9, 9], [1, 2, 3, 4],
    ]
    for seed in (0, 1, 2):
        rng = _random.Random(seed)
        reqs = [
            (list(rng.choice(templates)), rng.randint(2, 8))
            for _ in range(7)
        ]

        def drive(share):
            eng = ServingEngine(
                params, cfg,
                ServeConfig(
                    max_batch=3, max_new_tokens=8, max_len=64,
                    kv_block_size=8, num_kv_blocks=10,
                    enable_prefix_sharing=share,
                ),
            )
            rids = [eng.submit(p, b) for p, b in reqs]
            while eng.sched.has_work():
                eng.tick()
                check_invariants(eng.blocks)
            outs = {
                r.rid: r.output
                for r in eng.sched.all_requests()
            }
            assert eng.blocks.available == eng.blocks.capacity
            return [outs[r] for r in rids]

        assert drive(True) == drive(False), f"trace seed {seed} diverged"


# ---------------------------------------------------------------------------
# Suffix-only prefill + chunked, interleaved prefill scheduling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_partial_sharing_byte_identity(arch):
    """Acceptance contract for suffix-only prefill: a trace of prompts
    sharing a long common prefix (but NOT full prompts) decodes
    byte-identically with sharing on vs off, while sharing-on computes
    only the suffixes.  ``prefill_chunk=8`` gives the hybrid family a
    chunk grid whose boundary states are stashed, so recurrent-state
    models get suffix resumes too — not just the attention-only family."""
    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    head = list(range(1, 17))  # 16 shared real tokens
    prompts = [
        head + [30 + i] * 8 for i in range(3)  # 24 tokens, bucket 32
    ]

    def drive(share):
        eng = ServingEngine(
            params, cfg,
            ServeConfig(
                max_batch=3, max_new_tokens=6, max_len=64, kv_block_size=8,
                prefill_chunk=8, enable_prefix_sharing=share,
            ),
        )
        rids = [eng.submit(p, 6) for p in prompts]
        outs = eng.run()
        return eng, [outs[r] for r in rids]

    eng_on, out_on = drive(True)
    eng_off, out_off = drive(False)
    assert out_on == out_off
    m_on, m_off = eng_on.metrics(), eng_off.metrics()
    assert m_off.prefix_partial_hits == 0
    assert m_off.prefill_tokens_saved == 0
    # padded prompts agree on 8 pad + 16 head = 24 tokens = 3 blocks; the
    # two repeats each resume at 24 (a chunk boundary, so the hybrid
    # family's stored state snapshot is used)
    assert m_on.prefix_partial_hits == 2
    assert m_on.prefill_tokens_saved == 2 * 24
    assert m_on.prefill_tokens == m_off.prefill_tokens - 2 * 24
    assert m_on.prefix_hits == 0  # no full hits in this trace


def test_chunked_prefill_interleaves_with_decode(smoke):
    """A long prompt's prefill spreads over multiple ticks (one chunk per
    tick) while an in-flight request keeps emitting a token EVERY tick —
    the TTFT-jitter bound chunking exists for.  Token streams stay
    byte-identical to the unchunked engine."""
    cfg, params = smoke

    def drive(chunk):
        eng = ServingEngine(
            params, cfg,
            ServeConfig(
                max_batch=2, max_new_tokens=10, max_len=64,
                kv_block_size=8, prefill_chunk=chunk,
            ),
        )
        # disjoint token ranges: no padded block of r1 can match r0's
        r0 = eng.submit([50, 51, 52], 10)
        eng.tick()  # r0 prefills (one 8-token bucket = one chunk), decodes
        r1 = eng.submit(list(range(1, 28)), 4)  # bucket 32 -> 4 chunks
        decode_ticks = 0
        while eng.sched.request(r1).state is not RequestState.DECODE:
            before = len(eng.sched.request(r0).output)
            eng.tick()
            decode_ticks += len(eng.sched.request(r0).output) - before
        outs = eng.run()
        return decode_ticks, [outs[r] for r in (r0, r1)]

    ticks_chunked, outs_chunked = drive(8)
    ticks_mono, outs_mono = drive(0)
    assert outs_chunked == outs_mono
    # r1's prefill took 4 ticks (4 chunks); r0 decoded through every one
    assert ticks_chunked >= 4
    assert ticks_mono <= 2


def test_chunked_prefill_recompile_guard(smoke):
    """Chunked prefill compiles once per (bucket, chunk shape) pair — the
    start position, page ids, slot and seeds are traced — and a repeat
    trace (including the partial-hit suffix shapes) compiles nothing."""
    cfg, params = smoke
    eng = ServingEngine(
        params, cfg,
        ServeConfig(
            max_batch=2, max_new_tokens=4, max_len=64, kv_block_size=8,
            prefill_chunk=16,
        ),
    )

    def trace():
        rids = [
            # long budget: still resident when the third request arrives
            eng.submit(list(range(1, 25)), 16),  # bucket 32: 16+16 chunks
            eng.submit(list(range(1, 7)), 4),    # bucket 8: one 8 chunk
            # shares 3 padded blocks with the first prompt (8 pad + 16
            # head); admitted into the second slot after the short
            # request evicts, while the first is still decoding
            eng.submit(list(range(1, 17)) + [40] * 8, 4),
        ]
        eng.run()
        return rids

    trace()
    counts = eng.compile_counts()
    # three (bucket, chunk-shape) pairs: bucket-32 cold runs as two
    # 16-token chunks (ONE compile), bucket 8 as one whole-bucket chunk,
    # and the partial hit (24 matched tokens: 8 pad + 16 head = 3 blocks)
    # resumes mid-grid with an 8-token tail chunk [24, 32)
    assert counts["suffix_prefill"] == 3, counts
    m = eng.metrics()
    assert m.prefix_partial_hits == 1
    assert m.prefill_tokens_saved == 24
    trace()
    assert eng.compile_counts() == counts, "repeat trace recompiled"


def test_prefill_chunk_validation_is_loud(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(
            params, cfg, ServeConfig(kv_block_size=8, prefill_chunk=12)
        )
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(params, cfg, ServeConfig(prefill_chunk=-8))
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(
            params, cfg, ServeConfig(kv_layout="dense", prefill_chunk=16)
        )


def test_demoted_full_hit_does_not_corrupt_registrant(smoke):
    """Regression: a full-hit job that loses its stored payload while
    queued (the registrant's first decode write in-place-diverges the
    partial boundary block, deregistering it) demotes to a boundary-block
    recompute — which must COW-fork the now-diverged shared page onto the
    job's reserved spare instead of rewriting it in place, or the
    registrant's live decode K/V rows get zeroed and its token stream
    silently diverges from the sharing-off engine.

    The trace forces the window: R1 (unaligned 12-token bucket) admits
    and completes first; M occupies the one compute chunk of the next
    tick so R2 (identical to R1, full hit stashed with no payload yet)
    waits in the FIFO while R1's decode kills the terminal index entry."""
    cfg, params = smoke
    kw = dict(
        max_batch=3, max_new_tokens=8, max_len=64, kv_block_size=8,
        prefill_buckets=(12, 16),
    )
    prompts = [
        list(range(1, 13)),    # R1: bucket 12, partial boundary block
        list(range(20, 36)),   # M: bucket 16, blocks the compute slot
        list(range(1, 13)),    # R2: full match on R1, demotes later
    ]

    def drive(share):
        eng = ServingEngine(
            params, cfg,
            ServeConfig(**kw, enable_prefix_sharing=share),
        )
        rids = [eng.submit(p, 8) for p in prompts]
        outs = eng.run()
        return eng, [outs[r] for r in rids]

    eng_on, out_on = drive(True)
    _, out_off = drive(False)
    assert out_on == out_off
    m = eng_on.metrics()
    assert m.prefix_partial_hits >= 1  # the demotion actually fired
    assert m.cow_forks >= 1            # ...and forked, not rewrote
    assert eng_on.blocks.available == eng_on.blocks.capacity


def test_full_hit_on_boundary_snapshot_demotes_not_crashes(smoke):
    """Regression: a short prompt that IS the shared prefix of a longer
    in-flight prompt full-matches blocks whose terminal hash carries only
    the longer prompt's logits-less chunk-boundary snapshot.  The engine
    must demote that job to a suffix recompute (never feed None logits to
    the sampler) and republish terminal logits on the hash, so a LATER
    identical short prompt full-hits properly — with every token stream
    byte-identical to sharing-off."""
    cfg, params = smoke
    kw = dict(
        max_batch=3, max_new_tokens=6, max_len=64, kv_block_size=8,
        prefill_buckets=(16, 32), prefill_chunk=16,
    )

    def drive(share):
        eng = ServingEngine(
            params, cfg, ServeConfig(**kw, enable_prefix_sharing=share)
        )
        a = eng.submit(list(range(1, 25)), 6)  # bucket 32: [0]*8 + 1..24
        eng.tick()  # A's first chunk [0, 16) stashes (None, state)
        # B's padded prompt ([0]*8 + 1..8) == A's first 16 padded tokens:
        # B full-matches A's blocks but the terminal payload has no logits
        b = eng.submit(list(range(1, 9)), 6)
        c = eng.submit(list(range(1, 9)), 6)  # repeat of B
        outs = eng.run()
        return eng, [outs[r] for r in (a, b, c)]

    eng_on, out_on = drive(True)
    _, out_off = drive(False)
    assert out_on == out_off
    m = eng_on.metrics()
    assert m.prefix_partial_hits >= 1  # B demoted to a suffix recompute
    assert m.prefix_hits >= 1          # C full-hit on B's republished logits


def test_admission_gate_refusal_has_no_side_effects(smoke):
    """Directed regression for the admission-gate audit: a gate that
    REFUSES (pool exhausted) must leave the allocator bit-for-bit
    untouched — no refcount bump on the probed/matched pages, no index
    mutation — even when the refused request had a partial prefix match.
    A True gate bumps exactly the pages it maps (its owned list)."""
    from repro.serving.scheduler import prefix_block_hashes

    cfg, params = smoke
    sc = ServeConfig(
        max_batch=2, max_new_tokens=8, max_len=64, kv_block_size=8,
        num_kv_blocks=6,  # capacity 5: the first request takes 3
    )
    eng = ServingEngine(params, cfg, sc)
    head = list(range(1, 17))  # bucket 16, block-aligned: 2 prompt blocks
    r1 = eng.submit(head, 8)   # + 8 budget tokens -> 3 blocks
    eng.tick()                 # admitted, prefilled, decoding
    hashes = [h for h, _ in prefix_block_hashes(head, 8)]
    matched = eng.blocks.longest_prefix_match(hashes)
    assert len(matched) == 2   # r1's prompt blocks are resident
    refs_before = {p: eng.blocks.refcount(p) for p in matched}
    index_before = eng.blocks.registered_pages()
    # same head, bigger budget: matches both prompt blocks but needs 3
    # fresh decode pages when only 2 remain -> the gate must refuse
    # without touching anything
    r2 = eng.submit(head, 24)
    eng.tick()
    assert eng.sched.request(r2).state is RequestState.QUEUED
    assert {p: eng.blocks.refcount(p) for p in matched} == refs_before
    assert eng.blocks.registered_pages() == index_before
    outs = eng.run()  # r1 evicts -> r2 admits and completes
    assert sorted(outs) == [r1, r2]


# ---------------------------------------------------------------------------
# WTA majority-vote concentration (paper Fig. 6 at the serving layer)
# ---------------------------------------------------------------------------


def test_wta_vote_concentration_with_trials(smoke):
    """As the trial count T grows, the majority vote concentrates on the
    argmax token — the paper's accuracy-recovery mechanism, exercised
    through the serving sampler (`sample_tokens`) with per-slot keys."""
    cfg, _ = smoke
    z = jnp.asarray(
        [0.0, -0.5, 0.3, 2.0, 0.8, -1.0, 0.5, -0.2,
         0.1, -0.8, 0.4, 0.0, -0.3, 0.6, -0.6, 0.2],
        jnp.float32,
    )
    target = int(jnp.argmax(z))
    n_samples = 256
    logits = jnp.broadcast_to(z, (n_samples, z.shape[0]))
    base = jax.random.PRNGKey(123)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(n_samples)
    )
    steps = jnp.zeros((n_samples,), jnp.int32)

    rates = {}
    for trials in (1, 16, 256):
        wcfg = dataclasses.replace(
            cfg,
            wta_head=True,
            analog=dataclasses.replace(cfg.analog, wta_trials=trials),
        )
        toks = SP.sample_tokens(wcfg, logits, keys, steps)
        rates[trials] = float(jnp.mean(toks == target))
    # monotone concentration (with sampling slack) ... Fig. 6 mechanism
    assert rates[16] > rates[1] - 0.05
    assert rates[256] > rates[16] - 0.05
    assert rates[256] > 0.9, rates
    assert rates[256] > rates[1] + 0.1, rates


# ---------------------------------------------------------------------------
# Host-bookkeeping bug sweep + sharded decode over the (data, model) mesh
# ---------------------------------------------------------------------------


def test_evict_severs_slot_binding():
    """Refill-reuse regression: eviction must null the DONE request's live
    ``slot`` binding (keeping the historical slot as ``done_slot``), so a
    done record can never alias the per-slot state of whichever request
    refills the slot next."""
    s = Scheduler(n_slots=1)
    a = s.submit([1, 2], max_new_tokens=1)
    b = s.submit([3, 4], max_new_tokens=1)
    (req,) = s.admit()
    s.start_decode(req)
    assert s.record_token(req, 5, eos_token=-1) is True
    assert a.state is RequestState.DONE
    assert a.slot is None          # live binding severed
    assert a.done_slot == 0        # history survives for metrics/debug
    (req2,) = s.admit()
    assert req2 is b and req2.slot == 0
    assert a.slot != req2.slot     # DONE record does not alias the reuse


def test_submit_rejects_empty_prompt(smoke):
    """An empty prompt would left-pad to an all-pad window and decode from
    a pad token's logits — garbage that previously sailed through."""
    cfg, params = smoke
    eng = ServingEngine(
        params, cfg, ServeConfig(max_batch=1, max_new_tokens=2, max_len=32)
    )
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([])
    assert not eng.sched.has_work()


def test_mesh_validation_is_loud(smoke):
    cfg, params = smoke
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1, data=1)
    with pytest.raises(ValueError, match="paged-layout knob"):
        ServingEngine(
            params, cfg,
            ServeConfig(
                max_batch=1, max_new_tokens=2, max_len=32,
                kv_layout="dense", mesh=mesh,
            ),
        )
    bad = jax.make_mesh((1,), ("x",), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match=r"\('data', 'model'\) axes"):
        ServingEngine(
            params, cfg,
            ServeConfig(
                max_batch=1, max_new_tokens=2, max_len=32,
                kv_layout="paged", mesh=bad,
            ),
        )


def test_data_axis_mesh_refused_with_tpu_kernels(monkeypatch):
    """With the Pallas kernels selected (as on TPU), a mesh whose data
    axis is > 1 is refused at validation: each chip's kernel reads only
    the pages that chip holds.  A data axis of 1 passes, and so does
    data > 1 where the jnp oracles stand in for the kernels."""
    from repro.kernels import ops

    class Mesh:  # validate reads only the axis names and sizes
        axis_names = ("data", "model")

        def __init__(self, data, model):
            self.devices = np.empty((data, model))

    cfg = lambda mesh: ServeConfig(
        max_batch=2, max_new_tokens=2, max_len=32, mesh=mesh
    )
    cfg(Mesh(2, 2)).validate()            # CPU: the oracles read any page
    monkeypatch.setattr(ops, "tpu_kernels", lambda: True)
    cfg(Mesh(1, 4)).validate()
    with pytest.raises(ValueError, match="data=2 > 1"):
        cfg(Mesh(2, 2)).validate()


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_sharded_1x1_mesh_byte_identity(arch):
    """The sharded-decode acceptance contract: an engine on a 1×1
    ``(data, model)`` mesh must be BYTE-identical to ``mesh=None`` over
    the full mixed-length trace (admission, refill, page recycling) —
    for pure-attention and hybrid recurrent families."""
    from repro.launch.mesh import make_host_mesh

    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    _, base = _run_layout(params, cfg, "paged")
    _, shard = _run_layout(
        params, cfg, "paged", {"mesh": make_host_mesh(model=1, data=1)}
    )
    assert base == shard


def test_sharded_recompile_guard(smoke):
    """The mesh-aware entry points keep the compile discipline of the
    single-device engine: one suffix-prefill compile per bucket, windowed
    serve_step compiles, zero new compiles on a repeat trace."""
    from repro.launch.mesh import make_host_mesh

    cfg, params = smoke
    eng, _ = _run_layout(
        params, cfg, "paged", {"mesh": make_host_mesh(model=1, data=1)}
    )
    counts = eng.compile_counts()
    buckets_used = {eng._bucket(len(p)) for p in MIXED_PROMPTS}
    assert counts["suffix_prefill"] == len(buckets_used)
    assert counts["state_insert"] == 1
    assert counts["serve_step"] <= 4
    for p, b in zip(MIXED_PROMPTS, MIXED_BUDGETS):
        eng.submit(p, b)
    eng.run()
    assert eng.compile_counts() == counts, "steady-state trace recompiled"


# ---------------------------------------------------------------------------
# Preemption, priorities, deadlines & KV spill/restore
# ---------------------------------------------------------------------------


def test_priority_admission_order():
    """Interactive (priority 0) jumps the queue ahead of earlier batch
    submissions; within a class, FIFO by rid."""
    s = Scheduler(n_slots=1)
    b0 = s.submit([1], 2, priority=PRIORITY_BATCH)
    b1 = s.submit([2], 2, priority=PRIORITY_BATCH)
    i0 = s.submit([3], 2, priority=PRIORITY_INTERACTIVE)
    assert s.peek() is i0
    (req,) = s.admit()
    assert req is i0
    s.start_decode(req)
    s.evict(req, "length")
    (nxt,) = s.admit()
    assert nxt is b0 and s.peek() is b1


def test_requeue_roundtrip():
    """requeue frees the slot, returns the request to QUEUED, and bumps
    the preemption counter; the next admit re-seats it."""
    s = Scheduler(n_slots=1)
    a = s.submit([1, 2], 4)
    (req,) = s.admit()
    s.start_decode(req)
    s.record_token(req, 7, eos_token=-1)
    s.requeue(req)
    assert a.state is RequestState.QUEUED
    assert a.slot is None
    assert a.preemptions == 1
    assert a.output == [7]  # decoded tokens survive the round trip
    (req2,) = s.admit()
    assert req2 is a and a.slot == 0


def test_requeue_restarts_the_queue_wait():
    s = Scheduler(n_slots=1)
    a = s.submit([1, 2], 4, now=1.0)
    assert a.queued_since == 1.0
    (req,) = s.admit()
    s.start_decode(req)
    s.requeue(req, now=5.0)
    assert a.queued_since == 5.0 and a.submit_time == 1.0


def test_queue_wait_counts_each_admission_from_submit_to_its_slot(smoke):
    """One slot, two requests: the second waits in the queue until the
    first is done; each seat comes before its first token."""
    cfg, params = smoke
    eng = ServingEngine(params, cfg, ServeConfig(
        max_batch=1, max_new_tokens=4, max_len=64, kv_block_size=8))
    a, b = (eng.sched.request(eng.submit(p, 4)) for p in ([1, 2], [3, 4]))
    eng.run()
    m = eng.metrics()
    assert m.admissions == 2
    assert m.queue_wait_s >= a.done_time - b.submit_time
    assert m.queue_wait_s <= (a.first_token_time - a.submit_time) + (
        b.first_token_time - b.submit_time)


def test_cancel_and_expired():
    s = Scheduler(n_slots=1)
    a = s.submit([1], 4, now=0.0, deadline_ms=50.0)
    b = s.submit([2], 4, now=0.0)  # no deadline: never expires
    assert s.expired(now=0.040) == []
    assert s.expired(now=0.060) == [a]
    s.cancel(a, "deadline", now=0.060)
    assert a.state is RequestState.DONE and a.done_reason == "deadline"
    assert s.expired(now=99.0) == []  # DONE requests never re-expire
    (req,) = s.admit()
    assert req is b


def _preempt_fixture(arch, injector=None, **kw):
    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    sc = ServeConfig(
        max_batch=2, max_new_tokens=10, max_len=64, kv_block_size=8,
        prefill_buckets=(16,), fault_injector=injector, **kw,
    )
    return cfg, params, ServingEngine(params, cfg, sc)


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_preempt_restore_byte_identity(arch):
    """The spill/restore acceptance contract: a request preempted
    mid-decode (pages spilled to host, slot freed, later restored through
    the normal admission gate) must emit a token stream BYTE-identical to
    an un-preempted run — attention-only and hybrid recurrent families."""
    from repro.serving import FaultInjector

    inj = FaultInjector().at(4, "preempt").at(8, "preempt")
    cfg, params, eng = _preempt_fixture(arch, injector=inj)
    prompts = [list(range(1, 10)), list(range(2, 14))]
    rids = [eng.submit(p, 10) for p in prompts]
    out = eng.run()
    m = eng.metrics()
    assert m.preemptions == 2 and m.restores == 2
    assert inj.applied and all(k == "preempt" for _, k, _ in inj.applied)

    _, _, ref = _preempt_fixture(arch)
    ref_rids = [ref.submit(p, 10) for p in prompts]
    ref_out = ref.run()
    for r, rr in zip(rids, ref_rids):
        assert out[r] == ref_out[rr], arch


def test_preempt_restore_compile_counts():
    """Spill, restore, and slot-state gather are one compile each — page
    ids are fixed-width (trash-padded), so every preemption depth reuses
    the same trace; a repeat preemption compiles nothing new."""
    from repro.serving import FaultInjector

    inj = FaultInjector().at(3, "preempt").at(7, "preempt")
    _, _, eng = _preempt_fixture("stablelm-3b", injector=inj)
    for p in ([1, 2, 3, 4], list(range(2, 14))):
        eng.submit(p, 10)
    eng.run()
    counts = eng.compile_counts()
    assert counts["page_spill"] == 1
    assert counts["page_restore"] == 1
    assert counts["state_gather"] == 1
    assert counts["serve_step"] <= 4


def test_higher_priority_arrival_preempts_lowest(smoke):
    """A tight pool running a batch request back-pressures an interactive
    arrival; with preemption on, the batch victim spills, the interactive
    request takes the pool, and the victim restores and STILL finishes
    byte-identically."""
    cfg, params = smoke

    def run(enable):
        sc = ServeConfig(
            max_batch=1, max_new_tokens=6, max_len=64, kv_block_size=8,
            prefill_buckets=(16,), num_kv_blocks=7,
            enable_preemption=enable,
        )
        eng = ServingEngine(params, cfg, sc)
        rb = eng.submit(list(range(1, 10)), 6, priority=PRIORITY_BATCH)
        for _ in range(3):
            eng.tick()
        ri = eng.submit(
            list(range(3, 12)), 6, priority=PRIORITY_INTERACTIVE
        )
        n = 0
        while eng.sched.has_work() and n < 300:
            eng.tick()
            n += 1
        return eng, rb, ri

    eng_on, rb, ri = run(True)
    m = eng_on.metrics()
    assert m.preemptions >= 1 and m.restores >= 1
    b_on = eng_on.sched.request(rb)
    assert b_on.preemptions >= 1
    # interactive finished BEFORE the preempted batch request
    i_done = eng_on.sched.request(ri).done_time
    assert i_done is not None and i_done < b_on.done_time

    eng_off, rb2, _ = run(False)
    assert eng_off.metrics().preemptions == 0
    # the preempted run's batch stream matches the unpreempted one
    assert b_on.output == eng_off.sched.request(rb2).output


def test_uniform_priority_never_preempts(smoke):
    """A victim must have STRICTLY lower priority than the arrival —
    single-class traffic under pool pressure back-pressures (PR-3
    behavior) instead of thrashing."""
    cfg, params = smoke
    sc = ServeConfig(
        max_batch=2, max_new_tokens=6, max_len=64, kv_block_size=8,
        prefill_buckets=(16,), num_kv_blocks=7,
    )
    eng = ServingEngine(params, cfg, sc)
    for i in range(3):
        eng.submit(list(range(1 + i, 10 + i)), 6)
    eng.run()
    assert eng.metrics().preemptions == 0
    assert eng.metrics().completed == 3


def test_deadline_eviction_mid_stream(smoke):
    """A request whose deadline lapses mid-decode is evicted with reason
    ``"deadline"`` and its pool pages are reclaimed."""
    cfg, params = smoke
    sc = ServeConfig(
        max_batch=1, max_new_tokens=200, max_len=256, kv_block_size=8,
        prefill_buckets=(16,),
    )
    eng = ServingEngine(params, cfg, sc)
    rid = eng.submit(list(range(1, 10)), 200, deadline_ms=1e-3)
    eng.run()
    req = eng.sched.request(rid)
    assert req.done_reason == "deadline"
    assert eng.blocks.available == eng.blocks.capacity
    assert eng.metrics().evictions.get("deadline") == 1


def test_queued_deadline_eviction_without_slot(smoke):
    """Expiry must also reap QUEUED requests that never got a slot."""
    cfg, params = smoke
    sc = ServeConfig(
        max_batch=1, max_new_tokens=4, max_len=64, kv_block_size=8,
        prefill_buckets=(16,),
    )
    eng = ServingEngine(params, cfg, sc)
    r0 = eng.submit(list(range(1, 10)), 4)
    r1 = eng.submit(list(range(2, 12)), 4, deadline_ms=1e-3)
    eng.run()
    assert eng.sched.request(r0).done_reason == "length"
    assert eng.sched.request(r1).done_reason == "deadline"
    assert len(eng.sched.request(r1).output) == 0


def test_metrics_latency_by_class(smoke):
    """Per-priority-class TTFT/latency percentiles and the eviction-reason
    counters surface in metrics() and row()."""
    cfg, params = smoke
    sc = ServeConfig(
        max_batch=2, max_new_tokens=4, max_len=64, kv_block_size=8,
        prefill_buckets=(16,),
    )
    eng = ServingEngine(params, cfg, sc)
    eng.submit(list(range(1, 10)), 4, priority=PRIORITY_INTERACTIVE)
    eng.submit(list(range(2, 12)), 4, priority=PRIORITY_BATCH)
    eng.run()
    m = eng.metrics()
    assert set(m.latency_by_class) == {PRIORITY_INTERACTIVE, PRIORITY_BATCH}
    for cls in m.latency_by_class.values():
        assert cls["n"] == 1
        assert 0 < cls["ttft_p50_ms"] <= cls["ttft_p99_ms"]
        assert 0 < cls["latency_p50_ms"] <= cls["latency_p99_ms"]
    assert m.ttft_p50 <= m.ttft_p99
    row = m.row()
    assert "ttft_p99_ms=" in row
    # row() must surface the per-class view it used to drop: one compact
    # class=... entry per priority with its n and latency p99
    assert "class=" in row
    for pr, cls in m.latency_by_class.items():
        assert f"{pr}:n={cls['n']}" in row
    # the analog energy accounting rides along in row() too
    assert "raca_pj_per_tok=" in row and "adc1b_pj_per_tok=" in row
    # done-reason counts: both requests spent their budget normally
    assert m.evictions == {"length": 2}


def test_metrics_row_compact_renderings():
    """ServingMetrics.row() unit-level: optional sections render only when
    non-empty, with the documented compact shapes."""
    from repro.serving.engine import ServingMetrics

    bare = ServingMetrics()
    assert "class=" not in bare.row()
    assert "raca_pj_per_tok=" not in bare.row()
    m = ServingMetrics(
        latency_by_class={
            0: {"n": 2, "ttft_p50_ms": 1.0, "ttft_p99_ms": 2.0,
                "latency_p50_ms": 3.0, "latency_p99_ms": 40.0},
            1: {"n": 5, "ttft_p50_ms": 1.0, "ttft_p99_ms": 2.0,
                "latency_p50_ms": 3.0, "latency_p99_ms": 90.0},
        },
        analog={
            "raca": {"energy_pj_per_token": 123.4},
            "adc1b": {"energy_pj_per_token": 456.7},
        },
    )
    row = m.row()
    assert "class=0:n=2/p99=40ms,1:n=5/p99=90ms" in row
    assert "raca_pj_per_tok=123" in row
    assert "adc1b_pj_per_tok=457" in row


def test_preemption_rejected_on_dense(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(
            params, cfg,
            ServeConfig(kv_layout="dense", fault_injector=object()),
        )

# ---------------------------------------------------------------------------
# Self-speculative decoding (draft-k + fused verify through the paged pool)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_spec_greedy_byte_identity(arch):
    """The speculative acceptance contract: greedy decode over the mixed
    trace must be byte-identical speculate_k=4 vs plain — speculation
    changes latency, never output — for pure-attention and hybrid
    (attention + recurrent state) families."""
    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    _, out_plain = _run_layout(params, cfg, "paged")
    eng, out_spec = _run_layout(params, cfg, "paged", {"speculate_k": 4})
    assert out_plain == out_spec
    m = eng.metrics()
    assert m.spec_rounds > 0 and m.spec_drafted > 0
    # greedy drafts verify against themselves: every non-truncated draft
    # accepts, so the only losses are budget/EOS truncation mid-round
    assert m.spec_accepted <= m.spec_drafted
    assert m.spec_acceptance > 0.5


def test_spec_wta_byte_identity(smoke):
    """WTA stochastic sampling is a pure function of (slot key, step), so
    the draft run resamples the SAME votes the plain engine would have —
    stochastic streams stay byte-identical under speculation too."""
    cfg, params = smoke
    wcfg = dataclasses.replace(cfg, wta_head=True)
    _, out_plain = _run_layout(params, wcfg, "paged")
    _, out_spec = _run_layout(params, wcfg, "paged", {"speculate_k": 3})
    assert out_plain == out_spec


def test_spec_forced_rejection_mid_run(smoke):
    """Tamper with the REPORTED draft tokens (host side, after the device
    round) so the engine sees a mismatch and takes the rollback path: the
    verifier consumed the true drafts, its resample IS the plain-engine
    token, so the published stream must stay byte-identical while
    spec_rollback compiles exactly once and acceptance drops."""
    cfg, params = smoke
    _, out_plain = _run_layout(params, cfg, "paged")

    sc = ServeConfig(
        max_batch=3, max_new_tokens=8, max_len=64, kv_block_size=8,
        kv_layout="paged", speculate_k=4,
    )
    eng = ServingEngine(params, cfg, sc)
    orig = eng._spec_round
    calls = {"n": 0}

    def tampered(*a, **kw):
        cache, d, dok, v, vok, vs = orig(*a, **kw)
        calls["n"] += 1
        if calls["n"] % 2 == 0:  # every other round rejects at step 1
            d = np.asarray(d).copy()
            d[:, 1] ^= 1
        return cache, d, dok, v, vok, vs

    eng._spec_round = tampered
    for p, b in zip(MIXED_PROMPTS, MIXED_BUDGETS):
        eng.submit(p, b)
    out_spec = eng.run()
    eng._spec_round = orig  # compile_counts reads the jitted entry point
    assert out_plain == out_spec
    m = eng.metrics()
    assert calls["n"] >= 2
    assert m.spec_accepted < m.spec_drafted  # rejections really happened
    assert eng.compile_counts()["spec_rollback"] == 1


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_spec_preempt_restore_byte_identity(arch):
    """Preempting a SPECULATING slot (pages spilled between rounds, slot
    freed, restored through the admission gate) must not perturb the
    stream: rollback state, pos, and the drafted-KV dead rows all travel
    through spill/restore correctly."""
    from repro.serving import FaultInjector

    # speculation emits up to k tokens per TICK, so the trace drains in
    # far fewer ticks than the plain preempt test — inject early, and a
    # late event that finds nothing left to spill is fine (>= 1 applied)
    inj = FaultInjector().at(1, "preempt").at(3, "preempt")
    cfg, params, eng = _preempt_fixture(arch, injector=inj, speculate_k=3)
    prompts = [list(range(1, 10)), list(range(2, 14))]
    rids = [eng.submit(p, 10) for p in prompts]
    out = eng.run()
    m = eng.metrics()
    assert m.preemptions >= 1 and m.restores == m.preemptions
    assert m.spec_rounds > 0

    _, _, ref = _preempt_fixture(arch)  # plain, unpreempted oracle
    ref_rids = [ref.submit(p, 10) for p in prompts]
    ref_out = ref.run()
    for r, rr in zip(rids, ref_rids):
        assert out[r] == ref_out[rr], arch


def test_spec_sharded_1x1_mesh_byte_identity(smoke):
    """The mesh-aware speculative entry points (spec_round/spec_rollback
    from make_sharded_paged_entry_points) produce the same stream as the
    unsharded jits on a degenerate 1x1 mesh."""
    from repro.launch.mesh import make_host_mesh

    cfg, params = smoke
    _, base = _run_layout(params, cfg, "paged", {"speculate_k": 4})
    _, shard = _run_layout(
        params, cfg, "paged",
        {"speculate_k": 4, "mesh": make_host_mesh(model=1, data=1)},
    )
    assert base == shard


def test_spec_recompile_guard(smoke):
    """One spec_round compile per decode-window width (the same
    power-of-two bucketing as serve_step), zero rollback compiles on a
    fault-free greedy trace, and a second identical trace through the
    same engine compiles nothing new."""
    cfg, params = smoke
    eng, _ = _run_layout(params, cfg, "paged", {"speculate_k": 4})
    counts = eng.compile_counts()
    assert 1 <= counts["spec_round"] <= 4
    assert counts["spec_rollback"] == 0  # greedy drafts never reject
    for p, b in zip(MIXED_PROMPTS, MIXED_BUDGETS):
        eng.submit(p, b)
    eng.run()
    assert eng.compile_counts() == counts, "steady-state trace recompiled"


def test_spec_validation_is_loud(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="speculate_k"):
        ServingEngine(params, cfg, ServeConfig(speculate_k=-1))
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(
            params, cfg,
            ServeConfig(kv_layout="dense", speculate_k=2),
        )
    with pytest.raises(ValueError, match="max_new_tokens"):
        ServingEngine(
            params, cfg,
            ServeConfig(speculate_k=8, max_new_tokens=8),
        )


# ---------------------------------------------------------------------------
# Spill-store bytes budget (LRU drop + recompute-from-prompt restore)
# ---------------------------------------------------------------------------


def test_spill_budget_validation_is_loud(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError, match="spill_budget_bytes"):
        ServingEngine(params, cfg, ServeConfig(spill_budget_bytes=-1))
    with pytest.raises(ValueError, match="dense"):
        ServingEngine(
            params, cfg,
            ServeConfig(kv_layout="dense", spill_budget_bytes=1 << 20),
        )


def test_spill_budget_drop_recomputes_byte_identical(smoke):
    """A zero budget drops EVERY spill record at insertion: the preempted
    victim restores through the fresh-admission gate (full prompt
    recompute + teacher-forced replay of its published tokens) and still
    finishes byte-identical to an unpreempted run."""
    from repro.serving import FaultInjector

    cfg, params = smoke
    inj = FaultInjector().at(4, "preempt").at(8, "preempt")
    _, _, eng = _preempt_fixture(
        "stablelm-3b", injector=inj, spill_budget_bytes=0
    )
    prompts = [list(range(1, 10)), list(range(2, 14))]
    rids = [eng.submit(p, 10) for p in prompts]
    out = eng.run()
    m = eng.metrics()
    assert m.preemptions == 2 and m.spill_drops == 2
    assert eng._spill == {} and eng._spill_bytes == 0

    _, _, ref = _preempt_fixture("stablelm-3b")
    ref_rids = [ref.submit(p, 10) for p in prompts]
    ref_out = ref.run()
    for r, rr in zip(rids, ref_rids):
        assert out[r] == ref_out[rr]


def test_spill_budget_keeps_newest_drops_oldest(smoke):
    """With a budget sized for ONE record, a tick that spills two victims
    keeps only the newer one: the second insertion drops the first
    (oldest — dict insertion order).  The kept victim restores from its
    host pages (counted in ``restores``); the dropped one re-admits
    through the fresh gate and replays — both streams stay byte-identical
    to an unpreempted run."""
    from repro.serving import FaultInjector

    cfg, params = smoke
    # size the budget by spying on the store at insertion time — a
    # spilled victim restores through the admission gate later in the
    # SAME tick (its slot and blocks are free again by then), so the
    # store is empty whenever tick() returns and can't be probed from
    # outside
    probe = FaultInjector().at(3, "preempt")
    _, _, peng = _preempt_fixture("stablelm-3b", injector=probe)
    sizes: list[int] = []
    orig = peng._store_spill

    def spy(rid, rec):
        orig(rid, rec)
        sizes.append(peng._spill_bytes)

    peng._store_spill = spy
    peng.submit(list(range(1, 10)), 10)
    for _ in range(4):
        peng.tick()
    assert sizes, "probe engine never spilled"
    one = sizes[0]

    # spill records are fixed-width (trash-padded page-id vectors), so
    # both victims cost exactly `one`; preempting both in one tick puts
    # the store over budget before either can restore
    inj = FaultInjector().at(3, "preempt").at(3, "preempt")
    _, _, eng = _preempt_fixture(
        "stablelm-3b", injector=inj, spill_budget_bytes=one
    )
    prompts = [list(range(1, 10)), list(range(2, 14))]
    rids = [eng.submit(p, 10) for p in prompts]
    out = eng.run()
    m = eng.metrics()
    assert m.preemptions == 2 and m.spill_drops == 1
    assert m.restores == 1  # only the kept (newest) record page-restores
    assert eng.blocks.available == eng.blocks.capacity

    _, _, ref = _preempt_fixture("stablelm-3b")
    ref_rids = [ref.submit(p, 10) for p in prompts]
    ref_out = ref.run()
    for r, rr in zip(rids, ref_rids):
        assert out[r] == ref_out[rr]


def test_spill_budget_unbounded_never_drops(smoke):
    from repro.serving import FaultInjector

    cfg, params = smoke
    inj = FaultInjector().at(4, "preempt").at(8, "preempt")
    _, _, eng = _preempt_fixture("stablelm-3b", injector=inj)
    for p in ([1, 2, 3, 4], list(range(2, 14))):
        eng.submit(p, 10)
    eng.run()
    m = eng.metrics()
    assert m.preemptions == 2 and m.spill_drops == 0
