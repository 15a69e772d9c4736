"""Shape contracts for the serving specs layer: `decode_cache_specs`,
`make_serve_step`, and the slot-addressable cache insert.  Cache-layout
refactors must fail HERE, loudly, instead of surfacing as silent XLA
recompiles or wrong-slot writes in the serving engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.shapes import ShapeSpec
from repro.launch import specs as SP

B, S = 4, 32
DSHAPE = ShapeSpec("d", S, B, "decode")


def _tree_specs(tree):
    return jax.tree.map(lambda l: (tuple(l.shape), jnp.dtype(l.dtype)), tree)


@pytest.mark.parametrize(
    "arch", ["stablelm-3b", "recurrentgemma-2b", "mamba2-1.3b"]
)
def test_decode_cache_batch_axis_contract(arch):
    """Every cache leaf carries the request/slot axis where
    `cache_batch_axis` says it is — the invariant slot insertion needs."""
    cfg = get_smoke_config(arch)
    specs = SP.decode_cache_specs(cfg, DSHAPE)
    assert "pos" in specs
    assert specs["pos"].shape == (B,)
    assert specs["pos"].dtype == jnp.int32
    for name, leaf in specs.items():
        ax = SP.cache_batch_axis(cfg, name)
        assert leaf.shape[ax] == B, (arch, name, leaf.shape, ax)


def test_decode_cache_attn_layout():
    cfg = get_smoke_config("stablelm-3b")
    specs = SP.decode_cache_specs(cfg, DSHAPE)
    n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
    want = (cfg.n_units, n_attn, B, S, cfg.n_kv_heads, cfg.head_dim)
    assert specs["k"].shape == want
    assert specs["v"].shape == want


def test_init_decode_cache_matches_specs():
    cfg = get_smoke_config("stablelm-3b")
    live = SP.init_decode_cache(cfg, B, S)
    assert _tree_specs(live) == _tree_specs(SP.decode_cache_specs(cfg, DSHAPE))


@pytest.mark.parametrize("wta", [False, True])
def test_serve_step_shape_contract(wta):
    """(params, cache, token(B,)) -> (cache, token(B,)): the output cache
    must have exactly the input cache's specs (donation + no recompile)."""
    cfg = dataclasses.replace(get_smoke_config("stablelm-3b"), wta_head=wta)
    ps = SP.params_specs(cfg)
    cs = SP.decode_cache_specs(cfg, DSHAPE)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    step = SP.make_serve_step(cfg)
    out_cache, out_tok = jax.eval_shape(step, ps, cs, tok)
    assert _tree_specs(out_cache) == _tree_specs(cs)
    assert out_tok.shape == (B,)
    assert out_tok.dtype == jnp.int32


def test_serve_step_per_slot_key_contract():
    """Per-slot PRNG path: keys (B, 2) + step counters (B,) keep the same
    (cache, token) output contract."""
    cfg = dataclasses.replace(get_smoke_config("stablelm-3b"), wta_head=True)
    ps = SP.params_specs(cfg)
    cs = SP.decode_cache_specs(cfg, DSHAPE)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32)
    steps = jax.ShapeDtypeStruct((B,), jnp.int32)
    out_cache, out_tok = jax.eval_shape(
        SP.make_serve_step(cfg), ps, cs, tok, keys, steps
    )
    assert _tree_specs(out_cache) == _tree_specs(cs)
    assert out_tok.shape == (B,)


def test_cache_insert_writes_only_the_target_slot():
    cfg = get_smoke_config("stablelm-3b")
    batch_cache = SP.init_decode_cache(cfg, B, S)
    one = jax.tree.map(
        lambda l: jnp.full_like(l, 7), SP.init_decode_cache(cfg, 1, S)
    )
    insert = jax.jit(SP.make_cache_insert(cfg))
    out = insert(batch_cache, one, 2)
    assert _tree_specs(out) == _tree_specs(batch_cache)
    for name, leaf in out.items():
        ax = SP.cache_batch_axis(cfg, name)
        arr = np.moveaxis(np.asarray(leaf, np.float32), ax, 0)
        np.testing.assert_array_equal(arr[2], 7)
        np.testing.assert_array_equal(arr[[0, 1, 3]], 0)


def test_cache_insert_slot_index_is_traced():
    """One compile serves every slot index — insertion must not specialize
    on the slot value (that would recompile per refill)."""
    cfg = get_smoke_config("stablelm-3b")
    batch_cache = SP.init_decode_cache(cfg, B, S)
    one = SP.init_decode_cache(cfg, 1, S)
    insert = jax.jit(SP.make_cache_insert(cfg))
    for slot in range(B):
        insert(batch_cache, one, slot)
    ntraces = insert._cache_size()
    assert ntraces == 1, f"cache insert recompiled {ntraces}x across slots"


# ---------------------------------------------------------------------------
# Paged (block-table) cache contracts
# ---------------------------------------------------------------------------

P, BS = 9, 8  # pool pages (page 0 = trash), tokens per block


def test_paged_cache_layout():
    """Attention K/V become shared (pool, block) leaves, their rows the
    head dim zero-padded to whole 128-lane rows (16 → 128 here); pos and
    the recurrent states keep the dense slot layout."""
    from repro.models import transformer as TF

    cfg = get_smoke_config("stablelm-3b")
    specs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
    assert [TF.paged_head_dim(d) for d in (16, 80, 128, 256)] == [
        128, 128, 128, 256
    ]
    want = (cfg.n_units, n_attn, P, BS, cfg.n_kv_heads, 128)
    assert specs["k_pages"].shape == want
    assert specs["v_pages"].shape == want
    assert specs["pos"].shape == (B,)
    assert "k" not in specs and "v" not in specs
    live = SP.init_paged_decode_cache(cfg, B, P, BS)
    assert _tree_specs(live) == _tree_specs(specs)


def test_paged_cache_hybrid_keeps_dense_state_leaves():
    cfg = get_smoke_config("recurrentgemma-2b")
    specs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    assert specs["rec_h"].shape[2] == B  # slot axis unchanged
    assert specs["k_pages"].shape[2] == P  # pool axis, not slots


def _suffix_prefill_fixture(cfg):
    """(params, paged cache, zero state, jitted chunk fn) for one arch."""
    from repro.models import get_model_fns

    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    cache = SP.init_paged_decode_cache(cfg, B, P, BS)
    state = SP.init_prefill_state(cfg)
    fn = jax.jit(
        SP.make_paged_suffix_prefill(cfg), static_argnames=("bucket",)
    )
    return params, cache, state, fn


def test_suffix_prefill_writes_only_covered_pages():
    """A chunk's K/V land in exactly the pages its blocks cover; pages of
    other blocks (and every per-slot batch-cache leaf) stay untouched;
    the returned state carries the advanced position."""
    cfg = get_smoke_config("stablelm-3b")
    params, cache, state, fn = _suffix_prefill_fixture(cfg)
    bucket = 2 * BS
    toks = jnp.arange(1, bucket + 1, dtype=jnp.int32)[None]
    row = jnp.asarray([3, 5], jnp.int32)
    # first chunk covers block 0 only -> page 3 written, page 5 not yet
    out, st1, _ = fn(
        params, cache, state, toks[:, :BS], row,
        jnp.asarray(0, jnp.int32), bucket=bucket,
    )
    kp = np.asarray(out["k_pages"], np.float32)
    assert np.abs(kp[:, :, 3]).sum() > 0
    untouched = [p for p in range(P) if p != 3]
    np.testing.assert_array_equal(kp[:, :, untouched], 0)
    assert np.asarray(st1["pos"])[0] == BS
    # the batch cache's per-slot leaves ride along untouched: a prefill
    # in flight can never be corrupted by interleaved decode steps
    np.testing.assert_array_equal(np.asarray(out["pos"]), 0)
    # second chunk resumes at q0=BS and fills page 5
    out2, st2, logits = fn(
        params, out, st1, toks[:, BS:], row,
        jnp.asarray(BS, jnp.int32), bucket=bucket,
    )
    kp2 = np.asarray(out2["k_pages"], np.float32)
    assert np.abs(kp2[:, :, 5]).sum() > 0
    np.testing.assert_array_equal(
        kp2[:, :, [p for p in range(P) if p not in (3, 5)]], 0
    )
    assert np.asarray(st2["pos"])[0] == bucket
    assert logits.shape == (1, cfg.vocab)


def test_suffix_prefill_matches_monolithic_prefill():
    """THE equivalence anchor: one whole-bucket chunk from zeroed state
    writes bit-identical K/V to the monolithic lm_prefill and returns
    bit-identical last-token logits — which is why dense-vs-paged (and
    sharing-on-vs-off) greedy decode stays byte-identical."""
    from repro.models import transformer as TF

    for arch in ("stablelm-3b", "recurrentgemma-2b"):
        cfg = get_smoke_config(arch)
        params, cache, state, fn = _suffix_prefill_fixture(cfg)
        bucket = 2 * BS
        toks = (jnp.arange(bucket, dtype=jnp.int32) % 97 + 1)[None]
        row = jnp.asarray([4, 2], jnp.int32)
        out, st, logits = fn(
            params, cache, state, toks, row,
            jnp.asarray(0, jnp.int32), bucket=bucket,
        )
        ref_cache, ref_logits = TF.lm_prefill(params, toks, cfg, bucket)
        dh = cfg.head_dim
        kb = np.asarray(out["k_pages"])[:, :, [4, 2]]  # (nu,na,2,BS,H,Dp)
        ref_k = np.asarray(ref_cache["k"])[:, :, 0].reshape(
            kb.shape[:-1] + (dh,)
        )
        np.testing.assert_array_equal(kb[..., :dh], ref_k)
        np.testing.assert_array_equal(kb[..., dh:], 0)  # zero lane padding
        vb = np.asarray(out["v_pages"])[:, :, [4, 2]]
        ref_v = np.asarray(ref_cache["v"])[:, :, 0].reshape(ref_k.shape)
        np.testing.assert_array_equal(vb[..., :dh], ref_v)
        np.testing.assert_array_equal(vb[..., dh:], 0)
        np.testing.assert_array_equal(
            np.asarray(logits), np.asarray(ref_logits)
        )
        for name, leaf in st.items():
            if name == "pos":
                assert int(np.asarray(leaf)[0]) == bucket
            else:
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(ref_cache[name])
                )


def test_suffix_prefill_start_and_pages_are_traced():
    """One compile serves every (start position, page set) of a given
    (bucket, chunk shape) — resume points and allocator page choices must
    not specialize the trace."""
    cfg = get_smoke_config("stablelm-3b")
    params, cache, state, fn = _suffix_prefill_fixture(cfg)
    bucket = 2 * BS
    toks = jnp.ones((1, BS), jnp.int32)
    for q0, row in ((0, [3, 5]), (BS, [3, 5]), (BS, [6, 1]), (0, [2, 4])):
        cache, state, _ = fn(
            params, cache, state, toks, jnp.asarray(row, jnp.int32),
            jnp.asarray(q0, jnp.int32), bucket=bucket,
        )
    ntraces = fn._cache_size()
    assert ntraces == 1, f"suffix prefill recompiled {ntraces}x"


@pytest.mark.parametrize("wta", [False, True])
def test_paged_serve_step_shape_contract(wta):
    """(params, cache, table(B,W), token(B,)) -> (cache, token, sane):
    output cache specs must equal the input's (donation + no recompile);
    sane is the per-slot int32 sanity code the engine's logit guard reads
    (0 = ok, nonzero = typed eviction reason)."""
    cfg = dataclasses.replace(get_smoke_config("stablelm-3b"), wta_head=wta)
    ps = SP.params_specs(cfg)
    cs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    tbl = jax.ShapeDtypeStruct((B, 2), jnp.int32)
    args = [ps, cs, tbl, tok]
    if wta:
        args += [
            jax.ShapeDtypeStruct((B, 2), jnp.uint32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ]
    out_cache, out_tok, out_ok = jax.eval_shape(
        SP.make_paged_serve_step(cfg), *args
    )
    assert _tree_specs(out_cache) == _tree_specs(cs)
    assert out_tok.shape == (B,)
    assert out_tok.dtype == jnp.int32
    assert out_ok.shape == (B,)
    assert out_ok.dtype == jnp.int32


def test_paged_serve_step_rejects_encdec():
    cfg = get_smoke_config("whisper-small")
    with pytest.raises(ValueError, match="token-LM"):
        SP.make_paged_serve_step(cfg)
    with pytest.raises(ValueError, match="token-LM"):
        SP.make_paged_suffix_prefill(cfg)


def test_suffix_prefill_shape_contract():
    """(params, cache, state, tokens, row, q0) -> (cache, state, logits):
    output cache and state specs must equal the inputs' (cache donation +
    state threading across chunks rely on it)."""
    cfg = get_smoke_config("recurrentgemma-2b")
    ps = SP.params_specs(cfg)
    cs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    ss = jax.eval_shape(lambda: SP.init_prefill_state(cfg))
    out_cache, out_state, logits = jax.eval_shape(
        lambda p, c, s, t, r, q: SP.make_paged_suffix_prefill(cfg)(
            p, c, s, t, r, q, bucket=2 * BS
        ),
        ps, cs, ss,
        jax.ShapeDtypeStruct((1, BS), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    assert _tree_specs(out_cache) == _tree_specs(cs)
    assert _tree_specs(out_state) == _tree_specs(ss)
    assert logits.shape == (1, cfg.vocab)


def test_paged_cache_int8_layout():
    """int8 pools: K/V pages hold int8 codes and grow per-(page,
    slot-in-page, head) f32 scale planes; everything else keeps the bf16
    pool's layout."""
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-3b"), kv_cache_dtype="int8"
    )
    specs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
    want = (cfg.n_units, n_attn, P, BS, cfg.n_kv_heads, 128)
    assert specs["k_pages"].shape == want
    assert specs["k_pages"].dtype == jnp.int8
    assert specs["v_pages"].dtype == jnp.int8
    assert specs["k_scale_pages"].shape == want[:-1]
    assert specs["k_scale_pages"].dtype == jnp.float32
    assert specs["v_scale_pages"].shape == want[:-1]
    live = SP.init_paged_decode_cache(cfg, B, P, BS)
    assert _tree_specs(live) == _tree_specs(specs)


@pytest.mark.parametrize("wta", [False, True])
def test_int8_paged_serve_step_shape_contract(wta):
    """The int8 pool keeps the (params, cache, table, token) -> (cache,
    token) contract with output cache specs equal to the input's — codes
    AND scale planes (donation + no recompile)."""
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-3b"), kv_cache_dtype="int8", wta_head=wta
    )
    ps = SP.params_specs(cfg)
    cs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    tbl = jax.ShapeDtypeStruct((B, 2), jnp.int32)
    args = [ps, cs, tbl, tok]
    if wta:
        args += [
            jax.ShapeDtypeStruct((B, 2), jnp.uint32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ]
    out_cache, out_tok, out_ok = jax.eval_shape(
        SP.make_paged_serve_step(cfg), *args
    )
    assert _tree_specs(out_cache) == _tree_specs(cs)
    assert out_tok.shape == (B,)
    assert out_ok.shape == (B,) and out_ok.dtype == jnp.int32


def test_page_spill_restore_shape_contract():
    """spill: (cache, ids(W,)) -> {pool leaf: rows}; restore scatters the
    payload back and must return cache specs equal to the input's
    (donation); gather: (cache, slot) -> the exact init_prefill_state
    pytree, so state_insert reuses its one compile on restore."""
    cfg = get_smoke_config("recurrentgemma-2b")
    cs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    ids = jax.ShapeDtypeStruct((3,), jnp.int32)
    payload = jax.eval_shape(SP.make_page_spill(cfg), cs, ids)
    assert set(payload) == {k for k in SP.PAGE_POOL_LEAVES if k in cs}
    for name, rows in payload.items():
        want = list(cs[name].shape)
        want[2] = 3
        assert rows.shape == tuple(want), (name, rows.shape)
    out = jax.eval_shape(SP.make_page_restore(cfg), cs, ids, payload)
    assert _tree_specs(out) == _tree_specs(cs)
    state = jax.eval_shape(
        SP.make_slot_state_gather(cfg), cs, jax.ShapeDtypeStruct((), jnp.int32)
    )
    ref = jax.eval_shape(lambda: SP.init_prefill_state(cfg))
    assert _tree_specs(state) == _tree_specs(ref)


def test_int8_spill_payload_excludes_global_quant_step():
    """The int8 pool's stochastic-rounding step counter is a GLOBAL
    scalar, not per-slot state — the slot gather must skip it (restoring
    it would replay other slots' rounding draws)."""
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-3b"), kv_cache_dtype="int8"
    )
    cs = SP.paged_decode_cache_specs(cfg, B, P, BS)
    assert "quant_step" in cs
    state = jax.eval_shape(
        SP.make_slot_state_gather(cfg), cs, jax.ShapeDtypeStruct((), jnp.int32)
    )
    assert "quant_step" not in state
    # but the scale planes DO spill with the pages
    payload = jax.eval_shape(
        SP.make_page_spill(cfg), cs, jax.ShapeDtypeStruct((2,), jnp.int32)
    )
    assert "k_scale_pages" in payload and "v_scale_pages" in payload


def test_int8_suffix_prefill_quantizes_into_covered_pages():
    """A chunk lands as int8 codes + scales in exactly the pages it
    covers; untouched pages keep zero codes and unit scales; the first
    unit's dequantized codes reconstruct the full-precision K the
    monolithic prefill computes within one scale step (the
    stochastic-rounding error bound).  Only unit 0 is compared: the
    chunked int8 prefill attends against the already-quantized pages, so
    deeper units' K legitimately absorb upstream quantization error —
    exactly what their decode-time readers see (engine-level agreement is
    pinned at token level by tests/test_serving.py)."""
    from repro.models import transformer as TF

    cfg = dataclasses.replace(
        get_smoke_config("stablelm-3b"), kv_cache_dtype="int8"
    )
    fp_cfg = dataclasses.replace(cfg, kv_cache_dtype="same")
    params, cache, state, fn = _suffix_prefill_fixture(cfg)
    bucket = 2 * BS
    toks = (jnp.arange(bucket, dtype=jnp.int32) % 89 + 1)[None]
    row = jnp.asarray([3, 5], jnp.int32)
    seeds = jnp.asarray([7, 9], jnp.uint32)  # per-block content seeds
    out, st, _ = fn(
        params, cache, state, toks, row,
        jnp.asarray(0, jnp.int32), seeds, bucket=bucket,
    )
    kp = np.asarray(out["k_pages"], np.float32)
    np.testing.assert_array_equal(kp[..., cfg.head_dim:], 0)  # lane padding
    kp = kp[..., : cfg.head_dim]
    ks = np.asarray(out["k_scale_pages"], np.float32)
    untouched = [p for p in range(P) if p not in (3, 5)]
    np.testing.assert_array_equal(kp[:, :, untouched], 0)
    np.testing.assert_array_equal(ks[:, :, untouched], 1.0)
    ref_cache, _ = TF.lm_prefill(params, toks, fp_cfg, bucket)
    nu, na, _, L, hkv, dh = ref_cache["k"].shape
    src = np.asarray(ref_cache["k"], np.float32)[:, :, 0].reshape(
        nu, na, 2, BS, hkv, dh
    )
    deq = kp[:, :, [3, 5]] * ks[:, :, [3, 5], ..., None] / 127.0
    step = ks[:, :, [3, 5], ..., None] / 127.0
    assert np.all(np.abs(deq - src)[0] <= step[0] + 1e-6)
    # the scale plane is the per-row max |K| of the same unit-0 source
    sc_ref = np.maximum(np.abs(src).max(-1), 1e-6)
    np.testing.assert_allclose(
        ks[:, :, [3, 5]][0], sc_ref[0], rtol=1e-6
    )
    assert np.asarray(st["pos"])[0] == bucket


def test_int8_suffix_prefill_seeds_are_content_positional():
    """The prefix-sharing contract on the quantizer: a block's codes are
    a function of (block content at position, block seed, layer) ONLY —
    not of what the rest of the prompt is.  Two prefills agreeing on
    block 0 (same tokens, same seed) write bit-identical codes for it
    even though their second blocks differ; the same seed on different
    content must not."""
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-3b"), kv_cache_dtype="int8"
    )
    params, cache, state, fn = _suffix_prefill_fixture(cfg)
    bucket = 2 * BS
    toks_a = jnp.concatenate(
        [jnp.arange(1, BS + 1), jnp.arange(30, 30 + BS)]
    ).astype(jnp.int32)[None]
    toks_b = jnp.concatenate(
        [jnp.arange(1, BS + 1), jnp.arange(60, 60 + BS)]
    ).astype(jnp.int32)[None]
    seeds = jnp.asarray([7, 9], jnp.uint32)
    out_a, _, _ = fn(
        params, cache, state, toks_a, jnp.asarray([1, 2], jnp.int32),
        jnp.asarray(0, jnp.int32), seeds, bucket=bucket,
    )
    out_b, _, _ = fn(
        params, cache, state, toks_b, jnp.asarray([3, 4], jnp.int32),
        jnp.asarray(0, jnp.int32), seeds, bucket=bucket,
    )
    np.testing.assert_array_equal(
        np.asarray(out_a["k_pages"])[:, :, 1],
        np.asarray(out_b["k_pages"])[:, :, 3],
    )
    np.testing.assert_array_equal(
        np.asarray(out_a["v_pages"])[:, :, 1],
        np.asarray(out_b["v_pages"])[:, :, 3],
    )
    # same seed, different content → different codes (sanity)
    assert not np.array_equal(
        np.asarray(out_a["k_pages"])[:, :, 2],
        np.asarray(out_b["k_pages"])[:, :, 4],
    )


def test_int8_suffix_prefill_seeds_are_traced():
    """One compile serves every (page set, start, per-block seed vector)
    — the stochastic-rounding seeds must not trigger per-request
    recompiles."""
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-3b"), kv_cache_dtype="int8"
    )
    params, cache, state, fn = _suffix_prefill_fixture(cfg)
    for i in range(3):
        cache, state, _ = fn(
            params, cache, state, jnp.ones((1, BS), jnp.int32),
            jnp.asarray([i + 1], jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray([i * 13 + 1], jnp.uint32), bucket=BS,
        )
    ntraces = fn._cache_size()
    assert ntraces == 1, f"int8 suffix prefill recompiled {ntraces}x"


# ---------------------------------------------------------------------------
# Prefix-sharing entry points (state insert + COW page copy)
# ---------------------------------------------------------------------------


def test_paged_state_insert_writes_only_dense_leaves_at_slot():
    """The full-hit admission path: per-slot leaves (pos, recurrent
    states) land at the slot, the shared page pools are untouched."""
    cfg = get_smoke_config("recurrentgemma-2b")
    cache = SP.init_paged_decode_cache(cfg, B, P, BS)
    one = SP.init_decode_cache(cfg, 1, BS)
    state = {
        n: jnp.full_like(v, 7)
        for n, v in one.items() if n not in ("k", "v")
    }
    insert = jax.jit(SP.make_paged_state_insert(cfg))
    out = insert(cache, state, 2)
    for name in state:
        ax = SP.cache_batch_axis(cfg, name)
        arr = np.moveaxis(np.asarray(out[name], np.float32), ax, 0)
        np.testing.assert_array_equal(arr[2], 7)
        np.testing.assert_array_equal(arr[[0, 1, 3]], 0)
    np.testing.assert_array_equal(np.asarray(out["k_pages"]), 0)
    np.testing.assert_array_equal(np.asarray(out["v_pages"]), 0)


def test_paged_state_insert_slot_is_traced():
    cfg = get_smoke_config("stablelm-3b")
    cache = SP.init_paged_decode_cache(cfg, B, P, BS)
    one = SP.init_decode_cache(cfg, 1, BS)
    state = {n: v for n, v in one.items() if n not in ("k", "v")}
    insert = jax.jit(SP.make_paged_state_insert(cfg))
    for slot in range(B):
        insert(cache, state, slot)
    ntraces = insert._cache_size()
    assert ntraces == 1, f"state insert recompiled {ntraces}x"


@pytest.mark.parametrize("int8", [False, True])
def test_page_copy_copies_every_pool_leaf(int8):
    """The device half of a COW fork: page dst becomes a bit-copy of page
    src on every pool leaf (codes AND scale planes for int8), and no other
    page or per-slot leaf moves."""
    cfg = get_smoke_config("stablelm-3b")
    if int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    cache = SP.init_paged_decode_cache(cfg, B, P, BS)
    pool_names = [n for n in cache if n.endswith("_pages")]
    for i, n in enumerate(pool_names):
        fill = jnp.arange(cache[n].size, dtype=jnp.float32).reshape(
            cache[n].shape
        ) % 113 + i
        cache[n] = fill.astype(cache[n].dtype)
    before = {n: np.asarray(cache[n]) for n in cache}
    copy = jax.jit(SP.make_page_copy(cfg))
    out = copy(cache, 3, 5)
    for n in pool_names:
        arr = np.asarray(out[n])
        np.testing.assert_array_equal(arr[:, :, 5], before[n][:, :, 3])
        others = [p for p in range(P) if p != 5]
        np.testing.assert_array_equal(arr[:, :, others], before[n][:, :, others])
    for n in cache:
        if n not in pool_names:
            np.testing.assert_array_equal(np.asarray(out[n]), before[n])


def test_page_copy_page_ids_are_traced():
    cfg = get_smoke_config("stablelm-3b")
    cache = SP.init_paged_decode_cache(cfg, B, P, BS)
    copy = jax.jit(SP.make_page_copy(cfg))
    for src, dst in ((1, 2), (3, 4), (5, 1)):
        cache = copy(cache, src, dst)
    ntraces = copy._cache_size()
    assert ntraces == 1, f"page copy recompiled {ntraces}x"


def test_sample_tokens_greedy_and_legacy_key():
    cfg = get_smoke_config("stablelm-3b")
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, cfg.vocab))
    toks = SP.sample_tokens(cfg, logits)  # no key -> argmax
    np.testing.assert_array_equal(
        np.asarray(toks), np.asarray(jnp.argmax(logits, axis=-1))
    )
    # wta off: a provided key must be ignored
    toks2 = SP.sample_tokens(cfg, logits, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))
    # legacy single-key WTA path still returns (B,) int32
    wcfg = dataclasses.replace(cfg, wta_head=True)
    toks3 = SP.sample_tokens(wcfg, logits, jax.random.PRNGKey(1))
    assert toks3.shape == (B,)
    assert toks3.dtype == jnp.int32


# ---------------------------------------------------------------------------
# Speculative round / rollback (draft-k + expanded-batch verify)
# ---------------------------------------------------------------------------

SPEC_K = 3


def _spec_fixture(arch="stablelm-3b"):
    """A B=3 paged cache with slot 0 prefilled (pages [1, 2], 8-token
    prompt) and slots 1-2 parked on the trash page."""
    from repro.models import get_model_fns

    cfg = get_smoke_config(arch)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    nb, ps = 3, 8
    cache = SP.init_paged_decode_cache(cfg, nb, ps, BS)
    prefill = jax.jit(
        SP.make_paged_suffix_prefill(cfg), static_argnames=("bucket",)
    )
    prompt = [5, 3, 7, 2, 9, 4, 6, 8]
    cache, st, _ = prefill(
        params, cache, SP.init_prefill_state(cfg),
        jnp.asarray([prompt], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.int32(0), bucket=8,
    )
    cache = jax.jit(SP.make_paged_state_insert(cfg))(cache, st, jnp.int32(0))
    table = jnp.asarray([[1, 2], [0, 0], [0, 0]], jnp.int32)
    token = jnp.asarray([7, 0, 0], jnp.int32)
    keys = jnp.zeros((nb, 2), jnp.uint32)
    steps = jnp.zeros((nb,), jnp.int32)
    return cfg, params, cache, table, token, keys, steps


@pytest.mark.parametrize("arch", ["stablelm-3b", "recurrentgemma-2b"])
def test_spec_round_matches_plain_chain(arch):
    """Contract of the fused round: drafts are bitwise the k chained
    plain decode steps, the greedy verify resamples the drafts exactly
    (fault-free rounds accept everything), vstates carries the per-step
    states, and the returned cache equals the plain chain's end state."""
    from repro.models import transformer as TF

    cfg, params, cache, table, token, keys, steps = _spec_fixture(arch)
    rnd = jax.jit(SP.make_paged_spec_round(cfg, SPEC_K))
    out_cache, d, dok, v, vok, vs = rnd(
        params, cache, table, token, keys, steps
    )
    assert d.shape == v.shape == dok.shape == vok.shape == (3, SPEC_K)
    for leaf in vs.values():
        assert leaf.shape[0] == SPEC_K
    assert np.asarray(dok).all() and np.asarray(vok).all()
    np.testing.assert_array_equal(np.asarray(v), np.asarray(d))

    step = jax.jit(
        lambda p, c, t: TF.lm_decode_step(p, c, t, cfg, table)
    )
    c, t = cache, token
    for j in range(SPEC_K):
        c, logits = step(params, c, t)
        t = SP.sample_tokens(cfg, logits, keys, steps + j)
        np.testing.assert_array_equal(np.asarray(t), np.asarray(d[:, j]))
        # vstates[j] = the state AFTER consuming input j — bitwise the
        # plain chain's state (pos included)
        np.testing.assert_array_equal(
            np.asarray(vs["pos"][j]), np.asarray(c["pos"])
        )
    for name in c:
        np.testing.assert_array_equal(
            np.asarray(out_cache[name]), np.asarray(c[name]), err_msg=name
        )


def test_spec_rollback_rewinds_one_slot():
    cfg, params, cache, table, token, keys, steps = _spec_fixture()
    pre_pos = np.asarray(cache["pos"]).copy()
    rnd = jax.jit(SP.make_paged_spec_round(cfg, SPEC_K))
    out_cache, *_, vs = rnd(params, cache, table, token, keys, steps)
    rb = jax.jit(SP.make_spec_rollback(cfg))
    back = rb(out_cache, vs, jnp.int32(1), jnp.int32(0))
    pos = np.asarray(back["pos"])
    assert pos[0] == pre_pos[0] + 2  # idx 1 = consumed inputs 0 and 1
    np.testing.assert_array_equal(pos[1:], np.asarray(out_cache["pos"])[1:])
    # idx and slot are traced: every (idx, slot) pair reuses one trace
    back = rb(back, vs, jnp.int32(0), jnp.int32(2))
    assert rb._cache_size() == 1


def test_decode_step_kv_write_false_is_read_only():
    """The verify cell: run the writing step once (the 'draft' — it lands
    the token's K/V row in the pool), then re-decode the same position
    read-only from the written pool + the pre-step dense state.  Logits
    must match bitwise and the returned cache must carry only dense
    per-slot leaves (no pool pages, no quant_step tick)."""
    from repro.models import transformer as TF

    cfg, params, cache, table, token, keys, steps = _spec_fixture()
    wr = jax.jit(lambda p, c, t: TF.lm_decode_step(p, c, t, cfg, table))
    ro = jax.jit(
        lambda p, c, t: TF.lm_decode_step(
            p, c, t, cfg, table, kv_write=False
        )
    )
    c_wr, lg_wr = wr(params, cache, token)
    # written pool + pre-step dense state = a verify row for this position
    replay = dict(c_wr)
    for name in SP._spec_state_leaves(cache):
        replay[name] = cache[name]
    c_ro, lg_ro = ro(params, replay, token)
    np.testing.assert_array_equal(np.asarray(lg_wr), np.asarray(lg_ro))
    pool = set(SP.PAGE_POOL_LEAVES) | {"quant_step"}
    assert set(c_ro) == set(replay) - (pool & set(replay))
    np.testing.assert_array_equal(
        np.asarray(c_ro["pos"]), np.asarray(c_wr["pos"])
    )


def _per_layer_decode(params, cache, token, cfg, table):
    """A decode step that hands each unit its own slice of the pool
    (the scan's ``xs``), writes and attends there, and stacks the slices
    back (``ys``): the per-layer dataflow the in-place step replaces
    (attention-only patterns)."""
    from repro.models import attention as ATT
    from repro.models import layers as L

    pos, qstep = cache["pos"], cache.get("quant_step")
    names = [n for n in SP.PAGE_POOL_LEAVES if n in cache]

    def unit(x, xs):
        up, pool, u = xs
        pool = dict(pool)
        for i, kind in enumerate(cfg.layer_pattern):
            sub = up[f"l{i}"]
            kw = {}
            if "k_scale_pages" in pool:
                kw = dict(
                    k_scale_pages=pool["k_scale_pages"][i],
                    v_scale_pages=pool["v_scale_pages"][i],
                    quant_seed=qstep.astype(jnp.uint32)
                    * jnp.uint32(2654435761)
                    + jnp.asarray(u).astype(jnp.uint32) * jnp.uint32(40503)
                    + jnp.uint32(i * 1299721),
                )
            res = ATT.paged_decode_self_attention(
                sub["attn"], L.rmsnorm(sub["ln1"], x, cfg.norm_eps),
                pool["k_pages"][i], pool["v_pages"][i], table, pos,
                cfg, kind=kind, **kw,
            )
            for n, layer in zip(names, res[1:]):
                pool[n] = pool[n].at[i].set(layer)
            a = res[0]
            if cfg.post_norms:
                a = L.rmsnorm(sub["post_ln1"], a, cfg.norm_eps)
            x = x + a
            f = L.mlp_apply(
                sub["ffn"], L.rmsnorm(sub["ln2"], x, cfg.norm_eps), cfg, None
            )
            if cfg.post_norms:
                f = L.rmsnorm(sub["post_ln2"], f, cfg.norm_eps)
            x = x + f
        return x, pool

    x = L.embed(params["embed"], token[:, None], cfg)
    pool = {n: cache[n] for n in names}
    if cfg.scan_layers:
        x, pool = jax.lax.scan(
            unit, x, (params["units"], pool, jnp.arange(cfg.n_units))
        )
    else:
        ys = []
        for u in range(cfg.n_units):
            at = lambda a: a[u]
            x, y = unit(
                x, (jax.tree.map(at, params["units"]),
                    jax.tree.map(at, pool), u)
            )
            ys.append(y)
        pool = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.logits_out(params["embed"], params.get("head"), x, cfg)
    return pool, logits[:, 0, :]


@pytest.mark.parametrize("scan", [True, False])
@pytest.mark.parametrize("kv", ["same", "int8"])
@pytest.mark.parametrize("arch", ["stablelm-3b", "gemma2-2b"])
def test_paged_decode_step_writes_the_stacked_pool_in_place(arch, kv, scan):
    """The paged decode step over 3 units (gemma2: two attention
    sublayers a unit, local and global) writes each token's K/V rows (and
    int8 scales) straight into the stacked pool: every pool entry no slot
    wrote is bit-identical after the step, and the written rows and the
    logits equal the per-layer slice / write / re-stack replay bit for
    bit (same rows, same rounding seeds, same places)."""
    from repro.models import get_model_fns
    from repro.models import transformer as TF

    base = get_smoke_config(arch)
    cfg = dataclasses.replace(
        base, n_layers=3 * len(base.layer_pattern), kv_cache_dtype=kv,
        scan_layers=scan,
    )
    assert cfg.n_units == 3
    assert set(cfg.layer_pattern) <= {"global", "local"}
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    n_pages, bs, nb = 10, 4, 4
    cache = SP.init_paged_decode_cache(cfg, nb, n_pages, bs)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for n in SP.PAGE_POOL_LEAVES:
        if n in cache:
            leaf = cache[n]
            if leaf.dtype == jnp.int8:
                val = jax.random.randint(next(keys), leaf.shape, -127, 128)
            else:
                val = jax.random.uniform(next(keys), leaf.shape, minval=0.5)
            cache[n] = val.astype(leaf.dtype)
    cache["pos"] = jnp.asarray([5, 9, 2, 7], jnp.int32)
    if "quant_step" in cache:
        cache["quant_step"] = jnp.int32(11)
    # slots 0-2 own distinct pages; slot 3 is parked on the trash page
    table = jnp.asarray(
        [[3, 1, 0], [2, 5, 9], [7, 0, 0], [-1, -1, -1]], jnp.int32
    )
    token = jnp.asarray([4, 17, 9, 0], jnp.int32)

    new, logits = jax.jit(
        lambda p, c, t: TF.lm_decode_step(p, c, t, cfg, table)
    )(params, dict(cache), token)
    want_pool, want_logits = jax.jit(
        lambda p, c, t: _per_layer_decode(p, c, t, cfg, table)
    )(params, dict(cache), token)

    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    pos, tbl = np.asarray(cache["pos"]), np.maximum(np.asarray(table), 0)
    pages = tbl[np.arange(nb), pos // bs]
    written = np.zeros((n_pages, bs), bool)
    written[pages, pos % bs] = True
    assert written.sum() == nb
    for n in SP.PAGE_POOL_LEAVES:
        if n not in cache:
            continue
        before = np.asarray(cache[n])
        after = np.asarray(new[n])
        assert after.shape == before.shape and after.dtype == before.dtype
        np.testing.assert_array_equal(
            after[:, :, ~written], before[:, :, ~written], err_msg=n
        )
        np.testing.assert_array_equal(
            after[:, :, written], np.asarray(want_pool[n])[:, :, written],
            err_msg=n,
        )
        assert (after[:, :, written] != before[:, :, written]).any(), n


def test_spec_factories_reject_bad_args():
    cfg = get_smoke_config("stablelm-3b")
    with pytest.raises(ValueError, match="speculate_k"):
        SP.make_paged_spec_round(cfg, 0)
    encdec = get_smoke_config("whisper-small")
    with pytest.raises(ValueError, match="token-LM"):
        SP.make_paged_spec_round(encdec, 2)
    with pytest.raises(ValueError, match="token-LM"):
        SP.make_spec_rollback(encdec)
