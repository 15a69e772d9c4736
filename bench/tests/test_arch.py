"""Architectures found by name (``bench/arch/<name>.py``).

The dense one binds the benchmark's own functions, so the cells on it
read what they read before: its weights, reference, control and counts
are checked bit for bit against the functions it binds.  A test
architecture under ``data/arch/`` (sliding-window and full layers,
sparse experts in every layer) plugs in with files alone and runs
through the whole harness on the CPU: a sound run is correct, and a
reference that forgets the window or a program that routes from the
wrong scores is not."""

import functools
import os
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import reference as REF
from bench import roofline as RL
from bench import spec as SPEC
from bench import weights as W

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = "tiny-moe-window"
SEED = 3


def _load(name: str) -> dict:
    return SPEC.load_json(os.path.join(DATA, name + ".json"))


# -- dense is the benchmark's own code ---------------------------------------


@functools.lru_cache(maxsize=None)
def _parent_maker(items: tuple):
    """The weights as drawn before stacks of any rank: a rank-3 stack one
    layer at a time, everything else whole."""

    def leaf(key, shape, dtype, fan_in):
        std = W.NORM_STD if fan_in == 0 else fan_in ** -0.5

        def draw(k, shp):
            return (jax.random.normal(k, shp, jnp.float32) * std).astype(dtype)

        if len(shape) == 3:
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(lambda k: draw(k, shape[1:]), keys)
        return draw(key, shape)

    def make(key):
        return W._nest({path: leaf(jax.random.fold_in(key, i), *spec)
                        for i, (path, spec) in enumerate(items)})

    return jax.jit(make)


def _same(a, b) -> bool:
    return jax.tree.structure(a) == jax.tree.structure(b) and all(
        x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("config", ["tiny", "tiny-raca"])
def test_dense_weights_are_drawn_as_before(config):
    dense = SPEC.arch("dense")
    model = _load(config)["model"]
    seed = 2 ** 33 + 17
    got = W.make(dense.layout(model), seed)
    parent = _parent_maker(tuple(sorted(W.layout(model).items())))(
        W.seed_key(seed))
    assert _same(got, parent)


@pytest.mark.parametrize("config", ["tiny", "tiny-raca"])
def test_dense_reference_and_control_are_the_benchmarks_own(config):
    dense = SPEC.arch("dense")
    conf = _load(config)
    model, as_run = conf["model"], conf["as_run"]
    weights = W.make(dense.layout(model), 11)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, dense.dims(model)["V"], 48).tolist()
    rows = np.arange(20, 40)
    for variant in (None, conf["control"]):
        got = dense.logits_rows(weights, model, as_run, tokens, rows,
                                variant=variant)
        want = REF.logits_rows(weights, model, as_run, tokens, rows,
                               variant=variant)
        assert np.array_equal(np.asarray(got), np.asarray(want)), variant


@pytest.mark.parametrize("config", ["tiny", "tiny-raca"])
def test_dense_counts_are_the_benchmarks_own(config):
    dense = SPEC.arch("dense")
    conf = _load(config)
    model, kv = conf["model"], conf["kv_cache_dtype"]
    assert dense.decode_attention(model, kv, [70, 5, 33]) == (
        RL.decode_attention(model, kv, [70, 5, 33]))
    assert dense.prefill_attention(model, kv, 64, 32) == (
        RL.prefill_attention(model, kv, 64, 32))
    assert dense.token_flops(model, 99, head=True) == (
        RL.token_flops(model, 99, head=True))
    assert dense.chunk_flops(model, 32, 32) == RL.chunk_flops(model, 32, 32)
    assert dense.matmul_params(model) == RL.matmul_params(model)


def test_dense_states_the_fields_the_harness_checked_before():
    conf = _load("tiny")
    fields = SPEC.arch("dense").program_fields(conf["model"], conf["as_run"])
    assert fields["layer_pattern"] == ("global",)
    assert fields["mlp"] == "swiglu" and fields["tie_embeddings"] is False
    assert (fields["n_layers"], fields["d_ff"]) == (2, 128)


def test_a_stack_of_any_rank_is_drawn_one_leading_index_at_a_time():
    lay = {"units/l0/moe/w_up": ((2, 3, 4, 5), "bfloat16", 4)}
    got = W.make(lay, 7)["units"]["l0"]["moe"]["w_up"]
    key = jax.random.fold_in(W.seed_key(7), 0)
    for u, ku in enumerate(jax.random.split(key, 2)):
        for e, ke in enumerate(jax.random.split(ku, 3)):
            want = (jax.random.normal(ke, (4, 5), jnp.float32)
                    * 4 ** -0.5).astype(jnp.bfloat16)
            assert np.array_equal(np.asarray(got[u, e]), np.asarray(want))


# -- a new architecture is a file --------------------------------------------


BENCH = pathlib.Path(SPEC.BENCH_DIR)
LOOKUP_CODE = [BENCH / "harness.py", BENCH / "spec.py", BENCH / "control.py",
               *sorted((BENCH / "metrics").glob("*.py"))]


def test_a_new_architecture_is_a_file_found_by_name(tmp_path):
    conf = _load(FIXTURE)
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "arch"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(os.path.join(DATA, FIXTURE + ".json"),
                bench / "configs" / "new-moe.json")
    shutil.copy(os.path.join(DATA, "tiny_closed.json"),
                bench / "traffic" / "new_mix.json")
    shutil.copy(os.path.join(DATA, "arch", conf["arch"] + ".py"),
                bench / "arch" / (conf["arch"] + ".py"))
    (tmp_path / "BENCHMARK.json").write_text(
        '{"workloads": [{"name": "new-moe.new_mix", "config": "new-moe", '
        '"traffic": "new_mix", "chips": 1}], "end_to_end": [], '
        '"per_layer": []}')
    cell = SPEC.cell("new-moe.new_mix", root=str(tmp_path),
                     bench_dir=str(bench))
    m = cell.arch.dims(cell.config["model"])
    assert cell.arch.pattern(m) == ("local", "global")
    assert set(cell.arch.layout(cell.config["model"])) >= {
        "units/l0/moe/router", "units/l1/moe/w_down"}
    # nothing that looks architectures up names this one
    for path in LOOKUP_CODE:
        assert conf["arch"] not in path.read_text(), path


def test_a_windowed_layer_reads_less_once_lengths_pass_the_window():
    arch = SPEC.arch_of(_load(FIXTURE), DATA)
    model = _load(FIXTURE)["model"]
    full = dict(model, layer_types=["full_attention"] * 4)
    window = model["sliding_window"]
    short, long_ = [window - 2, 3], [window + 40, 5 * window]
    # within the window both kinds read every position
    assert arch.decode_attention(model, "same", short) == (
        arch.decode_attention(full, "same", short))
    f_win, b_win = arch.decode_attention(model, "int8", long_)
    f_full, b_full = arch.decode_attention(full, "int8", long_)
    assert b_win < b_full and f_win < f_full
    # the windowed layers read window positions a request, the full ones all
    per_pos = 2 * 2 * (16 + 4)
    assert b_full - b_win == 2 * per_pos * sum(n - window for n in long_)
    # all full, the counts are the dense ones at the same attention sizes
    dense_like = {"num_hidden_layers": 4, "hidden_size": 64,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 16, "intermediate_size": 1, "vocab_size": 256}
    assert arch.decode_attention(full, "same", long_) == (
        RL.decode_attention(dense_like, "same", long_))
    assert arch.prefill_attention(full, "same", 48, 32) == (
        RL.prefill_attention(dense_like, "same", 48, 32))
    f_win, b_win = arch.prefill_attention(model, "same", 48, 32)
    assert f_win < arch.prefill_attention(full, "same", 48, 32)[0]
    # a decoded token is a chunk of one
    assert arch.token_flops(model, 60, head=True) == (
        arch.chunk_flops(model, 60, 1))


# -- the test architecture through the whole harness -------------------------


def _run(arch, control=False, seed=SEED):
    b = SPEC.benchmark()
    cell = SPEC.Cell("tiny-moe", 1, _load(FIXTURE), _load("tiny_closed"),
                     b["end_to_end"], b["per_layer"], arch)
    return harness.run_cell(cell, seed, 1.0, False, require_tpu=False,
                            control=control, cache=False,
                            log=lambda *a: None)


@pytest.fixture(scope="module")
def program():
    harness._program()
    from repro.models import moe

    return moe


def test_moe_window_run_is_correct_and_the_control_is_not(program):
    r = _run(SPEC.arch_of(_load(FIXTURE), DATA), control=True)
    assert r["correct"], r["compared"]
    assert r["check"]["tokens_compared"] > 50
    limits = _load(FIXTURE)["check"]["limits"]
    assert any(r["check"]["control_gap"][k] > v for k, v in limits.items())


def test_a_reference_that_forgets_the_window_is_caught(program, monkeypatch):
    arch = SPEC.arch_of(_load(FIXTURE), DATA)   # a fresh module: fresh jits
    mask = arch.attention_mask
    causal = lambda s, kind, window: mask(s, "global", window)
    monkeypatch.setattr(arch, "attention_mask", causal)
    assert not _run(arch)["correct"]


def test_routing_from_the_wrong_scores_is_caught(program, monkeypatch):
    apply = program.moe_apply

    def inverted(p, x, cfg, key=None):
        return apply(dict(p, router=-p["router"]), x, cfg, key)

    monkeypatch.setattr(program, "moe_apply", inverted)
    assert not _run(SPEC.arch_of(_load(FIXTURE), DATA))["correct"]
