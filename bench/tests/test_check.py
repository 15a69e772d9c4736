"""The comparison that decides ``correct``, driven through a whole run of
the harness at a tiny size on the CPU (no chip: the harness's look for
one is skipped, the compile cache is off).

A sound run passes; the control (the reference in float8, put in the
program's place) fails; and so does the program with its timed path
broken underneath: a token altered where it is produced, and a decode
step that hands back its cache unchanged.  The WTA gap is checked
against a brute-force search on small shapes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import reference as REF
from bench import spec as SPEC

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 3


def _cell(config: str, traffic: str = "tiny_closed") -> SPEC.Cell:
    load = lambda n: SPEC.load_json(os.path.join(DATA, n + ".json"))
    b = SPEC.benchmark()
    return SPEC.Cell("tiny", 1, load(config), load(traffic),
                     b["end_to_end"], b["per_layer"])


def _run(cell, control=False, seed=SEED):
    return harness.run_cell(cell, seed, 1.0, False, require_tpu=False,
                            control=control, cache=False,
                            log=lambda *a: None)


@pytest.fixture(scope="module")
def program():
    harness._program()
    from repro.launch import specs

    return specs


def test_sound_run_is_correct_and_the_control_is_not(program):
    cell = _cell("tiny")
    r = _run(cell, control=True)
    limit = cell.config["check"]["limits"]["max"]
    assert r["correct"], r["compared"]
    assert r["check"]["gap"]["max"] <= limit
    assert r["check"]["control_gap"]["max"] > limit
    assert r["check"]["tokens_compared"] > 50
    assert r["res"]["values"]["output_tok_s"] > 0
    assert r["compiles_in_window"] == 0


def test_wta_and_int8_pool_run_is_correct_and_the_control_is_not(program):
    cell = _cell("tiny-raca")
    r = _run(cell, control=True)
    limit = cell.config["check"]["limits"]["max"]
    assert r["correct"], r["compared"]
    assert r["check"]["control_gap"]["max"] > limit


def test_open_loop_run_is_correct(program):
    r = _run(_cell("tiny", "tiny_open"))
    assert r["correct"], r["compared"]
    v = r["res"]["values"]
    assert v["ttft_p50_ms"] <= v["ttft_p90_ms"]
    assert r["res"]["attempted"] > 0


def test_a_token_altered_where_it_is_produced_is_caught(program, monkeypatch):
    sample = program.sample_tokens

    def altered(cfg, logits, *a, **kw):
        return (sample(cfg, logits, *a, **kw) + 1) % cfg.vocab

    monkeypatch.setattr(program, "sample_tokens", altered)
    r = _run(_cell("tiny"))
    assert not r["correct"]
    assert r["check"]["gap"]["max"] > 0.5


def test_a_step_that_returns_its_state_unchanged_is_caught(program,
                                                           monkeypatch):
    make = program.make_paged_serve_step

    def stale(cfg, **kw):
        step = make(cfg, **kw)

        def serve_step(params, cache, *a):
            _, tok, sane = step(params, cache, *a)
            return cache, tok, sane

        return serve_step

    monkeypatch.setattr(program, "make_paged_serve_step", stale)
    r = _run(_cell("tiny"))
    assert not r["correct"]


def _brute_wta_gap(z, key, t, wta):
    """Least rise of z[t] alone that makes the vote pick t."""
    kw = dict(trials=wta["trials"], sigma=wta["sigma_z"], vth0=wta["vth0"])
    onehot = jnp.zeros_like(z).at[t].set(1.0)
    for d in np.linspace(0.0, 8.0, 801):
        if int(REF.wta_choice((z + d * onehot)[None], key[None], **kw)[0]) == t:
            return d
    return np.inf


def test_wta_gap_is_zero_for_the_vote_and_bounded_by_raising_the_token():
    wta = {"trials": 8, "sigma_z": 1.702, "vth0": 2.896804}
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(1), 12)
    z = jnp.asarray(rng.normal(size=(12, 20)), jnp.float32)
    vote = REF.control_choice(z, keys, wta)
    assert np.all(REF.gaps(z, keys, vote, wta) == 0.0)
    other = (vote + 1 + rng.integers(0, 19, 12)) % 20
    got = REF.gaps(z, keys, other, wta)
    assert np.all(got > 0)
    for i in range(12):
        # the gap is twice the least uniform logit error that lets the
        # vote pick t; raising z[t] alone by d is one such error of size d
        assert got[i] <= 2 * _brute_wta_gap(z[i], keys[i], int(other[i]),
                                             wta) + 1e-4


def test_greedy_gap_is_the_distance_below_the_best():
    z = jnp.asarray([[0.0, 2.0, 1.5], [3.0, -1.0, 3.0]])
    assert list(REF.greedy_gaps(z, [2, 0])) == [0.5, 0.0]
