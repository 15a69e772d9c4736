"""Seeded random weights, made by the benchmark and handed to the program.

The tree has the layout the serving engine takes; an architecture's
``layout`` gives it, the dense one's below (``embed``, ``final_norm``,
``head`` and the layer stack under ``units/l0``).  The reference reads
the same arrays by the same names.  Matrices are drawn N(0, 1/fan_in)
and stored in the type they are served in (bfloat16); norm scales are
0.1·N(0, 1) in float32 and enter as ``x · (1 + scale)``.  All of it is
made on the device in one jitted program, a stack one leading index at a
time (a layer, then an expert) so that no float32 copy of more than one
matrix is ever live.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def dims(model: dict) -> dict:
    """The sizes the weights and the reference need, from a config's
    ``model`` group (Hugging Face key names)."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return {
        "L": model["num_hidden_layers"],
        "D": d,
        "H": h,
        "Hkv": model["num_key_value_heads"],
        "Dh": model.get("head_dim") or d // h,
        "F": model["intermediate_size"],
        "V": model["vocab_size"],
    }


def layout(model: dict) -> dict[str, tuple[tuple[int, ...], str, int]]:
    """path -> (shape, dtype, fan_in); fan_in 0 marks a norm scale."""
    m = dims(model)
    L, D, H, Hkv, Dh, F, V = (m[k] for k in ("L", "D", "H", "Hkv", "Dh", "F", "V"))
    u = "units/l0/"
    return {
        "embed/embedding": ((V, D), "bfloat16", D),
        "final_norm/scale": ((D,), "float32", 0),
        "head/w": ((D, V), "bfloat16", D),
        u + "ln1/scale": ((L, D), "float32", 0),
        u + "attn/wq": ((L, D, H * Dh), "bfloat16", D),
        u + "attn/wk": ((L, D, Hkv * Dh), "bfloat16", D),
        u + "attn/wv": ((L, D, Hkv * Dh), "bfloat16", D),
        u + "attn/wo": ((L, H * Dh, D), "bfloat16", H * Dh),
        u + "ln2/scale": ((L, D), "float32", 0),
        u + "ffn/w_gate": ((L, D, F), "bfloat16", D),
        u + "ffn/w_up": ((L, D, F), "bfloat16", D),
        u + "ffn/w_down": ((L, F, D), "bfloat16", F),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, 32 bits or more."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf(key, shape, dtype, fan_in):
    std = NORM_STD if fan_in == 0 else fan_in ** -0.5

    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * std).astype(dtype)

    def stack(k, shp):  # one leading index live in float32 at a time
        if len(shp) < 3:
            return draw(k, shp)
        keys = jax.random.split(k, shp[0])
        return jax.lax.map(lambda ki: stack(ki, shp[1:]), keys)

    return stack(key, shape)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = val
    return out


@functools.lru_cache(maxsize=None)
def _maker(items: tuple):
    def make(key):
        flat = {
            path: _leaf(jax.random.fold_in(key, i), shape, dtype, fan)
            for i, (path, (shape, dtype, fan)) in enumerate(items)
        }
        return _nest(flat)

    return jax.jit(make)


def make(lay: dict, seed: int) -> dict:
    """The weights of layout ``lay`` for ``seed``, on the default device."""
    return _maker(tuple(sorted(lay.items())))(seed_key(seed))


def layer(weights: dict, i: int) -> dict:
    """Layer ``i``'s leaves of the stack, by their short names."""
    u = weights["units"]["l0"]
    return {
        "ln1": u["ln1"]["scale"][i],
        "ln2": u["ln2"]["scale"][i],
        **{k: v[i] for k, v in u["attn"].items()},
        **{k: v[i] for k, v in u["ffn"].items()},
    }
