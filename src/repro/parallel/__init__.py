"""Logical-axis sharding context used by model code.

Models annotate activations with *logical* axis names
(``shard(x, ("batch", "seq", "embed"))``); the launcher installs a mesh and a
logical→mesh translation table (launch/sharding.py).  Outside any context the
annotation is a no-op, so the same model code runs on one CPU device and on a
512-chip production mesh unchanged.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_axis_rules", default=None
)


def current() -> Optional[tuple]:
    return _CTX.get()


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Mapping[str, Optional[object]]):
    """Install (mesh, logical→mesh table) for shard() annotations.

    ``rules`` maps a logical axis name to a mesh axis name, a tuple of mesh
    axis names, or None (replicated)."""
    token = _CTX.set((mesh, dict(rules)))
    try:
        yield
    finally:
        _CTX.reset(token)


def spec_for(names: Sequence[Optional[str]]) -> P:
    """Translate logical axis names to a PartitionSpec under current rules."""
    ctx = _CTX.get()
    if ctx is None:
        return P()
    _, rules = ctx
    return P(*[rules.get(n) if n is not None else None for n in names])


def shard(x: jax.Array, names: Sequence[Optional[str]]) -> jax.Array:
    """Constrain activation sharding by logical names; no-op with no context."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, _ = ctx
    if len(names) != x.ndim:
        raise ValueError(f"rank mismatch: {names} vs {x.shape}")
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(names))
    )


def sharding_for(names: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    ctx = _CTX.get()
    if ctx is None:
        return None
    mesh, _ = ctx
    return NamedSharding(mesh, spec_for(names))


_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kernel_mesh", default=None
)


@contextlib.contextmanager
def kernel_mesh(mesh: Mesh):
    """Install the serving mesh for the Pallas kernels traced inside.

    GSPMD cannot partition a Mosaic kernel, so under a mesh every kernel
    call runs inside ``shard_map`` (kernels/ops.py).  Unlike
    :func:`axis_rules` this constrains no activation: the rest of the
    program is partitioned from its argument shardings alone."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def current_kernel_mesh() -> Optional[Mesh]:
    return _KERNEL_MESH.get()
