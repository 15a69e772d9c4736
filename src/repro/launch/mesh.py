"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model") — TPU v5e pod.
Multi-pod: 2×16×16 = 512 chips, axes ("pod", "data", "model"); the "pod"
axis carries only data parallelism (cross-pod traffic = one gradient
all-reduce per step, which is what DCI-connected pods sustain).

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh here has Auto axes: the sharding rules place arrays with
``NamedSharding`` and leave the partitioning of the ops in between to
GSPMD.  (``jax.make_mesh`` defaults to Explicit axes, under which the
paged pool's scatter raises ``ShardingTypeError``.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None):
    """``(data, model)`` mesh over locally available devices.

    The serving mesh for tests, benches, and CPU multi-device runs
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  With
    ``data=None`` every local device not consumed by ``model`` goes to
    the data axis; pass ``data`` explicitly to use a subset (e.g. a 1×1
    mesh on a multi-device host for byte-identity checks).
    """
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        raise ValueError(
            f"model axis {model} does not divide the {n} local devices"
        )
    if data is None:
        data = n // model
    if data < 1 or data * model > n:
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices, "
            f"have {n}"
        )
    return auto_mesh(
        (data, model), ("data", "model"), devices=jax.devices()[: data * model]
    )
