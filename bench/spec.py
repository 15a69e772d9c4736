"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each lives in a file
of its own, found by name: ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``.  A configuration's ``"arch"`` (``dense``
where it has none) names its architecture, ``bench/arch/<arch>.py``.  A
per-layer metric is a reader ``bench/metrics/<metric>.py`` that defines
``read(ctx)``, returning the value or None where the run gave it nothing
to read.  Adding any of the four takes a new file and an entry, and no
change to the harness.

An architecture module gives, for a configuration's ``model`` group
(``model``) and its ``as_run`` group (``as_run``):

- ``dims(model)``: the sizes, with at least ``L``, ``D``, ``H``, ``Hkv``,
  ``Dh`` and ``V`` (the readers find kernels by them);
- ``layout(model)``: path -> (shape, dtype, fan_in) of every weight, in
  the program's parameter tree (``bench/weights.py`` draws them);
- ``program_fields(model, as_run)``: the program's ``ModelConfig`` fields
  and the values the file states for them, which the harness checks;
- ``logits_rows(weights, model, as_run, tokens, rows, variant=None)``:
  the float32 reference's logits at ``rows`` at ``highest`` precision,
  or with ``variant`` (the file's ``control``) the control's;
- the counts of work that the readers take: ``decode_attention(model,
  kv_dtype, lengths)`` and ``prefill_attention(model, kv_dtype, q0, c)``
  as (flops, bytes), ``token_flops(model, position, head)`` and
  ``chunk_flops(model, q0, c)`` (``bench/roofline.py`` has the dense
  ones).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DEFAULT_ARCH = "dense"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    arch: types.ModuleType


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "configs", f"{name}.json")


def traffic_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{name}.json")


def metric_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "metrics", f"{name}.py")


def arch_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "arch", f"{name}.py")


def _module(kind: str, name: str, path: str) -> types.ModuleType:
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of per-layer metric ``name``."""
    return _module("metric", name, metric_file(name, bench_dir)).read


def arch(name: str, bench_dir: str = BENCH_DIR) -> types.ModuleType:
    """The module of architecture ``name`` (see above)."""
    return _module("arch", name, arch_file(name, bench_dir))


def arch_of(config: dict, bench_dir: str = BENCH_DIR) -> types.ModuleType:
    """The architecture that configuration ``config`` names."""
    return arch(config.get("arch", DEFAULT_ARCH), bench_dir)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of the benchmark at ``root``, with its
    configuration, traffic and the metrics it reports."""
    b = benchmark(root)
    found = [w for w in b["workloads"] if w["name"] == name]
    if not found:
        known = [w["name"] for w in b["workloads"]]
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    w = found[0]
    config = load_json(config_file(w["config"], bench_dir))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=load_json(traffic_file(w["traffic"], bench_dir)),
        end_to_end=[m for m in b["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in b["per_layer"] if _applies(m, name)],
        arch=arch_of(config, bench_dir),
    )
