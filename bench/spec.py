"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each lives in a file
of its own, found by name: ``bench/configs/<config>.json`` and
``bench/traffic/<traffic>.json``.  A per-layer metric is a reader
``bench/metrics/<metric>.py`` that defines ``read(ctx)``, returning the
value or None where the run gave it nothing to read.  Adding any of the
three takes a new file and an entry, and no change to the harness.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


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


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of per-layer metric ``name``."""
    path = metric_file(name, bench_dir)
    if not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(config_file(w["config"], bench_dir)),
        traffic=load_json(traffic_file(w["traffic"], bench_dir)),
        end_to_end=[m for m in b["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in b["per_layer"] if _applies(m, name)],
    )
