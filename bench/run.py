"""Runs one cell of the benchmark on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints what it did on standard error, the numbers compared for
``correct`` with their limits as its last lines there, and one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``.  Exits non-zero, printing no
result, where JAX finds no TPU, fewer chips than the cell asks for, or
a device kind that the table of peaks lacks.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def result_line(cell, r: dict, trace: bool, reader) -> dict:
    """The result object of a run, as the benchmark prints it."""
    res = r["res"]
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(r["ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {
            m["name"]: {"value": res["values"][m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in res["values"]
        }
    dev = r["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": r["n_devices"],
              "memory_peak_bytes": r["memory_peak_bytes"]}
    out = {
        "correct": r["correct"],
        "attempted": res["attempted"],
        "failed": res["missing_first_token"] + len(r["failed_reasons"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        t = r["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["compared"] = r["compared"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness, spec

    cell = spec.cell(args.workload)
    r = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         log=eprint, t_start=T0)
    out = result_line(cell, r, bool(args.trace), spec.reader)
    if "control_gap" in r["check"]:
        eprint(f"control_gap={r['check']['control_gap']}")
    eprint(f"check_s={r['check']['check_s']:.3f} "
           f"tokens_compared={r['check']['tokens_compared']} "
           f"requests_compared={r['check']['requests_compared']}")
    for name, v in out["compared"].items():
        eprint(f"compared {name}={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
