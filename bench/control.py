"""Reads the numbers compared for ``correct``, and the control's, on
many seeds in one process: how the limits in the configuration files
were set.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13

Each seed is a whole run of the cell as ``bench/run.py`` makes it (its
own weights, traffic and window), and on the same sampled requests the
control (the reference of the cell's architecture in the next lower
precision: ``logits_rows`` with the file's ``control`` as its variant,
``bench/reference.py`` for dense) picks its own token at each position.
One JSON line per seed: the program's gaps, the control's gaps, and the
limits they are held to.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness, spec

    cell = spec.cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, control=True,
                             log=lambda *a: print(*a, file=sys.stderr))
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": r["correct"], "gap": r["check"]["gap"],
            "control_gap": r["check"]["control_gap"],
            "limits": cell.config["check"]["limits"],
            "tokens_compared": r["check"]["tokens_compared"],
            "check_s": r["check"]["check_s"],
            "values": r["res"]["values"],
            "memory_peak_bytes": r["memory_peak_bytes"],
            "compiles_in_window": r["compiles_in_window"],
            "run_s": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
