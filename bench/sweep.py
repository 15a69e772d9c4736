"""Finds the knee of an open-loop cell: runs its traffic at several fixed
rates, one window each, and reports what the engine sustained.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates 1.5 2 2.5 3

For each rate, one JSON line: requests due in the window and admitted
(first token) in it per second, the TTFT median and 90th percentile,
the inter-token tail and the requests still waiting for a first token at
the window's close.  The knee is the highest rate whose TTFT tail stays
flat and whose admissions keep up with the arrivals; the cell's traffic
file then states 0.8 x the knee as a number.  Outputs are not checked.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--grace", type=float, default=None,
                    help="seconds to wait after the window for first tokens "
                         "(default: the mix's grace_s)")
    args = ap.parse_args(argv)
    from bench import harness, spec

    cell = spec.cell(args.workload)
    if cell.traffic["kind"] != "open_loop":
        raise SystemExit("a knee sweep needs an open-loop cell")
    for rate in args.rates:
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        if args.grace is not None:
            cell.traffic["grace_s"] = args.grace
        r = harness.run_cell(cell, args.seed, args.seconds, False,
                             check_outputs=False,
                             log=lambda *a: print(*a, file=sys.stderr))
        res = r["res"]
        w = res["window_s"]
        print(json.dumps({
            "rate_per_s": rate, "due_per_s": res["attempted"] / w,
            "admitted_per_s": res["admitted_in_window"] / w,
            "missing_first_token": res["missing_first_token"],
            "values": res["values"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
