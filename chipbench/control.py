#!/usr/bin/env python3
"""The control of the answer check: ``reference.control`` put in the
program's place, on a cell's window traffic, at the size a run compares.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it takes the first states of the window's stream, as many
as a run compares, answers them with the control, and compares those
answers with the plain reference exactly as ``run.py`` does.  A sound
check reads mismatches here; the program's sound runs read none.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
from run import SAMPLE  # noqa: E402
from traffic import Cell, Traffic, load_json  # noqa: E402


def window_states(traffic: Traffic, n: int) -> list:
    """The first ``n`` states of the window's stream."""
    out, k = [], 0
    while len(out) < n:
        out.extend(traffic.states("window", k))
        k += 1
    return out[:n]


def readings(cell: Cell, seed: int, n: int = SAMPLE) -> dict:
    traffic = Traffic(cell.config_name, cell.config, cell.mix, seed)
    states = window_states(traffic, n)
    t0 = time.perf_counter()
    got = [reference.control(s) for s in states]
    out = reference.compare(got, states)
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--answers", type=int, default=SAMPLE)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"))
    cell = Cell.load(bench, args.workload)
    for seed in args.seeds:
        r = readings(cell, seed, args.answers)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "compared": r["compared"],
                          "mismatched": r["mismatched"],
                          "seconds": round(r["seconds"], 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
