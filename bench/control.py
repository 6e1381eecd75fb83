#!/usr/bin/env python3
"""Run a cell with its control in the program's place, to show that the
comparison deciding `correct` fails it.

  python bench/control.py --workload <cell> --seed <n> --seconds <s>

The run is a benchmark run in all but its answers: the window drives the
program as usual, then the compared answers come from the reference with
the guarantee that the cell's configuration names under "control" broken
("one_hop_short": k - 1 hops; "stale_snapshot": the edge set as it stood
before the last writes ahead of the read's batch). Prints the run's result
line, whose `correct` has to read false. Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                     "src")]

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev, log = harness.open_chip(cell, "control")
    control = cell.config["control"]
    out = harness.run_cell(cell, args.seed, args.seconds, False, T_START,
                           dev, control=control, log=log)
    out["control"] = control
    for name, c in out["checks"].items():
        log(f"control={control} check {name}={c['value']} "
            f"limit={c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
