#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it builds the cell's graph from the seed,
warms up on the cell's own traffic, measures for `--seconds`, checks a
seeded sample of the answers against the plain reference, and prints one
JSON object as the last line of stdout. `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` traces the window and reports its
per-layer metrics. It refuses to run where JAX finds no TPU, or fewer
chips than the cell asks for. Every line it prints names the device.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev, log = harness.open_chip(cell, "bench")
    log(f"setup seed={args.seed} seconds={args.seconds} trace={args.trace}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, dev, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
