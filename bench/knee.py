#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell once, to find its knee.

  python bench/knee.py --workload <cell> --seed <n> --seconds <s> RATE ...

One process builds the cell and warms it up as a run does, then offers the
cell's traffic at each rate in turn for `--seconds` and prints, per rate,
the offered and completed rates, the most reads pending in each half of
the window, and the p50 / p99 latency. The knee is the highest rate whose
completed rate keeps up with no backlog that grows over the window; a
cell's workload file then fixes its rate at about four fifths of it.
Not part of a benchmark run.
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

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev, log = harness.open_chip(cell, "knee")
    state = harness.CHECKOUT / ".bench_state" / f"{cell.name}.knee"
    ses = harness.set_up(cell, args.seed, state, T_START, log)
    target, half = ses.target, args.seconds / 2
    rows = []
    for rate in args.rates:
        target.phase = f"r{rate}"
        c0, p0 = harness._Compiles.count, len(target.queued)
        t0 = time.perf_counter()
        cell.loop.drive(target, ses.traffic(target.phase),
                        {"rate_qps": rate}, args.seconds, ses.rel)
        reads = [r for r in target.reads.values() if r.phase == target.phase]
        lat = np.array([r.done - r.due for r in reads if r.done is not None])
        queued = [(t - t0, q) for t, q in target.queued[p0:]]
        row = {"offered_qps": rate,
               "arrived_qps": len(reads) / args.seconds,
               "completed_qps": sum(r.done is not None and
                                    r.done <= t0 + args.seconds
                                    for r in reads) / args.seconds,
               "backlog_max_first_half": max(
                   [q for t, q in queued if t < half] or [0]),
               "backlog_max_second_half": max(
                   [q for t, q in queued if half <= t < 2 * half] or [0]),
               "p50_ms": float(np.percentile(lat, 50) * 1e3),
               "p99_ms": float(np.percentile(lat, 99) * 1e3),
               "compiles": harness._Compiles.count - c0}
        rows.append(row)
        log(json.dumps(row))
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "knee_sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
