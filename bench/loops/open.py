"""Open loop: independent users at a fixed Poisson rate.

Arrivals are due on a schedule drawn from the seed, whatever the server
has done; each read is submitted with its scheduled time (`arrival_s`), so
latency runs from when it was due and counts any wait a stall imposed.
The loop pumps whenever work is queued or in flight and sleeps otherwise.
After `seconds` it stops arriving and drains what is left.

A warm-up pass raises the rate linearly from 0 to `RAMP_TOP` times the
cell's rate over `RAMP_S`: past the knee (a cell runs at about four fifths
of it), so the batches it forms grow a few queries at a time through every
width up to a full sweep. The server builds one set of programs per batch
width. A compile stalls the ramp, and the backlog it leaves skips some
widths, so the pass then holds the cell's own rate for `STEADY_S`: the
widths the window forms most.
"""
from __future__ import annotations

import time

RAMP_TOP = 1.6
RAMP_S = 12.0
STEADY_S = 6.0


def warm(target, traffic, load: dict, rel: str) -> None:
    """One warm-up pass: the ramp, then the cell's rate."""
    rate = float(load["rate_qps"])
    _run(target, traffic, rate, RAMP_S, rel, ramp=True)
    _run(target, traffic, rate, STEADY_S, rel, ramp=False)


def drive(target, traffic, load: dict, seconds: float, rel: str) -> None:
    _run(target, traffic, float(load["rate_qps"]), seconds, rel, ramp=False)


def _run(target, traffic, rate: float, seconds: float, rel: str,
         ramp: bool) -> None:
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def gap(due: float) -> float:
        f = RAMP_TOP * max(due - t0, 0.05) / seconds if ramp else 1.0
        return traffic.gap.next() / (rate * f)

    def write(kind, s, t):
        target.write(kind, s, t, rel)

    due = t0 + gap(t0)
    while True:
        now = time.perf_counter()
        while due <= now and due < t_end:
            target.submit(*traffic.next_read(write), due)
            due += gap(due)
        if now >= t_end:
            break
        if target.pending:
            target.pump()
        else:
            time.sleep(min(max(due - now, 0.0), 1e-3))
    target.drain()
