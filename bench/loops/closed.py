"""Closed loop: `clients` callers that each wait for their reply.

Every client sends one operation, waits for its result, then sends the
next. An operation is a read (submitted to the server, latency from the
submit) or a write, which runs at once on the loop's own thread through
`Database.query`, as Redis runs commands, and delays whatever waits behind
it. After `seconds` no client sends again and the loop drains. A warm-up
pass is `WARM_S` of the same.
"""
from __future__ import annotations

import time

WARM_S = 3.0


def warm(target, traffic, load: dict, rel: str) -> None:
    """One warm-up pass."""
    drive(target, traffic, load, WARM_S, rel)


def drive(target, traffic, load: dict, seconds: float, rel: str) -> None:
    t_end = time.perf_counter() + seconds
    owner = {}
    ready = list(range(int(load["clients"])))

    def write(kind, s, t):
        target.write(kind, s, t, rel)

    while time.perf_counter() < t_end:
        for c in ready:
            read, seed = traffic.next_read(write)
            owner[target.submit(read, seed, time.perf_counter())] = c
        ready = [owner.pop(r.qid) for r in target.pump()]
    target.drain()
