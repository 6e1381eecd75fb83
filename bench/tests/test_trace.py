"""The trace reduction, on a small trace recorded on a TPU v5e and on
hand-made events with known answers."""
from __future__ import annotations

import json
import re

from pytest import approx

from bench import trace as tr
from bench.tests.conftest import BENCH

DATA = BENCH / "tests" / "data" / "trace_small.json"


def busy_by_timeline(intervals, w0, w1) -> int:
    """Busy nanoseconds by marking every nanosecond: no interval logic."""
    on = bytearray(w1 - w0)
    for s, e in intervals:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            on[s - w0:e - w0] = b"\x01" * (e - s)
    return sum(on)


def test_hand_made_events():
    events = {
        "devices": {"/device:TPU:0": [["a", 100, 50], ["b", 120, 60],
                                      ["a", 300, 100], ["c", 990, 100]]},
        "host": [["bench.window", 0, 1000], ["bench.pump", 180, 100],
                 ["bench.write", 400, 590]],
    }
    r = tr.reduce(events)
    assert r["window_s"] == approx(1000e-9)
    assert r["busy_s"] == approx(190e-9)          # 100..180, 300..400, 990..
    assert r["ops"]["a"] == [2, approx(150e-9)]
    assert r["ops"]["c"][1] == approx(10e-9)      # clipped to the window
    gaps = dict((round(s * 1e9), n) for n, s in r["idle_gaps"])
    assert gaps == {590: "bench.write", 120: "bench.pump", 100: "host_idle"}


def test_recorded_trace():
    events = json.loads(DATA.read_text())
    r = tr.reduce(events)
    win = [s for s in events["host"] if s[0] == "bench.window"][0]
    w0, w1 = win[1], win[1] + win[2]
    ev = next(iter(events["devices"].values()))
    want = busy_by_timeline([(s, s + d) for _, s, d in ev], w0, w1)
    assert abs(r["busy_s"] - want * 1e-9) < 1e-9
    assert 0 < r["busy_s"] < r["window_s"]
    calls, secs = tr.kernel_calls(r)
    hop = [(s, d) for name, s, d in ev if tr.HOP_KERNEL.search(name)]
    assert calls == len(hop) > 0
    assert abs(secs - sum(min(s + d, w1) - max(s, w0)
                          for s, d in hop) * 1e-9) < 1e-9
    assert not tr.kernel_calls(r, re.compile("no-such-kernel"))[0]


def test_hop_roofline_counts_each_sweep_by_its_reads():
    """Two sweeps, one of 40 reads (2 words) at k = 2 both ways and one of
    3 reads (1 word) at k = 1 out, against a kernel time of one second."""
    from types import SimpleNamespace as NS
    from bench.harness import load_module
    mod = load_module(BENCH / "metrics" / "hop_kernel_roofline.py")
    reads = ([NS(launch_pump=0, k=2, direction="both", done=1.0)] * 40
             + [NS(launch_pump=1, k=1, direction="out", done=1.0)] * 3
             + [NS(launch_pump=1, k=1, direction="out", done=None)])
    edges, n = 1000, 100
    obs = NS(trace={"ops": {"ell_mxv_packed": [6, 1.0]}}, reads=reads,
             edges=edges, n=n, peaks={"hbm_bytes_per_s": 1e6})
    least = (2 * (2 * (edges * 4 + edges * 2 * 4) + n * 2 * 4)
             + 1 * (edges * 4 + edges * 4 + n * 4))
    assert mod.read(obs) == approx(100.0 * least / 1e6)
    assert mod.read(NS(**{**vars(obs), "trace": None})) is None
