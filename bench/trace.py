"""From a profiler trace of the window to numbers.

`load` reads the `.xplane.pb` that `jax.profiler` wrote and keeps, on one
clock, the device operations (the "XLA Ops" line of each `/device:`
plane) and the harness's own host spans (`bench.*` TraceAnnotations).
`reduce` turns those events into the device's busy and idle time, the
time and count of each operation, and the longest idle gaps, each named
by the host span it fell in. Both are checked on a small recorded trace
in `tests/data`.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List

# The served hop's Pallas kernel (`kernels/bitmap_mxv.py`
# `ell_mxv_packed`): its custom call takes the jitted function's name.
HOP_KERNEL = re.compile(r"^ell_mxv_packed\b")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def op_name(hlo: str) -> str:
    """"%name = type{layout} op(...)" -> "name type": an HLO instruction
    as the TPU trace names it, cut to its name and result type."""
    head, _, rest = hlo.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{')[0].split(' ')[0]}".strip()


def load(trace_dir: Path) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no trace written under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                devices[plane.name] = [
                    [op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events]
            elif not device:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def union(intervals) -> List[list]:
    """Merged [start, end] intervals, sorted."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, spans) -> str:
    """The host span covering most of the gap, or host_idle."""
    best, cover = "host_idle", 0
    for name, s, d in spans:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name, c
    return best


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle seconds over the traced window, per-op time and call
    counts, and the longest idle gaps by host span.

    The window is the `bench.window` host span where the trace has one,
    else the span from the first to the last device operation. Busy time
    is the union of operation intervals inside it, averaged over the
    devices that ran anything."""
    spans = events["host"]
    win = [s for s in spans if s[0] == "bench.window"]
    devices = {k: v for k, v in events["devices"].items() if v}
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
    else:
        allev = [e for v in devices.values() for e in v]
        w0 = min(e[1] for e in allev)
        w1 = max(e[1] + e[2] for e in allev)
    ops: Dict[str, list] = {}
    busy_ns, gaps = [], []
    inner = [s for s in spans if s[0] != "bench.window"]
    for plane, evs in sorted(devices.items()):
        iv = []
        for name, s, d in evs:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            iv.append([s0, s1])
            o = ops.setdefault(name, [0, 0.0])
            o[0] += 1
            o[1] += (s1 - s0) * 1e-9
        merged = union(iv)
        busy_ns.append(sum(e - s for s, e in merged))
        if plane == sorted(devices)[0]:
            edges = [w0] + [x for m in merged for x in m] + [w1]
            gaps = [[edges[i], edges[i + 1]]
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    window_s = (w1 - w0) * 1e-9
    busy_s = (sum(busy_ns) / len(busy_ns)) * 1e-9 if busy_ns else 0.0
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "ops": ops,
        "top_ops": [[name, t] for name, (_, t) in
                    sorted(ops.items(), key=lambda kv: -kv[1][1])[:top]],
        "idle_gaps": [[_label(g, inner), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:top]],
    }


def kernel_calls(trace: dict, pattern=HOP_KERNEL):
    """(calls, seconds) of the device operations `pattern` matches."""
    calls, secs = 0, 0.0
    for name, (c, t) in trace["ops"].items():
        if pattern.search(name):
            calls += c
            secs += t
    return calls, secs
