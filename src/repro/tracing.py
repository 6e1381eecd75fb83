"""Spans and counters of the serving path, on the profiler's clock.

`span(name, **args)` marks one step of the serving path: under a
`jax.profiler` session it is a host event in the same trace as the device
operations (a `jax.profiler.TraceAnnotation`), and in every process it
adds one call and its wall time to process-wide totals. `span(name,
cpu=True)` also adds its thread's CPU time, which leaves out the time the
thread sleeps: on the device (a result read to the host, or a dispatch
held back while the device's queue is full), on I/O or on a lock. A
thread CPU clock read is a system call, dearer than the rest of a span,
so only spans entered a few times a second take it. `add(name, n)`
adds to a counter. A `jax.monitoring` listener counts the executables the
process builds (`compile.count`, and `compile.count.<fun_name>`) and sums
the seconds spent tracing, lowering and compiling them (`compile.s`, and
`compile.s.<span>` by the innermost span open on the compiling thread),
each second once: a jit traced inside another's trace is part of it.

`totals()` is a flat snapshot of all of it: `<span>.calls`, `<span>.s`,
`<span>.cpu_s` (where taken), each counter by name and the compile keys, every value an int or a float.
Totals are always kept; outside a profiler session an annotation costs a
no-op `TraceMe`. `QueryServer.stats` carries them (docs/API.md §Serving).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Deque, Dict, List, Tuple, Union

import jax

Number = Union[int, float]

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# the three phases of building an executable (jax/_src/dispatch.py): the
# backend compile fires for each executable, whether compiled or read back
# from the persistent cache
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
})
# the `compile.s.<span>` key of a compile that no span encloses
NO_SPAN = "unspanned"
# compile phases a thread remembers, to take those an enclosing phase
# covers out of its seconds (an inner jit's trace, or a compile run for
# a constant, inside an outer trace)
PHASES_KEPT = 1024

_lock = threading.Lock()        # guards _threads
_threads: List[Dict[str, Number]] = []


class _Thread(threading.local):
    """This thread's open span names (innermost last), its totals, and
    the (start, end) of the compile phases it has counted (latest last).
    Only the owning thread writes its totals, so the hot path takes no
    lock; `totals()` sums a copy of every thread's."""

    def __init__(self):
        self.names: List[str] = []
        self.totals: Dict[str, Number] = {}
        self.phases: Deque[Tuple[float, float]] = collections.deque(
            maxlen=PHASES_KEPT)
        with _lock:
            _threads.append(self.totals)


_local = _Thread()


def add(name: str, n: Number = 1) -> None:
    """Add `n` to the counter `name`."""
    t = _local.totals
    t[name] = t.get(name, 0) + n


class _Span:
    __slots__ = ("name", "_note", "_cpu", "_t0", "_c0")

    def __init__(self, name: str, cpu: bool, args: dict):
        self.name = name
        self._cpu = cpu
        self._note = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> "_Span":
        _local.names.append(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        if self._cpu:
            self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        dc = time.thread_time() - self._c0 if self._cpu else 0.0
        self._note.__exit__(*exc)
        local = _local
        local.names.pop()
        t, calls, secs = local.totals, self.name + ".calls", self.name + ".s"
        t[calls] = t.get(calls, 0) + 1
        t[secs] = t.get(secs, 0) + dt
        if self._cpu:
            cpu = self.name + ".cpu_s"
            t[cpu] = t.get(cpu, 0) + dc


def span(name: str, cpu: bool = False, **args) -> _Span:
    """Context manager: one call of the step `name`, with `args` (ids,
    sizes) attached to its profiler event; `cpu` also keeps the thread's
    CPU seconds (`<name>.cpu_s`)."""
    return _Span(name, cpu, args)


def totals() -> Dict[str, Number]:
    """A snapshot of every span total, counter and compile key."""
    with _lock:
        parts = [t.copy() for t in _threads]
    out: Dict[str, Number] = {}
    for part in parts:
        for k, v in part.items():
            out[k] = out.get(k, 0) + v
    return out


def _on_phase(event: str, start: float, end: float, **kw) -> None:
    """One compile phase ended on this thread. Phases nest (an inner jit's
    trace ends inside the outer one's) and end innermost first, so the
    phases counted since this one started lie inside it: only the rest of
    its time is new."""
    if event not in COMPILE_EVENTS:
        return
    local = _local
    phases = local.phases
    inner = 0.0
    while phases and phases[-1][0] >= start:
        s, e = phases.pop()
        inner += e - s
    phases.append((start, end))
    if event == BACKEND_COMPILE:
        add("compile.count")
        add(f"compile.count.{kw.get('fun_name', '')}")
    secs = max(end - start - inner, 0.0)
    where = local.names[-1] if local.names else NO_SPAN
    add("compile.s", secs)
    add(f"compile.s.{where}", secs)


jax.monitoring.register_event_time_span_listener(_on_phase)
