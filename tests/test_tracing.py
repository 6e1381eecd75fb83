"""`repro.tracing`: spans and counters of the serving path.

Totals are process-wide and cumulative, so every check reads the delta of
`tracing.totals()` around what it runs."""
from __future__ import annotations

import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.engine import Database

KHOP = "MATCH (a)-[:KNOWS*1..2]-(b) RETURN count(DISTINCT b)"
PUMP_STEPS = ("repro.admit", "repro.refresh", "repro.launch", "repro.fetch",
              "repro.project")
SERVING_SPANS = ("repro.submit", "repro.pump", *PUMP_STEPS, "repro.mask",
                 "repro.delta.apply_ops", "repro.delta.compact",
                 "repro.delta.patch", "repro.write", "repro.aof.fsync")


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_nested_spans_add_calls_seconds_and_counters():
    before = tracing.totals()
    with tracing.span("test.outer", k=1):
        with tracing.span("test.inner"):
            time.sleep(0.02)
        tracing.add("test.counter", 3)
        time.sleep(0.01)
    with tracing.span("test.inner"):
        pass
    tracing.add("test.counter")
    with pytest.raises(ValueError):
        with tracing.span("test.raises"):
            raise ValueError("counted all the same")
    d = delta(before, tracing.totals())
    assert d["test.outer.calls"] == 1 and d["test.inner.calls"] == 2
    assert d["test.raises.calls"] == 1
    assert d["test.counter"] == 4
    assert d["test.inner.s"] >= 0.02
    assert d["test.outer.s"] >= 0.03
    assert d["test.outer.s"] >= d["test.inner.s"] - 0.005


def test_cpu_seconds_leave_out_sleeps():
    """`.cpu_s` is the thread's CPU time inside a span entered with
    `cpu=True`: a sleep (as a wait on the device is) adds wall seconds
    only, work adds both. Other spans keep wall seconds alone."""
    before = tracing.totals()
    with tracing.span("test.sleeps", cpu=True):
        time.sleep(0.1)
    with tracing.span("test.works", cpu=True):
        t = time.thread_time()
        while time.thread_time() - t < 0.05:
            pass
    d = delta(before, tracing.totals())
    assert d["test.sleeps.s"] >= 0.1
    assert d["test.sleeps.cpu_s"] < 0.02
    assert d["test.works.cpu_s"] >= 0.05
    assert d["test.works.cpu_s"] <= d["test.works.s"] + 0.001
    with tracing.span("test.wall_only"):
        pass
    assert "test.wall_only.cpu_s" not in tracing.totals()


def test_totals_are_flat_immutable_snapshots():
    tracing.add("test.snapshot", 1)
    snap = tracing.totals()
    tracing.add("test.snapshot", 1)
    with tracing.span("test.snapshot_span"):
        pass
    assert tracing.totals()["test.snapshot"] == snap["test.snapshot"] + 1
    assert "test.snapshot_span.calls" not in snap
    assert all(isinstance(k, str) and type(v) in (int, float)
               for k, v in snap.items())


def probe_tracing_fn(x):
    return x * 3 + 1


def test_compiles_keyed_by_function_and_enclosing_span():
    f = jax.jit(probe_tracing_fn)
    before = tracing.totals()
    with tracing.span("test.compile_outer"):
        with tracing.span("test.compile"):
            f(jnp.arange(7.0)).block_until_ready()
    mid = tracing.totals()
    f(jnp.arange(7.0)).block_until_ready()     # cached: no compile
    d = delta(before, mid)
    assert d["compile.count.jit(probe_tracing_fn)"] == 1
    assert d["compile.count"] >= 1
    assert d["compile.s.test.compile"] > 0
    assert "compile.s.test.compile_outer" not in d      # innermost only
    assert d["compile.s"] >= d["compile.s.test.compile"]
    after = delta(mid, tracing.totals())
    assert "compile.count" not in after and "compile.s" not in after


def test_nested_jit_compile_seconds_counted_once():
    """An inner jit is traced inside the outer one's trace, and both
    report their phase: the inner seconds are counted once."""
    pause = 0.05

    def level(inner):
        def step(x):
            time.sleep(pause)               # at trace time only
            return inner(x) + 1
        return jax.jit(step)

    f = jax.jit(lambda x: x * 2)
    for _ in range(4):
        f = level(f)
    before = tracing.totals()
    t0 = time.perf_counter()
    f(jnp.ones(3)).block_until_ready()
    wall = time.perf_counter() - t0
    d = delta(before, tracing.totals())
    # counted once: 4 pauses, under the call's wall time; counted at
    # every level it would be 1 + 2 + 3 + 4 pauses
    assert 4 * pause <= d["compile.s"] <= wall
    assert d["compile.s"] == pytest.approx(d["compile.s.unspanned"])


def test_open_spans_are_per_thread():
    """A compile on one thread is put down to that thread's innermost
    span, whatever another thread has open."""
    f = jax.jit(lambda x: x - 5)
    before = tracing.totals()
    entered, release = threading.Event(), threading.Event()

    def hold():
        with tracing.span("test.other_thread"):
            entered.set()
            release.wait(30)

    t = threading.Thread(target=hold)
    t.start()
    try:
        assert entered.wait(30)
        with tracing.span("test.this_thread"):
            f(jnp.ones(3)).block_until_ready()
    finally:
        release.set()
        t.join(30)
    assert not t.is_alive()
    d = delta(before, tracing.totals())
    assert d["compile.s.test.this_thread"] > 0
    assert "compile.s.test.other_thread" not in d
    assert d["test.other_thread.calls"] == 1


def test_spans_are_profiler_events_with_their_args(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("test.profiled", batch=3, width=7):
            jnp.arange(5).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(tmp_path).glob("plugins/profile/*/*.xplane.pb"))
    events = [e for p in ProfileData.from_file(str(path)).planes
              for line in p.lines for e in line.events
              if e.name == "test.profiled"]
    assert len(events) == 1
    assert dict(events[0].stats) == {"batch": 3, "width": 7}


def live_db(tmp_path) -> Database:
    """A 64-vertex ring with chords, built through CREATE into an
    fsynced AOF: the delta path serves it."""
    db = Database(data_dir=str(tmp_path))
    edges = [(i, (i + 1) % 64) for i in range(64)]
    edges += [(i, (i * 7 + 3) % 64) for i in range(0, 64, 4)]
    db.query("g", "CREATE " + ", ".join(f"({a})-[:KNOWS]->({b})"
                                        for a, b in edges))
    return db


def serve(db: Database, srv, rounds: int, writes: bool) -> None:
    for r in range(rounds):
        for s in range(12):
            srv.submit(KHOP, seeds=[(5 * r + s) % 64])
        srv.pump()
        if writes:
            db.query("g", f"CREATE ({r})-[:KNOWS]->({(r + 30) % 64})")
            db.query("g", f"DELETE ({r + 40})-[:KNOWS]->({r + 41})")
    srv.flush()


def test_served_live_graph_emits_every_span(tmp_path):
    db = live_db(tmp_path)
    srv = db.server("g")
    before = dict(srv.stats)
    kept = dict(before)
    serve(db, srv, rounds=4, writes=True)
    after = dict(srv.stats)
    d = delta(before, after)
    for name in SERVING_SPANS:
        assert d.get(f"{name}.calls", 0) > 0, name
    assert d["repro.submit.calls"] == 48
    assert d["repro.project.reads"] == 48
    # each fetch copies the (n, padded width) float32 frontier
    assert d["repro.fetch.bytes"] == 64 * 16 * 4 * d["repro.fetch.calls"]
    assert d["repro.delta.ops"] > 0
    # the source's mask in the launch and the one expand's target mask
    assert d["repro.mask.calls"] == 2 * d["repro.launch.calls"]
    assert d["repro.aof.fsync.calls"] == d["repro.write.calls"] == 8
    # one batch id per launch, shared by the batch's members
    by_batch = {}
    for m in srv.log:
        by_batch.setdefault(m.batch, []).append(m.qid)
    assert sorted(by_batch) == list(range(d["repro.launch.calls"]))
    assert all(len(q) == 12 for q in by_batch.values())
    # the snapshot taken before serving is unchanged by it
    assert before == kept
    assert all(type(v) in (int, float) for v in after.values())


def test_pump_steps_cover_the_pump(tmp_path):
    db = live_db(tmp_path)
    srv = db.server("g")
    serve(db, srv, rounds=3, writes=True)          # compiles every shape
    before = tracing.totals()
    serve(db, srv, rounds=3, writes=True)
    d = delta(before, tracing.totals())
    steps = sum(d.get(f"{name}.s", 0.0) for name in PUMP_STEPS)
    assert steps <= d["repro.pump.s"]
    assert steps >= 0.9 * d["repro.pump.s"]
