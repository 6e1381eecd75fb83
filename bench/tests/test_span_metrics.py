"""The readers of the program's spans and counters, on hand-built
observations with known answers, and on a traced run of the live cell."""
from __future__ import annotations

import json
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest
from pytest import approx

from bench import harness
from bench.harness import load_module
from bench.tests.conftest import BENCH

LIVE = "g500s15live-khop2-ycsbB"
# the window's delta of each key read, over stats0 of ones
WINDOW = {
    "repro.submit.calls": 1000, "repro.submit.s": 0.016,
    "repro.pump.calls": 40, "repro.pump.s": 9.0, "repro.pump.cpu_s": 4.0,
    "repro.refresh.calls": 40, "repro.refresh.s": 2.0,
    "repro.launch.calls": 40, "repro.launch.s": 4.0,
    "repro.mask.calls": 80, "repro.mask.s": 2.5,
    "repro.fetch.calls": 40, "repro.fetch.s": 3.0, "repro.fetch.bytes": 5e6,
    "repro.project.calls": 40, "repro.project.s": 0.5,
    "repro.project.reads": 1000,
    "repro.write.calls": 50, "repro.write.s": 0.1,
    "repro.aof.fsync.calls": 50, "repro.aof.fsync.s": 0.04,
    "compile.s": 7.5,
}
WANT = {
    "plan_us_mean": 16.0,
    "refresh_ms_mean": 50.0,
    "launch_ms_mean": 100.0,
    "fetch_ms_mean": 75.0,
    "project_us_per_read": 500.0,
    "frontier_bytes_per_read": 5000.0,
    "compile_s_in_window": 7.5,
    "fsync_ms_mean": 0.8,
    "host_span_busy_pct": 100.0 * (4.0 + 0.016 + 0.1) / 20.0,
}


def reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def observation(window: dict, writes=("w",), trace=True):
    stats0 = {k: 1 for k in window}
    stats1 = {k: 1 + v for k, v in window.items()}
    return NS(stats0=stats0, stats1=stats1, writes=list(writes),
              trace={"window_s": 20.0} if trace else None)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_window_deltas(name):
    assert reader(name).read(observation(WINDOW)) == approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_where_the_program_has_no_spans(name):
    """The parent program's stats hold none of these keys: every reader
    returns None and raises nothing."""
    assert reader(name).read(observation({"queries": 5})) is None


@pytest.mark.parametrize("name,missing", [
    ("plan_us_mean", {"repro.submit.calls": 0}),
    ("refresh_ms_mean", {"repro.refresh.calls": 0}),
    ("launch_ms_mean", {"repro.launch.calls": 0}),
    ("fetch_ms_mean", {"repro.fetch.calls": 0}),
    ("project_us_per_read", {"repro.project.reads": 0}),
    ("frontier_bytes_per_read", {"repro.project.reads": 0}),
    ("fsync_ms_mean", {"repro.aof.fsync.calls": 0}),
    ("host_span_busy_pct", {"repro.pump.calls": 0}),
])
def test_reader_finds_nothing_where_the_window_made_no_calls(name, missing):
    assert reader(name).read(observation({**WINDOW, **missing})) is None


def test_readers_that_need_writes_or_a_trace():
    assert reader("refresh_ms_mean").read(
        observation(WINDOW, writes=())) is None
    assert reader("host_span_busy_pct").read(
        observation(WINDOW, trace=False)) is None
    # a window that compiled nothing reads 0, not None
    assert reader("compile_s_in_window").read(
        observation({**WINDOW, "compile.s": 0.0})) == 0.0


def test_program_compile_count_equals_the_harness_listener():
    from repro import tracing
    harness._Compiles.listen(jax)
    c0 = harness._Compiles.count
    t0 = tracing.totals().get("compile.count", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()
    jax.jit(lambda x: x.sum(axis=0))(jnp.ones((2, 3))).block_until_ready()
    jnp.arange(9).reshape(3, 3).T.block_until_ready()
    built = harness._Compiles.count - c0
    assert built >= 2
    assert tracing.totals()["compile.count"] - t0 == built


def test_traced_live_run_reads_every_new_metric(root):
    """Each metric BENCHMARK.json lists for the live cell, or lists for
    no cell in particular, reads a number in a traced run of it."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = harness.load_cell(LIVE, root)
    out = harness.run_cell(cell, 2**31 + 3, 1.0, True, 0.0, jax.devices()[0],
                           root=root, log=lambda m: None)
    assert out["correct"]
    for name in WANT:
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        assert LIVE in entry.get("workloads", [LIVE])
        assert out["metrics"][name]["unit"] == entry["unit"], name
