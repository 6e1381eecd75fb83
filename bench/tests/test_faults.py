"""The comparison that decides `correct` fails when the timed path is
broken underneath it, and when the control takes the program's place.
Runs the harness without its chip check, at a scale the CPU holds."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import tiny_root

OPEN, LIVE = "g500s15-khop6-open", "g500s15live-khop2-ycsbB"


def run(root, cell: str, control=None, seed: int = 2**31 + 17) -> dict:
    c = harness.load_cell(cell, root)
    return harness.run_cell(c, seed, 1.0, False, 0.0, jax.devices()[0],
                            root=root, control=control, log=lambda m: None)


def altered_answer(monkeypatch):
    from repro.query.executor import ExecutionContext, Result
    orig = ExecutionContext.project

    def project(self, p, seeds, B):
        r = orig(self, p, seeds, B)
        return Result(r.columns, [(r.rows[0][0] + 1,)], r.error)

    monkeypatch.setattr(ExecutionContext, "project", project)


def half_batch_left_out(monkeypatch):
    from repro.query.executor import ExecutionContext
    orig = ExecutionContext.traverse

    def traverse(self, p, seeds, keep=None):
        B = orig(self, p, seeds, keep)
        return B.at[:, (B.shape[1] + 1) // 2:].set(0)

    monkeypatch.setattr(ExecutionContext, "traverse", traverse)


def state_unchanged(monkeypatch):
    from repro.engine.database import MutableGraph
    orig = MutableGraph.freeze
    first = {}

    def freeze(self, fmt=None, compact=False):
        if id(self) not in first:
            first[id(self)] = orig(self, fmt, compact)
        return first[id(self)]

    monkeypatch.setattr(MutableGraph, "freeze", freeze)


@pytest.mark.parametrize("cell,fault", [
    (OPEN, altered_answer), (OPEN, half_batch_left_out),
    (LIVE, altered_answer), (LIVE, half_batch_left_out),
    (LIVE, state_unchanged)])
def test_fault_makes_the_run_incorrect(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = run(root, cell)
    assert not out["correct"]
    assert out["checks"]["mismatched_counts"]["value"] > 0


@pytest.mark.parametrize("cell", [OPEN, LIVE])
def test_sound_run_is_correct(root, cell):
    out = run(root, cell)
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("cell,scale,k", [(OPEN, 10, 2), (LIVE, 10, 2)])
def test_control_is_not_correct(tmp_path, cell, scale, k):
    """The control answers in the program's place, breaking the guarantee
    its configuration names: one hop short, or one batch stale. The reads
    go k hops: on the small graphs a test holds, six hops either way reach
    every vertex of a component in five already."""
    root = tiny_root(tmp_path, scale)
    c = harness.load_cell(cell, root)
    mix = root / "traffic" / f"{c.workload['traffic']}.json"
    m = json.loads(mix.read_text())
    for r in m["reads"]:
        r["k"] = k
    mix.write_text(json.dumps(m))
    control = c.config["control"]
    out = run(root, cell, control=control)
    assert not out["correct"]
    assert out["checks"]["mismatched_counts"]["value"] > 0
    assert np.isfinite(out["metrics"]["read_p99_ms"]["value"])
