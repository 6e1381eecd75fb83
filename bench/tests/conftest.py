"""Fixtures for the benchmark's CPU self-tests: a registry copied from
`bench/` with the configurations cut to a scale the CPU runs in seconds."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

TINY_SCALE = 8


def tiny_root(tmp: Path, scale: int = TINY_SCALE) -> Path:
    """bench/'s registry with every configuration at `scale` and a peaks
    row for the CPU, so a whole run fits a test."""
    root = tmp / "bench"
    for d in ("configs", "workloads", "traffic", "loops", "metrics"):
        shutil.copytree(BENCH / d, root / d)
    for p in (root / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        cfg["scale"] = scale
        p.write_text(json.dumps(cfg))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
    (root / "peaks.json").write_text(json.dumps(peaks))
    return root


@pytest.fixture
def root(tmp_path):
    return tiny_root(tmp_path)


@pytest.fixture(autouse=True)
def _cpu_only():
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        pytest.skip("bench self-tests run with JAX_PLATFORMS=cpu")
