"""A new cell is a new workload file: the command finds it by name with
no edit to any code, and the harness runs it end to end."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.tests.conftest import BENCH

NEW = {"config": "graph500-s15", "traffic": "khop6-uniform", "chips": 1,
       "load": {"rate_qps": 200}}


def command(tree, workload: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(tree / "bench" / "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)


def test_new_workload_file_is_a_cell_the_command_accepts(tmp_path):
    tree = tmp_path / "checkout"
    shutil.copytree(BENCH, tree / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tree / "src").symlink_to(BENCH.parent / "src")
    before = command(tree, "new-cell")
    assert before.returncode != 0 and "unknown workload" in before.stderr
    (tree / "bench" / "workloads" / "new-cell.json").write_text(
        json.dumps(NEW))
    after = command(tree, "new-cell")
    # found, then refused only for want of a TPU, naming the device
    assert after.returncode != 0 and after.stdout == ""
    assert "needs a TPU" in after.stderr and "[cpu " in after.stderr


def test_new_workload_runs_end_to_end(root):
    (root / "workloads" / "new-cell.json").write_text(json.dumps(NEW))
    cell = harness.load_cell("new-cell", root)
    out = harness.run_cell(cell, 2**31 + 1, 1.0, False, 0.0,
                           jax.devices()[0], root=root, log=lambda m: None)
    assert out["correct"] and out["attempted"] > 0
    assert set(out["metrics"]) == {"read_qps", "read_p50_ms", "read_p99_ms",
                                   "device_bytes_per_edge", "setup_s"}
    assert list(out)[-1] == "checks"


def test_benchmark_json_names_what_the_registry_holds():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.workload["config"] == w["config"]
        assert cell.workload["traffic"] == w["traffic"]
        assert cell.workload["chips"] == w["chips"]
    for c in spec["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    readers = harness.metric_readers()
    for m in spec["per_layer"]:
        assert readers[m["name"]].UNIT == m["unit"]


class _BuildsEveryPass:
    """A loop whose every warm-up pass builds an executable."""
    passes = 0

    @classmethod
    def warm(cls, target, traffic, load, rel):
        cls.passes += 1
        harness._Compiles.count += 1


@pytest.mark.parametrize("unsettled", [None, 3])
def test_warm_up_that_never_settles(root, tmp_path, monkeypatch, unsettled):
    """A run whose warm-up still builds after its last pass is not
    measured, unless its workload file says after how many passes such a
    cell is measured as it stands."""
    cell = harness.load_cell("g500s15-khop6-open", root)
    if unsettled:
        cell.workload["measure_unsettled_after"] = unsettled
    _BuildsEveryPass.passes = 0
    monkeypatch.setattr(cell, "loop", _BuildsEveryPass)
    if unsettled:
        harness.set_up(cell, 5, tmp_path / "state", 0.0, log=lambda m: None)
        assert _BuildsEveryPass.passes == unsettled
    else:
        said = []
        with pytest.raises(SystemExit):
            harness.set_up(cell, 5, tmp_path / "state", 0.0, log=said.append)
        assert "not measured" in said[-1]
        assert _BuildsEveryPass.passes == harness.WARM_MAX_PASSES
