"""CPU rehearsals of every cell at tiny sizes: the run drives the real
entry point, prints a result line of the contract's shape, and every
answer matches the reference."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from _util import ROOT, run_tiny

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    p, res = run_tiny(cell, seed=2 ** 31 + 77)
    assert p.returncode == 0, p.stderr[-3000:]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 1
    # the compared numbers are the last lines of standard error
    tail = p.stderr.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def _bare_run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[-1],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _bare_run(ROOT, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bare_run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
