"""Helpers of the benchmark's tests: run ``tiny.py`` in a child process
(a fresh JAX) and parse the result line."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
TINY = Path(__file__).with_name("tiny.py")


def run_tiny(workload: str, seed: int = 5, fault: str | None = None,
             seconds: float = 1.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = [sys.executable, str(TINY), "--workload", workload, "--seed",
            str(seed), "--seconds", str(seconds)]
    if fault:
        args += ["--fault", fault]
    p = subprocess.run(args, cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return p, result
