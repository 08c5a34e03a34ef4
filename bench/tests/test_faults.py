"""With the timed path broken underneath, a run's ``correct`` comes out
false: each fault the cell can have, and each app's control."""
from __future__ import annotations

import pytest

from _util import run_tiny

ENGINE = ("unchanged_state", "half_batch", "altered_answer")
CASES = ([("kvs-ycsb-b", f) for f in ENGINE + ("lost_write",)]
         + [("kvs-ycsb-a", "lost_write")]
         + [("tx-chain4-r4w2", f) for f in ENGINE + ("replica_skipped",)])


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(cell, fault):
    p, res = run_tiny(cell, seed=11, fault=fault)
    assert p.returncode == 0, p.stderr[-3000:]
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
