"""The phase reductions of ``bench/metrics/_phases.py`` on the hand-made
trace of ``test_reducers.py``, given an op-path map, and on the small trace
recorded on a TPU v5 lite before the program had named scopes."""
from __future__ import annotations

import json

import pytest

from bench.metrics._phases import ms_per_step, unscoped_share
from bench.xplane import Trace
from test_reducers import BENCH, PEAK, hand, read  # noqa: F401 (hand: a fixture)

# op paths of the hand trace's step program, as ``bench/scopes.py`` gives
# them; fusion.6 of the step program is the cpoll scan, but the trace's
# fusion.6 ran in the inject program and must not count
SCOPED = {
    "while.1": "jit(step)/engine.apu/kvs.plan_put/jit(searchsorted)/while",
    "fusion.2": "jit(step)/engine.apu/kvs.plan_put/jit(searchsorted)/while/body/add",
    "copy.3": "jit(step)/engine.apu/tx.commit/jit(commit_chain)/scatter",
    "probe.4": "jit(step)/engine.apu/kvs.get/cond/branch_0_fun/jit(probe)/pallas_call",
    "scatter.5": "jit(step)/engine.apu/tx.commit/jit(commit_chain)/jit(scatter)/pallas_call",
    "fusion.6": "jit(step)/engine.poll/sub",
}
MS = ("plan_ms", "commit_ms", "lookup_ms", "ring_ms")


def phases(tr):
    return {**{m: ms_per_step(tr, m) for m in MS}, "unscoped_share": unscoped_share(tr)}


def test_hand_trace_phases(hand):
    hand.extra["scopes"] = dict(SCOPED)
    # one step run (100..600 ns): plan = while.1 100 + fusion.2 200 self ns
    assert ms_per_step(hand, "plan_ms") == pytest.approx(300e-6)
    assert ms_per_step(hand, "commit_ms") == pytest.approx((100 + 50) * 1e-6)  # copy.3, scatter.5
    assert ms_per_step(hand, "lookup_ms") == pytest.approx(50e-6)  # probe.4
    assert ms_per_step(hand, "ring_ms") == 0.0  # the inject program's fusion.6 is left out
    assert unscoped_share(hand) == 0.0
    # the copy charged only to the APU: unscoped, 100 of 500 self ns
    hand.extra["scopes"]["copy.3"] = "jit(step)/engine.apu/copy"
    assert ms_per_step(hand, "commit_ms") == pytest.approx(50e-6)
    assert unscoped_share(hand) == pytest.approx(20.0)


def test_hand_trace_phases_absent(hand):
    # a program with no named scopes (the parent's), or no map at all
    hand.extra["scopes"] = {k: "jit(step)/" + k for k in SCOPED}
    assert all(v is None for v in phases(hand).values())
    del hand.extra["scopes"]
    assert all(v is None for v in phases(hand).values())


def test_recorded_tx_trace_phases_absent():
    """The trace recorded before the program had named scopes: every
    existing metric reads as it did, the phase reductions read nothing."""
    fx = json.loads((BENCH / "tests/fixtures/tx_engine_trace.json").read_text())
    before = {"idle_share": 14.83599106701653, "step_device_ms": 17.533903,
              "copy_share": 86.7537277567213, "kernel_share": 8.402879170409463,
              "tx_commit_roofline": 0.04019262224177898,
              "step_mfu": 0.0028762759952045648, "host_ms_per_step": 0.0}
    unscoped = {i: "jit(<lambda>)/" + i for i, *_ in fx["ops"]["0"]}
    for scopes in (None, unscoped):
        extra = {"rounds": 2, "step_module": "step", "peak": PEAK, "paths": fx["paths"],
                 "work": {"tx_commit": (1e6, 0), "step": (1e6, 0)}}
        if scopes is not None:
            extra["scopes"] = scopes
        tr = Trace.from_events({0: fx["ops"]["0"]}, {0: fx["modules"]["0"]},
                               fx["spans"], tuple(fx["window"]), extra)
        assert {m: read(m, tr) for m in before} == pytest.approx(before, rel=1e-12)
        assert all(v is None for v in phases(tr).values())
