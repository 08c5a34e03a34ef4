"""The trace reducers on a hand-made trace whose answers are worked by
hand, and on a small trace recorded on a TPU v5 lite."""
from __future__ import annotations

import json

import pytest

from _util import ROOT
from bench.run import breakdown, load
from bench.xplane import Trace, instr_name, self_times, union_ns

BENCH = ROOT / "bench"
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
BW = PEAK["hbm_bytes_per_s"]


def read(name, tr):
    return load(BENCH / "metrics" / f"{name}.py").read(tr)


@pytest.fixture
def hand():
    ops = {0: [
        ("while.1", "s32[]", 100, 400),
        ("fusion.2", "s32[9]", 150, 350),          # nested in while.1
        ("copy.3", "s32[8,128]", 400, 500),
        ("probe.4", "s32[256,2]", 500, 550),       # Pallas, GET walk
        ("scatter.5", "s32[4,9,16]", 550, 600),    # Pallas, TX commit
        ("fusion.6", "s32[256]", 650, 700),       # the inject program
    ]}
    modules = {0: [("step", 100, 600), ("inject", 650, 700), ("drain", 800, 850)]}
    spans = [("round", 0, 1000), ("make", 0, 90), ("inject", 640, 710),
             ("dispatch", 710, 720), ("wait", 720, 900), ("record", 900, 950)]
    extra = {
        "rounds": 1, "step_module": "step", "peak": PEAK,
        "paths": {"probe.4": "jit(step)/cond/branch_0_fun/jit(probe)/pallas_call",
                  "scatter.5": "jit(step)/jit(commit_chain)/jit(scatter)/pallas_call"},
        # 50% of probe.4's 50 ns, 100% of scatter.5's, 1% of the 1000 ns window
        "work": {"hash_get": (BW * 25e-9, 0), "tx_commit": (BW * 50e-9, 0),
                 "step": (BW * 10e-9, 0)},
        "hit_rate": (3, 4),
        # window rounds (s): the one over twice the median (1.0) loses 4.0
        "round_s": [1.0, 1.0, 0.9, 1.1, 5.0],
    }
    return Trace.from_events(ops, modules, spans, (0, 1000), extra)


def test_self_times_and_union():
    t = self_times([("a", "", 0, 10), ("b", "", 2, 5), ("c", "", 5, 7), ("d", "", 20, 30)])
    assert [x[4] for x in t] == [5, 3, 2, 10]
    assert union_ns([(0, 10), (5, 15), (20, 30)], 0, 25) == 20


def test_instr_name():
    assert instr_name("%copy.307 = s32[1,1048577,256]{2,1,0:T(8,128)} copy(s32[1]") == (
        "copy.307", "s32[1,1048577,256]")
    assert instr_name("%cache_probe.2 = (s32[256,2]{1,0}, s32[256,256]) custom-call(")[0] == "cache_probe.2"


def test_hand_trace(hand):
    busy = 600  # 500 + 50 + 50 ns of program runs
    assert hand.mean_busy_ns() == busy
    assert read("idle_share", hand) == pytest.approx(40.0)
    assert read("step_device_ms", hand) == pytest.approx(500e-6)
    assert read("copy_share", hand) == pytest.approx(100 * 100 / busy)
    assert read("kernel_share", hand) == pytest.approx(100 * 100 / busy)
    assert read("host_ms_per_step", hand) == pytest.approx((90 + 70 + 10 + 50) * 1e-6)
    assert read("hash_get_roofline", hand) == pytest.approx(50.0)
    assert read("tx_commit_roofline", hand) == pytest.approx(100.0)
    assert read("hash_put_roofline", hand) is None  # no such kernel ran
    assert read("cache_probe_roofline", hand) is None
    assert read("step_mfu", hand) == pytest.approx(1.0)
    assert read("cache_hit_rate", hand) == pytest.approx(75.0)
    assert read("stall_share", hand) == pytest.approx(100 * 4.0 / 9.0)


def test_breakdown(hand):
    b = breakdown(hand, hand.spans)
    assert b["device_ops"][0][0] == "fusion.2 s32[9]"
    gaps = dict(b["idle_gaps"])
    assert gaps["host:make"] == pytest.approx(100e-9)   # 0..100, mid in make
    assert gaps["host:wait"] == pytest.approx(100e-9)   # 700..800
    assert gaps["host:record"] == pytest.approx(150e-9)  # 850..1000


def test_recorded_tx_trace():
    fx = json.loads((BENCH / "tests/fixtures/tx_engine_trace.json").read_text())
    extra = {"rounds": 2, "step_module": "step", "peak": PEAK, "paths": fx["paths"],
             "work": {"tx_commit": (1e6, 0), "step": (1e6, 0)}}
    tr = Trace.from_events({0: fx["ops"]["0"]}, {0: fx["modules"]["0"]},
                           fx["spans"], tuple(fx["window"]), extra)
    lo, hi = tr.window
    busy = union_ns([(s, e) for _, s, e in fx["modules"]["0"]], lo, hi)
    kernels = sum(min(e, hi) - max(s, lo) for i, _, s, e in fx["ops"]["0"]
                  if i in fx["paths"])
    assert read("kernel_share", tr) == pytest.approx(100 * kernels / busy)
    assert 50 < read("copy_share", tr) < 100  # the store's relayout copies
    steps = [e - s for n, s, e in fx["modules"]["0"] if n == "step"]
    assert read("step_device_ms", tr) == pytest.approx(1e-6 * sum(steps) / len(steps))
    assert 0 < read("idle_share", tr) < 100
    assert 0 < read("tx_commit_roofline", tr) < 100
