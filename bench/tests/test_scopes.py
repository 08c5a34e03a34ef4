"""``bench/scopes.py``: the dataflow rules on a hand-written program, and
the engine step of both apps compiled on the CPU at tiny sizes, where
every phase must appear and almost every operation must find one."""
from __future__ import annotations

import json
import re

import pytest

from _util import ROOT
from bench.metrics._phases import PHASES, TABLE, phase_of
from bench.scopes import op_scopes, parse

HAND = """HloModule m, is_scheduled=true

%body (p: (s32[], s32[4])) -> (s32[], s32[4]) {
  %p = (s32[], s32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  %v = s32[4]{0} get-tuple-element(%p), index=1
  ROOT %t = (s32[], s32[4]{0}) tuple(%next, %v)
}

%cond (p.1: (s32[], s32[4])) -> pred[] {
  %p.1 = (s32[], s32[4]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %n = s32[] constant(9)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

%fused (param_0: s32[4]) -> s32[4] {
  %param_0 = s32[4]{0} parameter(0)
  ROOT %neg = s32[4]{0} negate(%param_0), metadata={op_name="jit(step)/engine.poll/neg"}
}

ENTRY %main (store: s32[4], x: s32[]) -> (s32[4], s32[4]) {
  %store = s32[4]{0} parameter(0)
  %x = s32[] parameter(1)
  %copy.1 = s32[4]{0} copy(%store)
  %fusion.2 = s32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/engine.apu/tx.commit/scatter" source_file="tx.py"}
  %copy.3 = s32[4]{0} copy(%fusion.2)
  %bitcast.4 = s32[2,2]{1,0} bitcast(%copy.3)
  %tuple.5 = (s32[], s32[4]{0}) tuple(%x, %copy.3)
  %while.6 = (s32[], s32[4]{0}) while(%tuple.5), condition=%cond, body=%body, metadata={op_name="jit(step)/engine.apu/kvs.plan_put/while"}
  %gte.7 = s32[4]{0} get-tuple-element(%while.6), index=1
  %neg.8 = s32[4]{0} negate(%gte.7), metadata={op_name="jit(step)/engine.apu/neg"}
  %c.10 = s32[] constant(0), metadata={op_name="jit(step)"}
  ROOT %tuple.9 = (s32[4]{0}, s32[4]{0}) tuple(%neg.8, %copy.3)
}
"""
COMMIT = "jit(step)/engine.apu/tx.commit/scatter"
PLAN = "jit(step)/engine.apu/kvs.plan_put/while"


def test_dataflow_rules():
    m = op_scopes(HAND)
    assert m["copy.1"] == COMMIT  # an entry parameter's copy: its consumer
    assert m["copy.3"] == m["bitcast.4"] == COMMIT  # the producer's
    assert m["tuple.5"] == COMMIT  # the nearest operand that has a scope
    assert m["gte.7"] == PLAN
    assert m["neg.8"] == "jit(step)/engine.apu/neg"  # its own: the APU alone
    assert m["c.10"] == "jit(step)"  # nothing to take a scope from
    for instr in ("next", "t", "lt", "i.1"):  # loop bookkeeping: the while's
        assert m[instr] == PLAN
    assert "neg" not in m and "param_0" not in m  # fused: never in a trace
    assert {phase_of(m[i]) for i in ("copy.1", "gte.7", "neg.8", "c.10")} == {
        "tx.commit", "kvs.plan_put", "engine.apu", None}


def test_table_names_the_program_scopes():
    from repro.core import engine, kvstore, transaction

    program = engine.SCOPES + kvstore.SCOPES + transaction.SCOPES
    assert sorted(TABLE["scopes"]) == sorted(program)
    assert PHASES == set(program) - {engine.APU}
    # no scope may hide or fake a path fragment ``kernels.json`` matches on
    kernels = json.loads((ROOT / "bench/metrics/kernels.json").read_text())
    frags = {r[k].strip("/") for rules in kernels["kernels"].values()
             for r in rules for k in ("path", "not_path") if k in r}
    assert frags == {"cond", "commit_chain"}
    assert not any(f in s for f in frags for s in program)


def _step_text(app_name: str, deadline: bool) -> str:
    import jax

    from bench.run import BENCH, load
    from repro.core import engine as eng
    from tiny import resize

    cell = {"kvs": ("kvs-ycsb-1kb", "ycsb-a"), "tx": ("tx-chain4-64b", "orca-tx-r4w2")}
    cfg_name, traffic_name = cell[app_name]
    config, traffic = resize(
        json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text()),
        json.loads((BENCH / "traffic" / f"{traffic_name}.json").read_text()))
    app = load(BENCH / "apps" / f"{app_name}.py").App(config, traffic, 5)
    ecfg = eng.EngineConfig(
        num_queues=traffic["queues"], capacity=traffic["window"], req_words=app.words,
        resp_words=app.words, budget=traffic["budget"], kernel_backend="ref",
        deadline_word=app.words - 1 if deadline else -1)
    app_fn = eng.bind_app(app.app_step, app.cfg, ecfg)
    make = app.kv.make if app_name == "kvs" else app.tx.make_chain
    state = jax.eval_shape(lambda: eng.make(ecfg, make(app.cfg)))
    step = jax.jit(lambda s: eng.engine_step(s, app_fn, ecfg))
    return step.lower(state).compile().as_text()


@pytest.fixture(scope="module")
def steps():
    # the KVS step with a deadline word, so the shed phase is compiled in
    return {"kvs": _step_text("kvs", deadline=True), "tx": _step_text("tx", deadline=False)}


def test_every_phase_appears(steps):
    seen = {phase_of(p) for text in steps.values() for p in op_scopes(text).values()}
    assert PHASES <= seen


def test_rank_loops_in_plan_put(steps):
    # kvstore._rank_within's searchsorted over every bucket (64 + 1 at this
    # size): the loops of the PUT plan, twice a step
    m = op_scopes(steps["kvs"])
    loops = re.findall(r"%(while[.\d]*) = \(s32\[\], s32\[65\]", steps["kvs"])
    assert len(loops) == 2
    assert {phase_of(m[w]) for w in loops} == {"kvs.plan_put"}


def test_tx_row_scatter_in_commit(steps):
    # the store scatter over (chain 4, 512 + 1 rows, 16 words), as the
    # device runs it (its fusion; the scatter inside is never in a trace)
    m = op_scopes(steps["tx"])
    rows = [i for i in re.findall(r"%([\w.\-]*scatter[\w.\-]*) = s32\[4,513,16\]",
                                  steps["tx"]) if i in m]
    assert rows and {phase_of(m[i]) for i in rows} == {"tx.commit"}


@pytest.mark.parametrize("app", ["kvs", "tx"])
def test_entry_ops_find_a_phase(steps, app):
    m = op_scopes(steps[app])
    (entry,) = [instrs for is_entry, instrs in parse(steps[app]).values() if is_entry]
    plain = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
    ops = [i.name for i in entry if i.opcode not in plain]
    unphased = [n for n in ops if phase_of(m[n]) not in PHASES]
    assert len(ops) > 50
    assert len(unphased) <= 0.05 * len(ops), [(n, m[n]) for n in unphased]
