"""The op path of every instruction of a compiled program, for charging
device time to the program's named scopes (``metrics/phases.json``).

A compiled program's HLO text records, in each instruction's
``metadata={op_name="..."}``, the JAX op path that made it, named scopes
included: ``jit(step)/engine.apu/kvs.plan_put/jit(searchsorted)/while``.
Instructions the compiler inserts carry no scope there, or no metadata at
all: the layout copies around a scatter or a Pallas call, the tuples and
``get-tuple-element``s of a loop. Those take a scope by dataflow, in order:

1. from their nearest operand that has one (``get-tuple-element``,
   ``tuple`` and ``bitcast`` pass their operand's on, so a copy of a
   scatter's result is charged to the scatter's phase);
2. else from their first consumer in the computation's order that has
   one (a copy of an entry parameter is charged to the phase that reads
   it);
3. else from the instruction that calls their computation (a ``while``
   body's loop bookkeeping is charged to the ``while``'s phase).

Only computations the device runs op by op are mapped: the entry, loop
bodies and conditions, branches and calls. A fusion's inner instructions
never show in a trace, so fused computations are left out.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import NamedTuple

TABLE = json.loads((Path(__file__).parent / "metrics" / "phases.json").read_text())
SCOPES = frozenset(TABLE["scopes"])

_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation|"
    r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")


class Instr(NamedTuple):
    name: str
    opcode: str
    operands: list
    op_name: str  # "" where the instruction has no metadata
    called: list  # computations it runs


def _closing(text: str, open_at: int = 0) -> int:
    """Index of the paren that closes the one at ``open_at``."""
    depth = 0
    for i in range(open_at, len(text)):
        depth += text[i] == "("
        depth -= text[i] == ")"
        if depth == 0:
            return i
    return len(text)


def parse(hlo_text: str) -> dict:
    """computation name -> (is the entry, [Instr] in the text's order)."""
    comps, instrs = {}, None
    for line in hlo_text.splitlines():
        if instrs is None:
            m = _COMP.match(line)  # a header starts its line; instructions are indented
            if m:
                instrs = []
                comps[m.group(2)] = (bool(m.group(1)), instrs)
            continue
        if line.strip() == "}":
            instrs = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(2)  # the result type, then the operation
        if rest.startswith("("):
            rest = rest[_closing(rest) + 1:]
        else:
            rest = rest.split(" ", 1)[-1]
        op = _OPCODE.match(rest)
        if not op:
            continue
        args = rest[op.end() - 1:_closing(rest, op.end() - 1)]
        called = [c for v in _CALLED.findall(rest)
                  for c in (_OPERAND.findall(v) or [v.lstrip("%")])]
        name = _OP_NAME.search(rest)
        instrs.append(Instr(m.group(1), op.group(1), _OPERAND.findall(args),
                            name.group(1) if name else "", called))
    return comps


def _has_scope(path: str) -> bool:
    return any(part in SCOPES for part in path.split("/"))


def op_scopes(hlo_text: str) -> dict:
    """instruction -> op path, for every instruction of every computation
    the device runs op by op. An instruction whose own path holds none of
    the scopes of ``metrics/phases.json`` takes a path that does by the
    dataflow rules above, or keeps its own."""
    comps = parse(hlo_text)
    fused, caller = set(), {}  # caller: computation -> the instruction that runs it
    for _, instrs in comps.values():
        for ins in instrs:
            for c in ins.called:
                if ins.opcode == "fusion":
                    fused.add(c)
                else:
                    caller.setdefault(c, ins.name)
    paths = {}
    # callers print after the computations they call: walk the text backwards
    for cname in reversed(list(comps)):
        if cname in fused:
            continue
        instrs = comps[cname][1]
        got = {i.name: i.op_name for i in instrs if _has_scope(i.op_name)}
        users = {}
        for ins in instrs:  # 1. the nearest operand with a scope
            for o in ins.operands:
                users.setdefault(o, []).append(ins.name)
            src = next((o for o in ins.operands if o in got), None)
            if ins.name not in got and src is not None:
                got[ins.name] = got[src]
        for ins in reversed(instrs):  # 2. the first consumer with a scope
            dst = next((u for u in users.get(ins.name, []) if u in got), None)
            if ins.name not in got and dst is not None:
                got[ins.name] = got[dst]
        above = paths.get(caller.get(cname), "")
        for ins in instrs:  # 3. the instruction that calls the computation
            paths[ins.name] = got.get(ins.name) or (
                above if _has_scope(above) else ins.op_name)
    return paths
