"""Profiler capture and its reduction to plain event lists.

``Capture`` records one traced window with ``jax.profiler`` into a
temporary directory and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``. ``Trace`` holds what the per-layer metric
readers under ``bench/metrics/`` take:

* ``ops[chip]``: device operations of the ``XLA Ops`` line, each
  ``(instr, shape, start_ns, end_ns, self_ns)``. Events nest on that line
  (a ``while`` holds its body's operations), so ``self_ns`` is the time not
  covered by a nested operation: summing ``self_ns`` never counts a
  nanosecond twice.
* ``async_ops[chip]``: the ``Async XLA Ops`` line (copies and collectives
  in flight beside the compute stream), each ``(instr, shape, start_ns,
  end_ns)``.
* ``modules[chip]``: executions of whole programs (``XLA Modules``), each
  ``(name, start_ns, end_ns)``; ``name`` is the jitted function's name.
* ``spans``: the benchmark's own host spans, each ``(name, start_ns,
  end_ns)``.
* ``window``: ``(start_ns, end_ns)`` of the traced rounds.

Everything is clipped to ``window``. ``Trace.from_events`` builds one from
lists of this shape, which is how the tests feed recorded fixtures.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile
from dataclasses import dataclass, field

# an XLA Ops event name is the HLO text of the instruction:
#   "%copy.307 = s32[1,1048577,256]{2,1,0:T(8,128)} copy(...), ..."
_INSTR = re.compile(r"^%?([^\s=]+)\s*=\s*(\(?[a-z0-9]+\[[^\]]*\])?")
# jit_step(1234567) -> step
_MODULE = re.compile(r"^jit_(.*?)(\(\d+\))?$")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def instr_name(event_name: str):
    """(instruction name, result shape) from an XLA op event's name."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name.split(" ", 1)[0].lstrip("%"), ""
    return m.group(1), (m.group(2) or "").lstrip("(")


def base_name(instr: str) -> str:
    """``probe.3`` -> ``probe``; ``copy-start.12`` -> ``copy-start``."""
    return re.sub(r"\.\d+$", "", instr)


def module_name(event_name: str) -> str:
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def self_times(events):
    """events: [(instr, shape, start, end)] of one line -> the same with a
    fifth field, the time not covered by events nested inside."""
    events = sorted(events, key=lambda e: (e[2], -(e[3] - e[2])))
    out = [[*e, e[3] - e[2]] for e in events]
    stack = []  # indices of open events
    for i, e in enumerate(out):
        while stack and out[stack[-1]][3] <= e[2]:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[4] -= min(e[3], parent[3]) - e[2]
        stack.append(i)
    return [tuple(e) for e in out]


def union_ns(intervals, lo, hi) -> int:
    """Length of the union of ``(start, end)`` intervals inside [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclass
class Trace:
    ops: dict
    modules: dict
    spans: list
    window: tuple
    extra: dict = field(default_factory=dict)
    async_ops: dict = field(default_factory=dict)

    @classmethod
    def from_events(cls, ops, modules, spans, window, extra=None, async_ops=None):
        lo, hi = window
        inside = lambda s, e: e > lo and s < hi  # noqa: E731
        return cls(
            ops={c: [o for o in self_times([tuple(x[:4]) for x in v])
                     if inside(o[2], o[3])] for c, v in ops.items()},
            async_ops={c: [tuple(o[:4]) for o in v if inside(o[2], o[3])]
                       for c, v in (async_ops or {}).items()},
            modules={c: [m for m in v if inside(m[1], m[2])]
                     for c, v in modules.items()},
            spans=[s for s in spans if inside(s[1], s[2])],
            window=window, extra=dict(extra or {}))

    @property
    def chips(self):
        return sorted(self.modules)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busy_ns(self, chip) -> int:
        """Device busy time of one chip: the union of its program runs."""
        return union_ns([(s, e) for _, s, e in self.modules[chip]], *self.window)

    def mean_busy_ns(self) -> float:
        return sum(self.busy_ns(c) for c in self.chips) / len(self.chips)

    def self_ns(self, chip, pick) -> int:
        """Summed self time of the operations ``pick(instr, shape)`` keeps,
        clipped to the window."""
        lo, hi = self.window
        total = 0
        for instr, shape, s, e, own in self.ops[chip]:
            if pick(instr, shape):
                full = e - s
                clip = min(e, hi) - max(s, lo)
                total += own * (clip / full if full else 0)
        return int(total)

    def union_of(self, chip, pick) -> int:
        """Time in which any picked operation, on the compute stream or in
        flight beside it, was running on one chip."""
        spans = [(s, e) for i, sh, s, e, *_ in self.ops[chip] + self.async_ops.get(chip, [])
                 if pick(i, sh)]
        return union_ns(spans, *self.window)

    def runs(self, chip, module: str):
        return [(s, e) for n, s, e in self.modules[chip] if n == module]


class Capture:
    """``with Capture() as cap: ...`` traces the block; ``cap.read()`` then
    gives the raw planes as a ``Trace`` over the spans' window."""

    SPAN = "round"

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def read(self, span_names) -> Trace:
        from jax.profiler import ProfileData

        try:
            (path,) = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            data = ProfileData.from_file(path)
            ops, async_ops, modules, spans = {}, {}, {}, []
            for plane in data.planes:
                m = _DEVICE.match(plane.name)
                for line in plane.lines:
                    if m and line.name == "XLA Ops":
                        ops[int(m.group(1))] = [
                            (*instr_name(e.name), int(e.start_ns), int(e.end_ns))
                            for e in line.events]
                    elif m and line.name == "Async XLA Ops":
                        async_ops[int(m.group(1))] = [
                            (*instr_name(e.name), int(e.start_ns), int(e.end_ns))
                            for e in line.events]
                    elif m and line.name == "XLA Modules":
                        modules[int(m.group(1))] = [
                            (module_name(e.name), int(e.start_ns), int(e.end_ns))
                            for e in line.events]
                    elif plane.name.startswith("/host:"):
                        spans += [(e.name, int(e.start_ns), int(e.end_ns))
                                  for e in line.events if e.name in span_names]
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        rounds = [s for s in spans if s[0] == self.SPAN]
        if not rounds or not modules:
            raise RuntimeError("the trace holds no traced round or no device program")
        window = (min(s[1] for s in rounds), max(s[2] for s in rounds))
        return Trace.from_events(ops, modules, spans, window,
                                 {"rounds": len(rounds)}, async_ops)
