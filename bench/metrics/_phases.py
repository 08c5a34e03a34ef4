"""Shared reductions of a ``bench.xplane.Trace`` by named scope: each
operation of the step program is charged to the innermost scope of
``phases.json`` on its op path (``tr.extra["scopes"]``, from
``bench/scopes.py``), and only operations that ran inside a run of the step
program count (an instruction name such as ``fusion.6`` can also belong to
the inject or drain program).

No metric of ``BENCHMARK.json`` reads these yet: ``bench/run.py`` puts no
``scopes`` map in a trace's ``extra``, and every function here then gives
None."""
from __future__ import annotations

import bisect

from bench.scopes import SCOPES, TABLE

PHASES = {s for names in TABLE["metrics"].values() for s in names}


def phase_of(path: str):
    """The innermost scope of the table on an op path, or None."""
    return next((p for p in reversed(path.split("/")) if p in SCOPES), None)


def step_time(tr):
    """({phase or None: self ns summed over the step's runs}, runs) per chip,
    or None where the trace holds no scope map or the program no phase."""
    scopes = tr.extra.get("scopes")
    if not scopes or not any(phase_of(p) in PHASES for p in scopes.values()):
        return None
    lo, hi = tr.window
    out = []
    for c in tr.chips:
        runs = sorted(tr.runs(c, tr.extra["step_module"]))
        if not runs:
            continue
        starts = [s for s, _ in runs]
        by = {}
        for instr, _, s, e, own in tr.ops[c]:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue  # not inside a step run
            full = e - s
            clip = min(e, hi) - max(s, lo)
            ph = phase_of(scopes.get(instr, ""))
            by[ph] = by.get(ph, 0.0) + own * (clip / full if full else 0)
        out.append((by, len(runs)))
    return out or None


def ms_per_step(tr, metric: str):
    """Device ms per step run in the scopes ``phases.json`` gives ``metric``
    (``plan_ms``, ``commit_ms``, ``lookup_ms``, ``ring_ms``), averaged over
    chips."""
    per_chip = step_time(tr)
    names = TABLE["metrics"][metric]
    if per_chip is None or not any(phase_of(p) in names
                                   for p in tr.extra["scopes"].values()):
        return None
    return sum(sum(by.get(n, 0.0) for n in names) / runs
               for by, runs in per_chip) / len(per_chip) * 1e-6


def unscoped_share(tr):
    """Percent of the step program's operation self time charged to none of
    the phases that ``phases.json`` gives a metric (operations charged only
    to ``engine.apu`` count as unscoped), averaged over chips."""
    per_chip = step_time(tr)
    if per_chip is None:
        return None
    shares = [100.0 * sum(t for p, t in by.items() if p not in PHASES) / total
              for by, _ in per_chip if (total := sum(by.values())) > 0]
    return sum(shares) / len(shares) if shares else None
