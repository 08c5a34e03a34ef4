"""Shared reductions of a ``bench.xplane.Trace`` for the metric readers:
which operations belong to a kernel or a copy (the table in
``kernels.json``), and the time they took."""
from __future__ import annotations

import json
import re
from pathlib import Path

from bench.xplane import base_name

TABLE = json.loads((Path(__file__).with_name("kernels.json")).read_text())
COPY = re.compile(TABLE["copies"])


def is_pallas(tr, instr: str) -> bool:
    return instr in tr.extra["paths"]


def in_kernel(tr, kernel: str, instr: str) -> bool:
    if not is_pallas(tr, instr):
        return False
    path = tr.extra["paths"][instr]
    for rule in TABLE["kernels"][kernel]:
        if base_name(instr) != rule["instr"]:
            continue
        if "path" in rule and rule["path"] not in path:
            continue
        if "not_path" in rule and rule["not_path"] in path:
            continue
        return True
    return False


def mean_self_ns(tr, pick) -> float:
    """Self time of the picked operations, averaged over the chips."""
    return sum(tr.self_ns(c, pick) for c in tr.chips) / len(tr.chips)


def share_of_busy(tr, pick):
    """Percent of device busy time spent in the picked operations, or None
    where the trace holds none of them."""
    t = mean_self_ns(tr, pick)
    busy = tr.mean_busy_ns()
    return 100.0 * t / busy if t > 0 and busy > 0 else None


def roofline(tr, kernel: str):
    """Percent of its roofline a kernel reached: the least time the chip
    needs for the work (``bench/counts``), over the kernel's trace time."""
    work = tr.extra["work"].get(kernel)
    t = mean_self_ns(tr, lambda i, s: in_kernel(tr, kernel, i)) * 1e-9
    if not work or t <= 0:
        return None
    nbytes, ops = work
    if nbytes <= 0 and ops <= 0:
        return None
    peak = tr.extra["peak"]
    least = max(nbytes / peak["hbm_bytes_per_s"], ops / peak["int8_ops_per_s"])
    return 100.0 * least / t
