#!/usr/bin/env python3
"""Run one cell with a fault planted under its timed path (``bench/faults.py``)
and print the run's result, whose ``checks`` must then fail.

    python3 bench/control.py --fault lost_write --workload kvs-ycsb-b-zipf99 --seed 11 --seconds 5 --trace 0

The benchmark's own runs never plant a fault. This is how each cell's
control and faults are read on the chip at the cell's own size; the tests
in ``bench/tests`` run the same at a size a CPU can hold.
"""
from __future__ import annotations

import sys
from pathlib import Path


def main(argv=None, **run_kw) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--fault")
    fault = argv[i + 1]
    del argv[i:i + 2]
    root = Path(__file__).resolve().parents[1]
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import faults
    from bench.run import main as run

    remove = faults.plant(fault)
    try:
        return run(argv, **run_kw)
    finally:
        remove()


if __name__ == "__main__":
    sys.exit(main())
