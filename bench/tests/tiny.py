"""Run a cell, or a cell with a planted fault, at a size a CPU can hold.

    JAX_PLATFORMS=cpu python3 bench/tests/tiny.py --workload tx-chain4-r4w2 --seed 5 --seconds 1
    (add --fault <name> to plant one of bench/faults.py)

Widths are as in the configuration; only counts are cut: buckets, pool,
records, rows, clients and batch. The harness's look for a TPU is skipped.
"""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def resize(config: dict, traffic: dict):
    config, traffic = dict(config), dict(traffic)
    if config["app"] == "kvs":
        config.update(num_buckets=64, pool_size=1024, cache_sets=16,
                      records=256, load_batch=64)
        traffic.update(readback=32)
    else:
        config.update(num_keys=512, log_capacity=64)
    traffic.update(queues=2, window=8, budget=8, warm_rounds=2)
    return config, traffic


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import control, run

    run.FINISH_SECONDS = 5.0
    if "--fault" in argv:
        return control.main(argv, platform="cpu", resize=resize)
    return run.main(argv, platform="cpu", resize=resize)


if __name__ == "__main__":
    sys.exit(main())
