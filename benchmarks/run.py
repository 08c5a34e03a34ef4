"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and persists each app's rows
to ``BENCH_<app>.json`` at the repo root (the per-PR perf trajectory).

``--smoke`` runs every benchmark for a couple of iterations only — the
tier-1 fail-fast mode wired into ``scripts/tier1.sh --smoke``. Smoke runs
never overwrite the persisted trajectory (pass ``--persist`` to force it;
the JSON is then flagged ``"smoke": true``).
"""
from __future__ import annotations

import argparse
import sys

_ROOT = __file__.rsplit("/", 2)[0]
sys.path.insert(0, _ROOT + "/src")
sys.path.insert(0, _ROOT)  # the `benchmarks` package itself


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="a few iterations per arm; implies no persistence")
    ap.add_argument("--no-persist", action="store_true",
                    help="skip writing BENCH_<app>.json")
    ap.add_argument("--persist", action="store_true",
                    help="write BENCH_<app>.json even in smoke mode")
    args = ap.parse_args(argv)
    do_persist = not args.no_persist and (args.persist or not args.smoke)

    from benchmarks import common

    common.SMOKE = args.smoke

    from benchmarks import bench_cpoll, bench_dlrm, bench_kvs, bench_lm, bench_tx

    apps = [
        ("cpoll", "Fig. 7: cpoll vs polling", bench_cpoll),
        ("kvs", "Fig. 8/9/10 + Tab. III: KVS", bench_kvs),
        ("tx", "Fig. 11: chain-replicated transactions", bench_tx),
        ("dlrm", "Fig. 12: DLRM inference", bench_dlrm),
        ("lm", "LM serving: dense vs paged decode", bench_lm),
    ]
    print("name,us_per_call,derived")
    for app, title, mod in apps:
        print(f"# --- {title} ---")
        rows = mod.run()
        if do_persist:
            path = common.persist(app, rows)
            print(f"# wrote {path}")


if __name__ == "__main__":
    main()
