#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload kvs-ycsb-b-zipf99 --seed 7 --seconds 20 --trace 0

The cell names a configuration (``bench/configs/<name>.json``, which names
its app, ``bench/apps/<app>.py``) and a traffic mix
(``bench/traffic/<name>.json``, which names its generator,
``bench/generators/<gen>.py``, and the driver of the entry point the window
drives, ``bench/drivers/<driver>.py``). Everything is found by name, so a
new cell is new files and new entries, never an edit.

A run builds the state on the device from ``--seed``, warms every program
the window uses, then drives the system for ``--seconds`` with closed-loop
clients. With ``--trace 0`` it reports the cell's end-to-end metrics from
the host clock; with ``--trace 1`` it traces the first seconds of the
window with ``jax.profiler`` and reports the per-layer metrics that
``bench/metrics/<name>.py`` read from that trace. Either way, once the
window has closed it answers every outstanding request, reads back what the
app promises to keep, frees the device state, and compares every answer
with the plain reference under ``bench/reference/``. The compared numbers
are printed, each beside its limit, as the last lines of standard error and
under ``checks``, last in the result line, the last line of standard output.

It exits non-zero without a result line when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 4.0  # traced part of a --trace 1 window
FINISH_SECONDS = 60.0  # allowance to answer what is outstanding at the close


def load(path: Path):
    """Import a file of the benchmark by its path."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(spec: dict, name: str):
    """The cell's entry, configuration, traffic and metric entries."""
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return cell, config, traffic, e2e, per_layer


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def breakdown(tr, spans) -> dict:
    """The device operations that took most time, and the idle gaps between
    programs by the host span they fell in (summed per span)."""
    ops = collections.Counter()
    for c in tr.chips:
        for instr, shape, s, e, own in tr.ops[c]:
            ops[f"{instr} {shape}".strip()] += own / 1e9 / len(tr.chips)
    gaps = collections.Counter()
    for c in tr.chips:
        runs = sorted((s, e) for _, s, e in tr.modules[c])
        edges = [tr.window[0]] + [x for r in runs for x in r] + [tr.window[1]]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            inner = [n for n, s, e in spans if s <= mid <= e and n != "round"]
            gaps["host:" + (inner[-1] if inner else "none")] += (b - a) / 1e9 / len(tr.chips)
    return {"device_ops": [[k, v] for k, v in ops.most_common(10)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(10)]}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, platform: str = "tpu", resize=None) -> int:
    """``platform`` and ``resize`` (a function of the configuration and
    traffic dicts) exist for the CPU rehearsals in ``bench/tests``."""
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic, e2e, per_layer = cell_of(spec, args.workload)
    if resize is not None:
        config, traffic = resize(config, traffic)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax
    import numpy as np

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell["chips"]:
        print(f"bench: the cell needs {cell['chips']} {platform} device(s); JAX "
              f"found {len(devices)} {devices[0].platform!r}: nothing was run",
              file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    kind = devices[0].device_kind
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if platform == "tpu" and kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 2

    from repro import runtime

    from bench.requests import Stream, now
    from bench.xplane import Capture

    runtime.enable_compile_cache()
    # cache every program, however quick to compile: each run is a new
    # process, and only the first run of a cell in a checkout should compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(now())
        if event == "/jax/core/compile/backend_compile_duration" else None)

    gen = load(BENCH / "generators" / f"{traffic['generator']}.py")
    app = load(BENCH / "apps" / f"{config['app']}.py").App(config, traffic, args.seed)
    stream = Stream(gen, gen.Requests(traffic, config, args.seed))
    drv = load(BENCH / "drivers" / f"{traffic['driver']}.py").Driver(
        app, traffic, stream, devices)
    marks = {"init": now()}
    drv.setup()
    marks["built"] = now()
    # draw the window's requests now, so the window draws none: as many as
    # the fastest warm round's pace could serve in twice the window
    done = [r.t_done for r in drv.record.rounds]
    per_round = min(np.diff(done)) if len(done) > 2 else None
    if per_round:
        need = drv.next_serial + 2 * args.seconds / per_round * drv.budget
        stream.prepare(need)
        drv.record.reserve(int(need))
    marks["drawn"] = now()
    # everything set-up made lives to the end: keep it out of the
    # collector's full scans
    gc.collect()
    gc.freeze()

    # ------------------------------------------------------- the window
    tr = None
    if args.trace:
        before = drv.counters()
        n0 = len(drv.record.rounds)
        with Capture() as cap:
            t0 = now()
            while now() < t0 + min(TRACE_SECONDS, args.seconds):
                drv.round("window")
        traced = drv.record.rounds[n0:]
        after = drv.counters()
        t_end = t0 + args.seconds
    else:
        t0 = now()
        t_end = t0 + args.seconds
    setup_s = t0 - T_START
    cpu0 = time.process_time()
    while now() < t_end:
        drv.round("window")
    window_cpu_s = time.process_time() - cpu0  # every thread of the process
    completed, latency = drv.record.window_stats(t0, t_end)
    steps = sum(r.phase == "window" for r in drv.record.rounds)
    # the slowest window rounds, each with the host seconds of its spans
    window = [r for r in drv.record.rounds if r.phase == "window"]
    slowest = sorted(((float(r.host_s.sum()) * 1e3,
                       [round(float(x) * 1e3, 1) for x in r.host_s])
                      for r in window), reverse=True)[:5]
    in_window = sum(t0 <= t <= t_end for t in compiles)

    # ------------------------------------------- after the window closed
    unanswered = drv.finish(FINISH_SECONDS)
    final = drv.final()
    peak = memory_peak(devices)
    prog = getattr(drv, "program_bytes", None)
    if prog:  # the runtime's peak leaves out the step program's temporaries
        peak = max(peak, prog["arguments"] + prog["temporaries"])
    if args.trace:
        tr = cap.read(drv.spans)
        delta = {k: after[k] - before[k] for k in before}
        tr.extra.update(paths=drv.paths, step_module=drv.step_module,
                        peak=peaks.get(kind, peaks.get("TPU v5 lite")),
                        round_s=[float(r.host_s.sum()) for r in window],
                        **app.calls(traced, stream.fields, delta, drv.chips))
    drv.release()
    gc.collect()
    mismatch, checked = app.check(drv.record.rounds, stream.fields, final)
    checks = {"unanswered": (unanswered, 0), **{k: (v, 0) for k, v in mismatch.items()}}
    correct = all(v <= lim for v, lim in checks.values())

    metrics = {}
    if args.trace:
        for m in per_layer:
            v = load(BENCH / "metrics" / f"{m['name']}.py").read(tr)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"ops_per_s": completed / args.seconds, "setup_s": setup_s}
        for m in e2e:  # a latency percentile is named p<q>_ms
            q = re.fullmatch(r"p(\d+(?:\.\d+)?)_ms", m["name"])
            if q and len(latency):
                values[m["name"]] = float(np.percentile(latency, float(q[1]))) * 1e3
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": checked + unanswered,
              "failed": sum(v for v, _ in checks.values()), "metrics": metrics,
              "device": device}
    if tr is not None:
        device["busy_s"] = tr.mean_busy_ns() / 1e9
        device["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = breakdown(tr, tr.spans)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}

    tail = {f"p{q}_ms": round(float(np.percentile(latency, q)) * 1e3, 3)
            for q in (50, 95, 99)} if len(latency) else {}
    print(f"bench: {args.workload} seed={args.seed} completed={completed} "
          f"latency_samples={len(latency)} steps={steps} latency={tail} "
          f"setup_s={setup_s:.3f} (init {marks['init'] - T_START:.3f}, build and "
          f"warm {marks['built'] - marks['init']:.3f}, draw "
          f"{marks['drawn'] - marks['built']:.3f}) compiles_in_window={in_window} "
          f"window_cpu_s={window_cpu_s:.3f} "
          f"slowest_rounds_ms={[(round(t, 1), p) for t, p in slowest]} "
          f"(spans {'/'.join(drv.parts)}) rounds_traced="
          f"{tr.extra['rounds'] if tr else 0} step_program_bytes="
          f"{drv.program_bytes}", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k}={v} limit={lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
