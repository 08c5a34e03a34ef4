"""Serving launcher: the ORCA engine driving LM token generation.

End-to-end path (all jitted device work, host only injects/drains):
clients write prompts into request rings (the one-sided-RDMA-write
analogue) → cpoll pointer-buffer scan notices them → round-robin admission
into continuous-batching slots (prefill) → decode step per engine tick →
finished generations land in response rings → clients poll + return credit.

By default the published config is served at its own widths and dtype;
``--reduced`` serves the CPU test size (2 layers, float32) instead.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.configs import get_config, reduced
from repro.core import engine as eng
from repro.core import placement
from repro.core import ringbuf as rb
from repro.fault import (
    DurabilityConfig, DurabilityManager, FaultConfig, FaultInjector,
    NackError, StragglerDetector, recover, request_with_retries,
)
from repro.launch.mesh import make_context
from repro.models import (
    decode_step, init_params, make_decode_state, prefill,
)
from repro.parallel.sharding import local_context


class EngineStep:
    """The LM engine's jitted step with the weights bound as an argument.

    ``step(state)`` runs it and ``step.lower(state)`` lowers it like a
    jitted function. The weights are an argument of the compiled program,
    not captured by it: jit bakes captured arrays into the program as
    constants, which at published widths is a gigabyte of literals."""

    def __init__(self, fn, params):
        self.jitted = jax.jit(fn, donate_argnums=0)
        self.params = params

    def __call__(self, state):
        return self.jitted(state, self.params)

    def lower(self, state):
        return self.jitted.lower(state, self.params)


def build_engine(cfg, ctx, ecfg: eng.LMEngineConfig, params):
    """(step, initial state) for either decode substrate.

    The engine state is DONATED at the jit boundary (``donate_argnums=0``):
    steady-state serving is a pure carry loop ``state = step(state)``, so
    every O(state) buffer — page pool, rings, slot arrays — aliases
    input→output instead of being copied per tick. Donation consumes the
    input: callers must never reuse a state they passed in
    (tests/test_lm_paged pins the aliasing at the HLO level)."""
    def uniquify(state):
        # donation needs every leaf to own its buffer: jnp.zeros' constant
        # cache can hand identical fresh fields (e.g. two (N,) zero
        # vectors) the SAME buffer, and XLA rejects donating it twice
        return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), state)

    if ecfg.paged:
        # page-pool decode: admission prefill lands prompt KV directly in
        # pages (default models.prefill_kv), no per-slot dense caches
        step = EngineStep(
            lambda s, p: eng.lm_engine_step(s, ecfg, cfg, ctx, p), params)
        return step, uniquify(eng.lm_make_paged(ecfg, cfg, ctx))

    def prefill_fn(p, prompts):
        st = make_decode_state(cfg, ctx, ecfg.admit_per_step, ecfg.cache_len)
        return prefill(p, prompts, st, cfg, ctx, chunk=16)

    def decode_fn(p, toks, st):
        return decode_step(p, toks, st, cfg, ctx)

    step = EngineStep(
        lambda s, p: eng.lm_engine_step(
            s, ecfg, cfg, ctx, p, prefill_fn, decode_fn
        ),
        params,
    )
    state = eng.lm_make(ecfg, make_decode_state(cfg, ctx, ecfg.slots, ecfg.cache_len))
    return step, uniquify(state)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--queues", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the CPU test size of the config (2 layers, "
                         "head_dim 8, vocab 128, float32) instead of its "
                         "published widths and dtype")
    ap.add_argument("--paged", action="store_true",
                    help="decode through the shared KV page pool")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="device pool pages (0 = worst-case auto-size)")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host cold-tier pages (>0 oversubscribes the "
                         "device pool with evict/restore)")
    ap.add_argument("--eos-token", type=int, default=-1,
                    help="EOS token id for early termination (-1 = off)")
    ap.add_argument("--vary-caps", action="store_true",
                    help="draw per-request generation caps in [1, gen_len]")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "ref"),
                    help="kernel dispatch for the paged-attention walk")
    ap.add_argument("--inject-faults", type=int, default=None, metavar="SEED",
                    help="drive the request path through a seeded "
                         "fault.FaultInjector (drop/dup/corrupt/delay/"
                         "doorbell-suppress); completion then counts "
                         "entries that actually landed")
    ap.add_argument("--snapshot-dir", default=None,
                    help="flush full engine-state snapshots to this host "
                         "NVM-tier directory (fault.recovery, atomic "
                         ".tmp-rename commit on the async checkpoint "
                         "thread, overlapping the jitted step)")
    ap.add_argument("--snapshot-every", type=int, default=16,
                    help="engine ticks between snapshot flushes")
    ap.add_argument("--durability-mode", default="full",
                    choices=("full", "delta", "adaptive"),
                    help="flush policy: full snapshots, streaming WAL "
                         "deltas (group-fsynced segment log), or adaptive "
                         "(measured dirty fraction + MemoryBudget "
                         "pressure pick per flush)")
    ap.add_argument("--recover", action="store_true",
                    help="restore the latest committed snapshot from "
                         "--snapshot-dir before serving (crash-restart "
                         "path; torn .tmp leftovers are garbage-collected)")
    args = ap.parse_args(argv)

    if args.recover and args.snapshot_dir is None:
        ap.error("--recover requires --snapshot-dir")

    runtime.enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg).replace(dtype="float32")
    ctx = local_context()
    params = init_params(jax.random.key(args.seed), cfg, ctx)
    ecfg = eng.LMEngineConfig(
        num_queues=args.queues, capacity=16,
        prompt_len=args.prompt_len, gen_len=args.gen_len,
        slots=8, admit_per_step=2, cache_len=args.prompt_len + args.gen_len + 4,
        eos_token=args.eos_token,
        paged=args.paged, page_size=args.page_size,
        num_pages=args.num_pages, host_pages=args.host_pages if args.paged else 0,
        expected_gen_len=max(args.gen_len // 2, 1) if args.host_pages else 0,
        kernel_backend=args.backend,
    )
    step, state = build_engine(cfg, ctx, ecfg, params)
    swap = None
    cold = None
    budget = None
    if ecfg.paged and ecfg.host_pages:
        # one ledger for both consumers of host memory: cold-tier slabs
        # reserve DRAM against it, and the durability tier reads its
        # pressure when splitting full-vs-delta flushes
        pcfg = eng.lm_paged_kv_config(ecfg, cfg, ctx)
        page_b = (2 * pcfg.layers * pcfg.page_size * pcfg.kv_heads
                  * pcfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
        budget = placement.MemoryBudget(
            dram_bytes=2 * ecfg.host_pages * page_b, nvm_bytes=1 << 34)
        swap, cold, _ = eng.make_swap_service(ecfg, cfg, ctx, budget=budget)

    mgr = None
    recovered_step = None
    if args.snapshot_dir is not None:
        mgr = DurabilityManager(DurabilityConfig(
            args.snapshot_dir, every=args.snapshot_every,
            mode=args.durability_mode,
        ), budget=budget, cold=cold)
    if args.recover:
        # fresh state is the geometry template; the restored tree replaces
        # it (copy per leaf: the jit step donates its input, so recovered
        # buffers must be owned). With a cold tier attached the parked
        # slabs + residency maps restore into it from the same stream.
        state, recovered_step = recover(args.snapshot_dir, state, cold=cold)
        state = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                       state)
        print(f"recovered engine state at step {recovered_step} from "
              f"{args.snapshot_dir}")

    rng = np.random.default_rng(args.seed)
    clients = [rb.HostClient(i, ecfg.capacity, ecfg.prompt_len)
               for i in range(args.queues)]
    fi = None
    straggler = StragglerDetector()
    stragglers = 0
    if args.inject_faults is not None:
        fi = FaultInjector(FaultConfig(
            seed=args.inject_faults, p_drop=0.05, p_dup=0.05,
            p_corrupt=0.05, p_delay=0.08, p_suppress=0.05,
        ))

    def send_faulted(qi, entry):
        # ring-credit rejection raises so request_with_retries resubmits
        nonlocal state
        state, acc = fi.inject(state, qi, entry)
        if not acc:
            raise NackError(0, f"ring credit exhausted on queue {qi}")

    sent = recv = 0
    t0 = time.time()
    ticks = 0
    outputs = []
    tokens_out = 0

    def serving_done():
        if fi is None:
            return recv >= args.requests
        # drops/dups decouple recv from sent: completion = every entry
        # that actually landed in a ring answered, nothing still in flight
        return (sent >= args.requests and fi.in_flight == 0
                and recv >= fi.counters["landed"])

    while not serving_done() and ticks < args.requests * (args.gen_len + 16):
        # clients inject
        qids, pls, caps = [], [], []
        for c in clients:
            if sent < args.requests and c.can_send() and rng.random() < 0.7:
                prompt = rng.integers(1, cfg.vocab_size, args.prompt_len)
                cap = (int(rng.integers(1, args.gen_len + 1))
                       if args.vary_caps else 0)
                if fi is not None:
                    entry = np.concatenate(
                        [prompt, [cap]]).astype(np.int32)
                    try:
                        request_with_retries(
                            send_faulted, c.queue_id, entry,
                            retries=2, backoff=0.001,
                        )
                    except NackError:
                        continue  # no credit this tick; try again later
                    sent += 1
                    continue
                qids.append(c.queue_id)
                pls.append(prompt.astype(np.int32))
                caps.append(cap)
                c.note_sent()
                sent += 1
        if qids:
            state = eng.lm_inject(
                state, jnp.asarray(qids, jnp.int32), jnp.asarray(np.stack(pls)),
                gen_caps=jnp.asarray(caps, jnp.int32),
            )
        if fi is not None:
            state, _ = fi.tick(state)
        t_step = time.time()
        state = step(state)
        if swap is not None:
            state = swap(state)
        jax.block_until_ready(state.resp.tail)
        stragglers += int(straggler.observe(time.time() - t_step)["straggler"])
        ticks += 1
        if mgr is not None and ticks % args.snapshot_every == 0:
            # synchronous device->host copy, async file write: the next
            # step's donation reuses the device buffers while the NVM
            # tier's atomic .tmp-rename commit happens off-thread
            mgr.flush(state)
        # clients poll responses (entry = [count | tokens..., zero pad])
        avail = np.asarray(rb.available(state.resp))
        for qi in range(args.queues):
            n = int(avail[qi])
            for j in range(n):
                ent = np.asarray(rb.peek(
                    state.resp, jnp.asarray([qi], jnp.int32), jnp.asarray([j], jnp.int32)
                ))[0]
                n_gen = int(ent[0])
                outputs.append((qi, ent[1:1 + n_gen].tolist()))
                tokens_out += n_gen
                clients[qi].note_received()
                recv += 1
        if avail.sum():
            state = state._replace(resp=rb.pop(
                state.resp, jnp.arange(args.queues, dtype=jnp.int32),
                jnp.asarray(avail, jnp.int32),
            ))
    if mgr is not None:
        mgr.flush(state)
        mgr.wait()
    dt = time.time() - t0
    dev = jax.devices()[0]
    print(f"served {recv}/{sent} requests ({tokens_out} tokens) in {ticks} "
          f"engine ticks ({dt:.1f}s wall, {recv / max(dt, 1e-9):.1f} req/s "
          f"on {dev.platform} {dev.device_kind})")
    if mgr is not None:
        committed = mgr.committed()
        print(f"  snapshots: {len(committed)} committed to "
              f"{args.snapshot_dir} ({mgr.flush_bytes()} bytes flushed)")
        s = mgr.stats()
        print(f"  durability: {s['fsyncs']} fsyncs / {s['wal_records']} WAL "
              f"records, {s['disk_bytes']} bytes on disk, "
              f"{s['gc_removed']} artifacts GC'd, flush wait "
              f"{s['flush_wait_us']:.0f}us, {s['flushes_skipped']} skipped")
        if budget is not None:
            print(f"  budget: dram {budget.used('dram')}/"
                  f"{budget.capacity['dram']}B used, "
                  f"{budget.bytes_written['nvm']}B written to the NVM tier")
    if cold is not None:
        print(f"  cold tier: {cold.evictions} evictions, "
              f"{cold.restores} restores, {cold.pages_used} pages stranded")
    if stragglers:
        print(f"  straggler ticks: {stragglers} "
              f"(EMA threshold x{straggler.threshold})")
    for qi, toks in outputs[:4]:
        print(f"  queue {qi}: generated {toks}")
    if fi is not None:
        c = fi.counters
        print(f"  faults: offered={c['offered']} landed={c['landed']} "
              f"dropped={c['dropped']} duplicated={c['duplicated']} "
              f"corrupted={c['corrupted']} delayed={c['delayed']} "
              f"suppressed={c['suppressed']} rejected={c['rejected']}")
        assert recv == c["landed"], (
            "every landed entry must be answered exactly once"
        )
    elif args.recover:
        # a recovered run inherits the crashed process's in-flight backlog
        # (restored ring/slot occupancy): this process's recv counts both
        # inherited and fresh completions, so only liveness is asserted
        assert recv > 0, "recovered engine must make progress"
    else:
        assert recv == args.requests, "all requests must complete"
    return recv


if __name__ == "__main__":
    main()
