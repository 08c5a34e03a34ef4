#!/usr/bin/env python3
"""Run the engine's main path once on a TPU and check every answer.

    python chip_smoke.py              # one chip: KVS, TX, DLRM and LM phases
    python chip_smoke.py --chips 4    # the SPMD TX chain on four chips only

Each phase goes through the engine's own entry points with
``kernel_backend="auto"`` (the Pallas kernels, native on the chip):

* ``kvs``: a YCSB-shaped store (1 KB records, 2^20 pool rows, 2^18 buckets
  x 8 ways, a hot-set cache tier) loaded by batched PUTs, then mixed
  GET/PUT rounds through rings -> cpoll -> scheduler -> APU, every answer
  checked against a numpy dict, acknowledged PUTs read back.
* ``tx``: a 3-replica chain over 2^20 64 B rows, transaction rounds through
  ``tx_app.app_step``; every replica's store checked against numpy.
* ``dlrm``: 8 tables x 2^20 rows x 64 f32, query rounds through
  ``dlrm.app_step``; logits checked against a float32 numpy forward pass,
  and the Pallas embedding reduction bit-for-bit against numpy sums.
* ``lm``: qwen1.5-0.5b at its published widths in bf16 with random
  weights, served by the paged engine (``serve.build_engine``); every
  request answered with its cap, and ``paged_attention_stats`` checked on
  the engine's live page pool against the float32 oracle.

Every phase's compiled step must hold a ``tpu_custom_call`` (a Pallas
kernel ran natively, not in interpret mode or through the ``ref`` path).
``--chips 4`` runs ``transaction.chain_commit_spmd`` with one replica per
chip and compares it bit-for-bit with ``chain_commit_local`` on one chip.

Weights and data come from ``--seed``. The script exits non-zero, without
printing its result line, when JAX finds no TPU or any check fails; the
last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.core import dlrm  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.core import kvstore as kv  # noqa: E402
from repro.core import transaction as tx  # noqa: E402
from repro.core import tx_app  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.parallel.sharding import local_context  # noqa: E402

I32, F32 = np.int32, np.float32

# Full sizes. Tests run the same phases at tiny sizes on the CPU.
KVS = dict(num_buckets=2 ** 18, ways=8, key_words=2, val_words=256,
           pool_size=2 ** 20, cache_sets=1024, cache_ways=4,
           load=2 ** 19, load_batch=2 ** 14, queues=8, per_queue=32,
           rounds=4)
TX = dict(num_keys=2 ** 20, val_words=16, max_ops=8, chain_len=3,
          log_capacity=2 ** 16, queues=8, per_queue=32, rounds=3)
DLRM = dict(num_tables=8, rows=2 ** 20, dim=64, lookups=32, queues=8,
            per_queue=16, rounds=2)
LM = dict(arch="qwen1.5-0.5b", reduced=False, requests=8, prompt_len=128,
          gen_len=32, page_size=16, admit_per_step=4)
CHAIN = dict(num_keys=2 ** 20, val_words=16, max_ops=8, log_capacity=2 ** 16,
             batch=256, rounds=3)


class CheckFailed(Exception):
    pass


def check(ok, what) -> None:
    """Fail the run when a check does not hold (kept under ``python -O``,
    which strips asserts)."""
    if not ok:
        raise CheckFailed(what)


def log(phase, **kv_):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv_.items()),
          flush=True)


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def owned(tree):
    """Give every leaf its own buffer: a donated step rejects two leaves
    that share one (jnp.zeros may hand equal constants the same buffer)."""
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), tree)


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def compile_step(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    return compiled, time.perf_counter() - t0


class RequestServer:
    """Drive a request app through the engine one round at a time: a round
    is ``per_queue`` entries on every queue, injected, served by ONE engine
    step (budget = the whole round) and drained. Responses come back per
    queue in FIFO order, so ``responses[q, j]`` answers ``payloads[q, j]``."""

    def __init__(self, app_step, app_cfg, app_state, *, queues, per_queue,
                 words):
        self.q, self.n = queues, per_queue
        self.ecfg = eng.EngineConfig(
            num_queues=queues, capacity=max(2 * per_queue, 8),
            req_words=words, resp_words=words, budget=queues * per_queue,
            kernel_backend="auto",
        )
        app_fn = eng.bind_app(app_step, app_cfg, self.ecfg)
        self.state = owned(eng.make(self.ecfg, app_state))
        self.step, self.compile_s = compile_step(
            lambda s: eng.engine_step(s, app_fn, self.ecfg), self.state)
        self.custom_calls = custom_calls(self.step)
        qids = jnp.arange(queues, dtype=jnp.int32)

        def inject(s, pay):  # pay (Q, n, W): one entry per queue per call
            return jax.lax.fori_loop(
                0, per_queue, lambda j, s: eng.inject(s, qids, pay[:, j]), s)

        self.inject = jax.jit(inject, donate_argnums=0)
        self.drain = jax.jit(lambda s: eng.drain_responses(s, per_queue),
                             donate_argnums=0)
        self.stats = {}

    def round(self, payloads: np.ndarray) -> np.ndarray:
        self.state = self.inject(self.state, jnp.asarray(payloads, jnp.int32))
        self.state, stats = self.step(self.state)
        for k, v in stats.items():
            self.stats[k] = self.stats.get(k, 0) + int(v)
        check(int(stats["served"]) == self.q * self.n, stats)
        resp, counts, self.state = self.drain(self.state)
        check(np.all(np.asarray(counts) == self.n), counts)
        return np.asarray(resp)


# --------------------------------- KVS -------------------------------------

def _kvs_values(keys, width, xp):
    """Load-time value rows, a pure function of the key (made on device;
    recomputed in numpy for the oracle)."""
    k = keys.astype(xp.uint32)
    col = xp.arange(width, dtype=xp.uint32)[None, :]
    v = (k[:, :1] * xp.uint32(2654435761) + k[:, 1:2] * xp.uint32(40503)
         + col * xp.uint32(97) + xp.uint32(12345))
    return (v >> xp.uint32(1)).astype(xp.int32)


def run_kvs(size=KVS, seed=0):
    rng = np.random.default_rng(seed)
    cfg = kv.KVConfig(
        num_buckets=size["num_buckets"], ways=size["ways"],
        key_words=size["key_words"], val_words=size["val_words"],
        pool_size=size["pool_size"], cache_sets=size["cache_sets"],
        cache_ways=size["cache_ways"])
    kw, vw = cfg.key_words, cfg.val_words
    n_load, n_round = size["load"], size["queues"] * size["per_queue"]
    # key ids: [0, load) loaded; beyond it, ids never written (absent) or
    # inserted while serving. Word 0 = id + 1 keeps every key unique.
    n_ids = n_load + 4 * n_round * (size["rounds"] + 1)
    keys = np.stack([np.arange(1, n_ids + 1),
                     rng.integers(1, 2 ** 31 - 1, n_ids)], 1).astype(I32)
    check(kw == 2, "two key words")

    state = owned(kv.make(cfg))
    load = jax.jit(lambda s, k: kv.put(s, k, _kvs_values(k, vw, jnp),
                                       backend="auto"), donate_argnums=0)
    t0 = time.perf_counter()
    loaded = np.zeros(n_ids, bool)
    lb = size["load_batch"]
    for s in range(0, n_load, lb):
        state, ok = load(state, jnp.asarray(keys[s:s + lb]))
        loaded[s:s + lb] = np.asarray(ok)
    jax.block_until_ready(state)
    log("kvs", loaded=int(loaded.sum()), of=n_load,
        load_s=f"{time.perf_counter() - t0:.3f}",
        state_bytes=tree_bytes(state))

    words = kv.request_words(cfg)
    srv = RequestServer(kv.app_step, cfg, state, queues=size["queues"],
                        per_queue=size["per_queue"], words=words)
    del state
    updates = {}  # key id -> value row written while serving

    def expect(i):
        if i in updates:
            return True, updates[i]
        if loaded[i]:
            return True, _kvs_values(keys[i:i + 1], vw, np)[0]
        return False, np.zeros(vw, I32)

    checked = 0
    fresh = iter(range(n_load, n_ids))

    def serve_round(get_ids, put_ids):
        nonlocal checked
        ids = np.concatenate([get_ids, put_ids])
        ops_ = np.r_[np.full(len(get_ids), kv.OP_GET),
                     np.full(len(put_ids), kv.OP_PUT)]
        perm = rng.permutation(len(ids))
        ids, ops_ = ids[perm], ops_[perm]
        vals = rng.integers(-2 ** 31, 2 ** 31 - 1, (len(ids), vw)).astype(I32)
        pay = np.concatenate([ops_[:, None], keys[ids], vals], 1).astype(I32)
        before = {int(i): expect(int(i)) for i in ids}
        resp = srv.round(pay.reshape(srv.q, srv.n, words)).reshape(-1, words)
        for r, i, op, v in zip(resp, ids, ops_, vals):
            found, want = before[int(i)]
            if op == kv.OP_GET:  # reads see the store from before the round
                check(r[0] == int(found), (i, r[0], found))
                check(np.array_equal(r[1:1 + vw], want), i)
            else:
                # an update of a present key always lands; an insert may be
                # refused only when both of its buckets are full
                check(r[0] == 1 or not found, (i, r[0]))
                if r[0] == 1:
                    updates[int(i)] = v
            checked += 1

    for _ in range(size["rounds"]):  # YCSB-A-shaped: half reads, half updates
        half = n_round // 2
        pool = rng.choice(n_load, n_round - 4 * (n_round // 16), replace=False)
        absent = [next(fresh) for _ in range(n_round // 16)]
        new = [next(fresh) for _ in range(3 * (n_round // 16))]
        ids = np.concatenate([pool, absent, new])
        rng.shuffle(ids)
        serve_round(ids[:half], ids[half:])
    # read back every acknowledged PUT, plus keys never written
    back = np.array(sorted(updates), np.int64)
    absent = np.array([next(fresh) for _ in range(n_round // 8)])
    back = np.concatenate([back, absent])
    for s in range(0, len(back), n_round):
        ids = back[s:s + n_round]
        if len(ids) < n_round:
            ids = np.concatenate(
                [ids, rng.choice(n_load, n_round - len(ids), replace=False)])
        serve_round(ids, np.zeros(0, np.int64))
    log("kvs", answers_checked=checked, puts_acked=len(updates),
        read_back=len(updates), absent_checked=len(absent),
        compile_s=f"{srv.compile_s:.3f}", tpu_custom_calls=srv.custom_calls,
        cache_hits=srv.stats.get("cache_hits"),
        cache_misses=srv.stats.get("cache_misses"))
    return {"custom_calls": srv.custom_calls, "checked": checked}


# ---------------------------------- TX -------------------------------------

def run_tx(size=TX, seed=0):
    rng = np.random.default_rng(seed + 1)
    cfg = tx.TxConfig(num_keys=size["num_keys"], val_words=size["val_words"],
                      max_ops=size["max_ops"], chain_len=size["chain_len"],
                      log_capacity=size["log_capacity"])
    m, vw, words = cfg.max_ops, cfg.val_words, tx_app.request_words(cfg)
    chain = tx.make_chain(cfg)
    log("tx", replicas=cfg.chain_len, state_bytes=tree_bytes(chain))
    srv = RequestServer(tx_app.app_step, cfg, chain, queues=size["queues"],
                        per_queue=size["per_queue"], words=words)
    del chain
    store = np.zeros((cfg.num_keys + 1, vw), I32)
    n_round = srv.q * srv.n
    committed = 0
    for _ in range(size["rounds"]):
        n_ops = rng.integers(1, m + 1, n_round)
        offs = rng.choice(cfg.num_keys, n_round * m, replace=False)
        offs = offs.astype(I32).reshape(n_round, m)
        vals = rng.integers(-2 ** 31, 2 ** 31 - 1, (n_round, m, vw)).astype(I32)
        # the second entry of every queue writes a row the first one holds:
        # first-claimant concurrency control must defer it
        conflict = np.zeros(n_round, bool)
        conflict[1::srv.n] = True
        offs[1::srv.n, 0] = offs[0::srv.n, 0]
        recs = np.zeros((n_round, words), I32)
        recs[:, 0] = n_ops
        body = recs[:, 1:].reshape(n_round, m, 1 + vw)
        body[..., 0] = offs
        body[..., 1:] = vals
        resp = srv.round(recs.reshape(srv.q, srv.n, words)).reshape(-1, words)
        want = np.where(conflict, tx_app.RESP_DEFERRED, tx_app.RESP_COMMITTED)
        check(np.array_equal(resp[:, 0], want), resp[:, 0])
        for t in np.flatnonzero(~conflict):
            store[offs[t, :n_ops[t]]] = vals[t, :n_ops[t]]
        committed += int((~conflict).sum())
    chain = srv.state.app
    got = np.asarray(chain.store)
    for r in range(cfg.chain_len):
        check(np.array_equal(got[r], store), f"replica {r} store differs")
    check(np.all(np.asarray(chain.committed) == committed), "commit counts")
    check(np.all(np.asarray(chain.log_tail) == committed), "log tails")
    log("tx", transactions=size["rounds"] * n_round, committed=committed,
        deferred_checked=size["rounds"] * srv.q, replicas_checked=cfg.chain_len,
        compile_s=f"{srv.compile_s:.3f}", tpu_custom_calls=srv.custom_calls)
    return {"custom_calls": srv.custom_calls, "checked": committed}


# --------------------------------- DLRM ------------------------------------

def _dlrm_reference(p, dense, emb, cfg, bf16_operands=False):
    """float32 numpy forward pass from gathered embedding sums (B, T, D).
    With ``bf16_operands`` every matmul operand is rounded to bfloat16
    first, which is what a DEFAULT-precision f32 matmul on a TPU does."""
    def r(x):
        return (x.astype(ml_dtypes.bfloat16).astype(F32) if bf16_operands
                else x)

    def mlp(layers, x, final_linear=False):
        for i, l in enumerate(layers):
            x = r(x) @ r(l["w"]) + l["b"]
            if not (final_linear and i == len(layers) - 1):
                x = np.maximum(x, 0)
        return x

    bot = mlp(p["bottom"], dense)
    feats = np.concatenate([bot[:, None, :], emb], axis=1)
    inter = np.einsum("bmd,bnd->bmn", r(feats), r(feats))
    iu, ju = np.triu_indices(cfg.num_tables + 1, k=1)
    z = np.concatenate([bot, inter[:, iu, ju]], axis=1)
    return mlp(p["top"], z, final_linear=True)[:, 0]


def run_dlrm(size=DLRM, seed=0):
    rng = np.random.default_rng(seed + 2)
    cfg = dlrm.DLRMConfig(num_tables=size["num_tables"], rows=size["rows"],
                          dim=size["dim"], lookups=size["lookups"])
    params = dlrm.init_params(jax.random.key(seed), cfg)
    log("dlrm", tables=f"{cfg.num_tables}x{cfg.rows}x{cfg.dim}",
        state_bytes=tree_bytes(params))
    words = dlrm.request_words(cfg)
    srv = RequestServer(dlrm.app_step, cfg, params, queues=size["queues"],
                        per_queue=size["per_queue"], words=words)
    host_p = jax.tree_util.tree_map(np.asarray,
                                    {"bottom": params["bottom"],
                                     "top": params["top"]})
    del params  # donated into the engine state by its first step
    t_ids = jnp.arange(cfg.num_tables)[None, :, None]
    gather = jax.jit(lambda tab, idx: tab[t_ids, idx])
    reduce_ = jax.jit(lambda tab, idx: dlrm.embedding_reduce(
        tab, idx, backend="auto"))
    n_round = srv.q * srv.n
    worst = bound = 0.0
    for _ in range(size["rounds"]):
        dense = rng.normal(size=(n_round, cfg.dense_features)).astype(F32)
        idx = rng.integers(0, cfg.rows, (n_round, cfg.num_tables, cfg.lookups))
        pay = np.concatenate(
            [np.full((n_round, 1), dlrm.OP_INFER, I32), dense.view(I32),
             idx.reshape(n_round, -1).astype(I32)], 1)
        resp = srv.round(pay.reshape(srv.q, srv.n, words)).reshape(-1, words)
        check(np.all(resp[:, 0] == 1), resp[:, 0])
        logits = resp[:, 1].copy().view(F32)
        tables = srv.state.app["tables"]
        rows = np.asarray(gather(tables, jnp.asarray(idx, jnp.int32)))
        emb = rows[:, :, 0]
        for l in range(1, cfg.lookups):  # sequential f32 sum, kernel order
            emb = emb + rows[:, :, l]
        # the kernel's reduction is exact f32 addition in the same order
        kern = np.asarray(reduce_(tables, jnp.asarray(idx, jnp.int32)))
        check(np.array_equal(kern, emb), "embedding reduction differs")
        want = _dlrm_reference(host_p, dense, emb, cfg)
        bf16 = _dlrm_reference(host_p, dense, emb, cfg, bf16_operands=True)
        worst = max(worst, float(np.max(np.abs(logits - want))))
        bound = max(bound, 4 * float(np.max(np.abs(bf16 - want)))
                    + 1e-4 * float(np.max(np.abs(want))) + 1e-6)
    log("dlrm", queries=size["rounds"] * n_round,
        embedding_sums="bit-exact", logit_max_abs_err=f"{worst:.3e}",
        logit_tol=f"{bound:.3e}",
        tol_basis="4x the f32-vs-bf16-operand reference gap + 1e-4 x max|logit|:"
                  " DEFAULT-precision f32 matmuls on TPU round operands to bf16",
        compile_s=f"{srv.compile_s:.3f}", tpu_custom_calls=srv.custom_calls)
    check(worst <= bound, (worst, bound))
    return {"custom_calls": srv.custom_calls, "checked": size["rounds"] * n_round}


# ---------------------------------- LM -------------------------------------

def _lm_serve(cfg, ctx, params, size, prompts, caps, backend, check_pool):
    """Serve one request per queue through the paged engine; returns
    (tokens per request, compiled step, compile seconds)."""
    n = len(prompts)
    ecfg = eng.LMEngineConfig(
        num_queues=n, capacity=4, prompt_len=size["prompt_len"],
        gen_len=size["gen_len"], slots=n,
        admit_per_step=size["admit_per_step"], paged=True,
        page_size=size["page_size"], kernel_backend=backend)
    engine_step, state = serve.build_engine(cfg, ctx, ecfg, params)
    t0 = time.perf_counter()
    compiled = engine_step.lower(state).compile()
    compile_s = time.perf_counter() - t0

    def step(s):
        return compiled(s, params)

    state = eng.lm_inject(state, jnp.arange(n, dtype=jnp.int32),
                          jnp.asarray(prompts, jnp.int32),
                          gen_caps=jnp.asarray(caps, jnp.int32))
    drain = jax.jit(lambda s: eng.drain_responses(s, 1), donate_argnums=0)
    out = [None] * n
    for tick in range(4 * (size["gen_len"] + n)):
        state = step(state)
        if check_pool is not None and tick == 2:
            check_pool(state.decode)
        pay, counts, state = drain(state)
        pay, counts = np.asarray(pay), np.asarray(counts)
        for q in np.flatnonzero(counts):
            out[q] = pay[q, 0, 1:1 + pay[q, 0, 0]]
        if all(o is not None for o in out):
            break
    return out, compiled, compile_s


def run_lm(size=LM, seed=0):
    rng = np.random.default_rng(seed + 3)
    cfg = get_config(size["arch"])
    if size["reduced"]:
        cfg = reduced(cfg).replace(dtype="float32")
    ctx = local_context()
    params = init_params(jax.random.key(seed), cfg, ctx)
    n, gen = size["requests"], size["gen_len"]
    log("lm", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
        heads=f"{cfg.num_heads}/{cfg.num_kv_heads}"
              f"x{cfg.head_dim or cfg.d_model // cfg.num_heads}",
        d_ff=cfg.d_ff, vocab=cfg.vocab_size, dtype=cfg.dtype,
        param_bytes=tree_bytes(params))
    prompts = rng.integers(1, cfg.vocab_size, (n, size["prompt_len"]))
    caps = np.resize([gen, gen // 2 + 1, 1, gen, 7, gen, 3, gen], n)
    caps = np.minimum(caps, gen)
    pool_checks = []

    def check_pool(pool):
        # the kernel on the engine's live pool vs the float32 oracle
        b = pool.page_table.shape[0]
        kvh, hd = pool.k_pages.shape[3], pool.k_pages.shape[4]
        g = cfg.num_heads // kvh
        lengths = np.asarray(pool.lengths)
        check(lengths.max() >= size["prompt_len"], lengths)
        live = lengths > 0
        q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)) * hd ** -0.5, F32)
        for layer in sorted({0, cfg.num_layers // 2, cfg.num_layers - 1}):
            args = (q, pool.k_pages[layer], pool.v_pages[layer],
                    pool.page_table, pool.lengths)
            acc, m, l = (np.asarray(x) for x in ops.paged_attention_stats(*args))
            with jax.default_matmul_precision("highest"):
                racc, rm, rl = (np.asarray(x)
                                for x in ref.paged_attention_stats(*args))
            out = acc / np.maximum(l, 1e-30)[..., None]
            rout = racc / np.maximum(rl, 1e-30)[..., None]
            pool_checks.append((
                float(np.max(np.abs(out - rout)[live])),
                float(np.max(np.abs(m - rm)[live])),
                float(np.max(np.abs(l - rl)[live] / rl[live])),
                float(np.max(np.abs(rout[live]))),
                float(np.max(np.abs(rm[live]))),
            ))

    toks, compiled, compile_s = _lm_serve(cfg, ctx, params, size, prompts,
                                          caps, "auto", check_pool)
    n_calls = custom_calls(compiled)
    del compiled
    got = [len(t) if t is not None else -1 for t in toks]
    check(got == list(caps), (got, list(caps)))
    check(all(np.all((t >= 0) & (t < cfg.vocab_size)) for t in toks),
          "token ids outside the vocabulary")
    check(pool_checks, "the page-pool kernel check did not run")
    out_err = max(c[0] for c in pool_checks)
    m_err = max(c[1] for c in pool_checks)
    l_rel = max(c[2] for c in pool_checks)
    # a Mosaic f32 matmul may round operands to bf16 (relative 2^-9); over
    # hd-long dot products and the exp that turns score error into weight
    # error, 2% of each quantity's scale bounds it
    tol = 2e-2 * max(1.0, max(c[3] for c in pool_checks))
    m_tol = 2e-2 * max(1.0, max(c[4] for c in pool_checks))
    log("lm", requests=n, prompt_len=size["prompt_len"],
        tokens=int(sum(got)), caps_met=True,
        compile_s=f"{compile_s:.3f}", tpu_custom_calls=n_calls,
        pool_layers_checked=len(pool_checks),
        attn_out_max_abs_err=f"{out_err:.3e}", m_max_abs_err=f"{m_err:.3e}",
        l_max_rel_err=f"{l_rel:.3e}", attn_tol=f"{tol:.3e}",
        m_tol=f"{m_tol:.3e}", l_rel_tol="2.000e-02",
        tol_basis="2% of max|out| and of max|m|: bf16-rounded matmul "
                  "operands, hd-long dots")
    check(out_err <= tol and m_err <= m_tol and l_rel <= 2e-2, pool_checks)
    ref_toks, _, ref_compile_s = _lm_serve(cfg, ctx, params, size, prompts,
                                           caps, "ref", None)
    same = sum(int(np.sum(a == b)) for a, b in zip(toks, ref_toks)
               if b is not None and len(a) == len(b))
    log("lm", ref_backend_token_agreement=f"{same}/{int(sum(got))}",
        ref_compile_s=f"{ref_compile_s:.3f}")
    return {"custom_calls": n_calls, "checked": int(sum(got))}


# --------------------------- SPMD chain (4 chips) --------------------------

def run_chain_spmd(devices, size=CHAIN, seed=0):
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(seed + 4)
    r = len(devices)
    cfg = tx.TxConfig(num_keys=size["num_keys"], val_words=size["val_words"],
                      max_ops=size["max_ops"], chain_len=r,
                      log_capacity=size["log_capacity"])
    mesh = Mesh(np.array(devices), ("data",), axis_types=(AxisType.Auto,))
    sharded = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    one = jax.sharding.SingleDeviceSharding(devices[0])
    chain_s = jax.device_put(tx.make_chain(cfg), sharded)
    chain_l = jax.device_put(tx.make_chain(cfg), one)
    b = size["batch"]
    batch0 = jax.ShapeDtypeStruct((b, tx.tx_words(cfg)), jnp.int32,
                                  sharding=replicated)
    t0 = time.perf_counter()
    spmd = jax.jit(lambda c, bb: tx.chain_commit_spmd(c, bb, cfg, mesh),
                   donate_argnums=0).lower(chain_s, batch0).compile()
    compile_s = time.perf_counter() - t0
    local = jax.jit(lambda c, bb: tx.chain_commit_local(c, bb, cfg),
                    donate_argnums=0)
    text = spmd.as_text()
    for _ in range(size["rounds"]):
        n_ops = rng.integers(1, cfg.max_ops + 1, b)
        offs = rng.integers(0, cfg.num_keys, (b, cfg.max_ops))  # conflicts
        recs = np.zeros((b, tx.tx_words(cfg)), I32)
        recs[:, 0] = n_ops
        body = recs[:, 1:].reshape(b, cfg.max_ops, 1 + cfg.val_words)
        body[..., 0] = offs
        body[..., 1:] = rng.integers(-2 ** 31, 2 ** 31 - 1,
                                     body[..., 1:].shape)
        chain_s, ack, dfr = spmd(chain_s, jax.device_put(recs, replicated))
        chain_l, ok, dfr_l = local(chain_l, jax.device_put(recs, one))
        check(np.array_equal(np.asarray(ack), np.asarray(ok)), "commit acks")
        check(np.array_equal(np.asarray(dfr), np.asarray(dfr_l)), "deferrals")
    for name, a, c in zip(tx.ReplicaState._fields, chain_s, chain_l):
        check(np.array_equal(np.asarray(a), np.asarray(c)), name)
    log("chain4", replicas=r, chips=len(devices),
        transactions=size["rounds"] * b,
        committed=int(np.asarray(chain_l.committed)[0]),
        bit_equal_to_local_chain=True, compile_s=f"{compile_s:.3f}",
        tpu_custom_calls=text.count("tpu_custom_call"),
        collective_permutes=text.count("collective-permute"))
    return {"custom_calls": text.count("tpu_custom_call")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    runtime.enable_compile_cache()
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax=jax.__version__)

    if args.chips == 4:
        res = run_chain_spmd(devices[:4], seed=args.seed)
        check(res["custom_calls"] > 0, "chain4: no Pallas kernel ran")
    else:
        for name, phase in (("kvs", run_kvs), ("tx", run_tx),
                            ("dlrm", run_dlrm), ("lm", run_lm)):
            t0 = time.perf_counter()
            res = phase(seed=args.seed)
            check(res["custom_calls"] > 0, f"{name}: no Pallas kernel ran")
            gc.collect()
            log(name, ok=True, seconds=f"{time.perf_counter() - t0:.3f}",
                peak_bytes_in_use=dev.memory_stats().get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
