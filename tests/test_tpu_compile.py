"""Every Pallas kernel entry point compiles for a TPU v5e at real widths.

Interpret mode (the rest of the suite) cannot see Mosaic's tiling rules:
a block must end in tile multiples or in the array's own dims, and a DMA
may not slice inside a tile. These tests lower each kernel with
``interpret=False`` for a described (not attached) ``v5e:2x2`` chip, so
the TPU compiler refuses here what it would refuse on the chip. Widths
are the ones ``chip_smoke.py`` drives: a 1 KB-record KVS over 2^20 rows,
a 3-replica TX chain over 2^20 64 B rows, 8 DLRM tables of 2^20 x 64 f32,
and qwen1.5-0.5b's paged decode and prefill attention in bf16. The KVS and
TX engine steps compile there too, at a small size, to check that each of
their Pallas calls keeps the name ``bench/metrics/kernels.json`` matches and
runs in its named phase, and at their benchmark cells' full sizes, to check
that every in-place scatter takes its state array once and so leaves XLA
no whole copy of it to make; the DLRM-DCNv2 step compiles at its benchmark
cell's full size, to check that it reads its 6.78 GB table in place.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file.
"""
import os
import re
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    embedding_reduce, flash_attention, hash_probe, paged_attention, tx_commit,
)

I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16

B = 256  # requests per engine batch
NB, W, KW = 2 ** 18, 8, 2  # KVS buckets x ways, key words
NP, VW = 2 ** 20, 256  # KVS pool rows, 1 KB values
CS, CW = 1024, 4  # KVS hot-set cache sets x ways
R, NK, TVW, M, LC = 3, 2 ** 20, 16, 8, 2 ** 16  # TX chain
TW = 1 + M * (1 + TVW)  # TX log record words
T, ROWS, D, L = 8, 2 ** 20, 64, 32  # DLRM tables x rows x dim, lookups
QB, KVH, HD, PS, MAXP, PAGES = 8, 16, 64, 16, 10, 128  # qwen1.5-0.5b decode


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kvs(s):
    return dict(bk=s((NB + 1, W, KW), I32), bp=s((NB + 1, W), I32),
                pool=s((NP + 1, VW), I32), keys=s((B, KW), I32),
                idx=s((B,), I32), vals=s((B, VW), I32))


def _tx(s, r):
    return dict(log=s((r, LC + 1, TW), I32), store=s((r, NK + 1, TVW), I32),
                batch=s((B, TW), I32), values=s((B, M, TVW), I32),
                slot=s((r, B), I32), rows=s((B * M,), I32))


def _kernel_call(name, s):
    """(function, shape arguments) for one kernel entry point."""
    native = dict(interpret=False)
    if name == "probe":
        k = _kvs(s)
        return (lambda bk, bp, keys, h1, h2: hash_probe.probe(
            bk, bp, keys, h1, h2, **native),
            (k["bk"], k["bp"], k["keys"], k["idx"], k["idx"]))
    if name == "cache_probe":
        return (lambda ck, cv, cm, keys, cset: hash_probe.cache_probe(
            ck, cv, cm, keys, cset, **native),
            (s((CS + 1, CW, KW), I32), s((CS + 1, CW, VW), I32),
             s((CS + 1, CW), I32), s((B, KW), I32), s((B,), I32)))
    if name == "fetch":
        k = _kvs(s)
        return (lambda pool, ptr: hash_probe.fetch(pool, ptr, **native),
                (k["pool"], k["idx"]))
    if name == "commit_buckets":
        k = _kvs(s)
        return (lambda bk, bp, keys, tb, tw, pv: hash_probe.commit_buckets(
            bk, bp, keys, tb, tw, pv, **native),
            (k["bk"], k["bp"], k["keys"], k["idx"], k["idx"], k["idx"]))
    if name == "write_rows":
        k = _kvs(s)
        return (lambda pool, vals, wp: hash_probe.write_rows(
            pool, vals, wp, **native), (k["pool"], k["vals"], k["idx"]))
    if name == "tx_commit":
        t = _tx(s, 1)
        return (lambda log, store, batch, values, slot, rows: tx_commit.commit(
            log[0], store[0], batch, values, slot[0], rows, **native),
            (t["log"], t["store"], t["batch"], t["values"], t["slot"],
             t["rows"]))
    if name == "commit_chain":
        t = _tx(s, R)
        return (lambda *a: tx_commit.commit_chain(*a, **native),
                (t["log"], t["store"], t["batch"], t["values"], t["slot"],
                 t["rows"]))
    if name == "embedding_reduce":
        n = B * T * L
        return (lambda table, idx, seg: embedding_reduce.embedding_reduce(
            table, idx, seg, B * T, **native),
            (s((T * ROWS, D), F32), s((n,), I32), s((n,), I32)))
    if name == "paged_attention_stats":
        return (lambda *a: paged_attention.paged_attention_stats(*a, **native),
                (s((QB, KVH, 1, HD), F32), s((PAGES + 1, PS, KVH, HD), BF16),
                 s((PAGES + 1, PS, KVH, HD), BF16), s((QB, MAXP), I32),
                 s((QB,), I32)))
    if name == "flash_attention":
        qkv = s((1, KVH, 128, HD), BF16)
        return (lambda q, k, v: flash_attention.flash_attention(
            q, k, v, **native), (qkv, qkv, qkv))
    raise KeyError(name)


KERNELS = ["probe", "cache_probe", "fetch", "commit_buckets", "write_rows",
           "tx_commit", "commit_chain", "embedding_reduce",
           "paged_attention_stats", "flash_attention"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = _kernel_call(name, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # what the kernel's operands and outputs hold, padding included, must
    # fit one 16 GB v5e chip
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


# the cells' widths at a small size, and at the benchmark cells' full sizes
STEP_SIZES = {
    "kvs": dict(num_buckets=64, ways=8, key_words=6, val_words=256,
                pool_size=1024, cache_sets=16, cache_ways=4),
    "tx": dict(num_keys=512, val_words=16, max_ops=8, chain_len=4,
               log_capacity=64),
}
CELL_SIZES = {
    "kvs": dict(STEP_SIZES["kvs"], num_buckets=NB, pool_size=NP, cache_sets=CS),
    "tx": dict(STEP_SIZES["tx"], num_keys=2 ** 21, log_capacity=2 ** 18),
}


def _engine_step(app, sizes=STEP_SIZES, queues=2, capacity=8, budget=8):
    """The engine step of one app at ``sizes[app]``, and its state's
    shapes."""
    from repro.core import engine, kvstore, transaction, tx_app

    if app == "kvs":
        cfg = kvstore.KVConfig(**sizes[app])
        words, mod, make = kvstore.request_words(cfg), kvstore, kvstore.make
    else:
        cfg = transaction.TxConfig(**sizes[app])
        words, mod, make = tx_app.request_words(cfg), tx_app, transaction.make_chain
    ecfg = engine.EngineConfig(num_queues=queues, capacity=capacity,
                               req_words=words, resp_words=words, budget=budget,
                               kernel_backend="auto")
    app_fn = engine.bind_app(mod.app_step, cfg, ecfg)
    state = jax.eval_shape(lambda: engine.make(ecfg, make(cfg)))
    return (lambda s: engine.engine_step(s, app_fn, ecfg)), state


# every Pallas call of the step: the kernels.json kernel it belongs to and
# the named phase it runs in
STEP_KERNELS = {
    "kvs": {("probe", "hash_get", "kvs.get"), ("gather", "hash_get", "kvs.get"),
            ("cache_probe", "cache_probe", "kvs.get"),
            ("cache_probe", "cache_probe", "kvs.commit_put"),
            ("probe", "hash_put", "kvs.plan_put"),
            ("commit_buckets", "hash_put", "kvs.commit_put"),
            ("scatter", "hash_put", "kvs.commit_put")},
    "tx": {("scatter", "tx_commit", "tx.commit")},
}


@pytest.mark.parametrize("app", ["kvs", "tx"])
def test_engine_step_kernels_keep_names_and_phases(one_chip, monkeypatch, app):
    """Named scopes leave each Pallas call matched by exactly one kernel of
    ``bench/metrics/kernels.json``, and put it in the phase it belongs to."""
    root = str(Path(__file__).resolve().parents[1])  # the benchmark's tables
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.drivers.engine_step import kernel_paths
    from bench.metrics import _ops
    from bench.metrics._phases import phase_of
    from bench.scopes import op_scopes
    from bench.xplane import base_name
    from repro.kernels import ops

    # the described chip is not the default backend: take the native path
    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    step, state = _engine_step(app)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), state)
    text = jax.jit(step).lower(state).compile().as_text()
    paths, scopes = kernel_paths(text), op_scopes(text)
    trace = types.SimpleNamespace(extra={"paths": paths})
    found = set()
    for instr in paths:
        (kernel,) = [k for k in _ops.TABLE["kernels"] if _ops.in_kernel(trace, k, instr)]
        found.add((base_name(instr), kernel, phase_of(scopes[instr])))
    assert found == STEP_KERNELS[app]


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _instructions(hlo_text: str) -> dict:
    """Instruction name -> (result shape, opcode, operand names, rest of the
    line) for every instruction of a compiled program."""
    out = {}
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if m:
            args, _, rest = m.group(4).partition(")")
            names = [a.strip().lstrip("%") for a in
                     re.sub(r"/\*.*?\*/", "", args).split(",") if a.strip()]
            out[m.group(1)] = (m.group(2), m.group(3), names, rest)
    return out


def _copied_from(instrs: dict, name: str) -> str:
    """The instruction a chain of ``copy`` ops starts from."""
    while name in instrs and instrs[name][1] == "copy":
        name = instrs[name][2][0]
    return name


# temporaries of the full-size steps with each destination passed twice (a
# whole copy of it made before every call): KVS 2,423,422,976 B, TX
# 8,595,774,976 B
STEP_TEMP_LIMIT = {"kvs": 2_423_422_976 - 1e9, "tx": 4.5e9}


@pytest.mark.parametrize("app", ["kvs", "tx"])
def test_cell_step_passes_each_scatter_destination_once(one_chip, monkeypatch,
                                                        app):
    """The KVS step (2^18 x 8 buckets of 6 key words, a 2^20 x 256 pool, a
    1024 x 4 cache) and the TX step (4 replicas of 2^21 x 16 rows and 2^18
    log records) at their benchmark cells' sizes, with the driver's engine
    (8 queues x 64, 256 a step) and the state donated. No aliased Pallas
    operand reaches its call beside another operand holding the same array,
    whether passed as is or through a copy: XLA would copy the whole
    destination before the call. What is left to copy is the relayout of
    the narrow arrays into the layout a ``tpu_custom_call`` takes."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    step, state = _engine_step(app, CELL_SIZES, queues=8, capacity=64, budget=B)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), state)
    compiled = jax.jit(step, donate_argnums=0).lower(state).compile()
    text = compiled.as_text()
    instrs = _instructions(text)
    n_aliased = 0
    for name, (_, op, args, rest) in instrs.items():
        if op != "custom-call" or 'custom_call_target="tpu_custom_call"' not in rest:
            continue
        aliasing = re.search(r"output_to_operand_aliasing=\{(.*?)\}\}", rest)
        if not aliasing:
            continue
        sources = [_copied_from(instrs, a) for a in args]
        for i in map(int, re.findall(r"\((\d+), \{", aliasing.group(1))):
            n_aliased += 1
            assert sources.count(sources[i]) == 1, (
                f"{name}: aliased operand {args[i]} holds the same array as "
                f"another operand")
    assert n_aliased == {"kvs": 3, "tx": 2}[app]
    pool = re.compile(r"s32\[(1,)?1048577,256\]")
    assert not [n for n, (shape, op, _, _) in instrs.items()
                if op in ("copy", "copy-start") and pool.search(shape)]
    assert compiled.memory_analysis().temp_size_in_bytes < STEP_TEMP_LIMIT[app]


def test_dlrm_dcnv2_step_reads_the_table_in_place(one_chip, monkeypatch):
    """The DLRM-DCNv2 engine step at the benchmark cell's shapes: one bf16
    (26500127, 128) table (one chip's rows of MLPerf's 26 tables), 256
    requests of 228 words, 54,784 lookups a step. The pooling kernel reads
    the table where it lies: the step's temporaries hold no table-sized
    buffer. Every phase is in the op metadata, the kernel in ``dlrm.embed``."""
    root = str(Path(__file__).resolve().parents[1])  # the benchmark's driver
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.drivers.engine_step import kernel_paths
    from repro.core import dlrm, engine
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    rows = (5000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 5000000,
            383495, 405282, 10, 2209, 11938, 155, 4, 976, 14, 5000000, 5000000,
            5000000, 590152, 12973, 108, 36)
    pooling = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
               27, 10, 3, 1, 1)
    cfg = dlrm.DLRMConfig(num_tables=26, dim=128, dense_features=13,
                          bottom=(512, 256), top=(1024, 1024, 512, 256, 1),
                          table_rows=rows, pooling=pooling, interaction="dcn",
                          cross_layers=3, cross_rank=512, matmul_precision="highest")
    words = dlrm.request_words(cfg)
    assert (sum(rows), sum(pooling), words) == (26500127, 214, 228)
    ecfg = engine.EngineConfig(num_queues=8, capacity=64, req_words=words,
                               resp_words=words, budget=B, kernel_backend="auto")
    app_fn = engine.bind_app(dlrm.app_step, cfg, ecfg)

    def app_state():
        p = dlrm.init_params(jax.random.key(0), cfg)
        return engine.make(ecfg, {**p, "tables": p["tables"].astype(BF16)})

    state = jax.eval_shape(app_state)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), state)
    compiled = jax.jit(lambda s: engine.engine_step(s, app_fn, ecfg),
                       donate_argnums=0).lower(state).compile()
    table = sum(rows) * 128 * 2
    mem = compiled.memory_analysis()
    assert table <= mem.argument_size_in_bytes < table + 0.1e9
    assert mem.temp_size_in_bytes < 0.1e9  # no copy of the 6.78 GB table
    text = compiled.as_text()
    (path,) = kernel_paths(text).values()
    assert f"/{dlrm.EMBED}/" in path
    assert f"s32[{B * sum(pooling)}]" in text  # one call pools every lookup
    for scope in dlrm.SCOPES:
        assert f"/{scope}/" in text
