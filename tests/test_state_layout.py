"""Sentinel-resident state layout: the no-copy invariants of the KVS PUT
and TX replica-commit hot paths.

The state arrays permanently carry their zero sentinel pad row
(``KVState``: (NB+1)/(NP+1), ``ReplicaState``: (LC+1)/(NK+1) — the page
pool's zero-sentinel-page convention), so the kernel wrappers must never
concatenate a pad row onto (or strip one off) an O(state) array per
dispatch. Pinned here at the jaxpr level (the pattern of
``test_lm_paged.test_paged_decode_scan_never_carries_the_pool``), plus
the donation/aliasing behaviour the layout exists to enable and the
hypothesis hygiene property that the sentinel rows stay zero forever.
Each aliased scatter takes its destination once: a second, read-only
operand of the same array would make XLA copy the whole array before the
call, so the read could not see the call's own writes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import kvstore as kv
from repro.core import transaction as tx
from repro.kernels import hash_probe, rows

I32 = jnp.int32

# deliberately odd, collision-free sizes: no model/batch dim equals any of
# the state dims below, so a shape test cannot pass by coincidence
# (cache_sets=11 makes the hot-set cache arrays part of the pinned layout)
KV_CFG = kv.KVConfig(num_buckets=37, ways=2, key_words=2, val_words=4,
                     pool_size=53, cache_sets=11, cache_ways=2)
TX_CFG = tx.TxConfig(num_keys=29, val_words=2, max_ops=3, chain_len=2,
                     log_capacity=19)


def _eqns(jaxpr):
    """Every equation, recursing into sub-jaxprs (scan/cond/pjit bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for v in val if isinstance(val, (tuple, list)) else (val,):
                if hasattr(v, "eqns"):  # open Jaxpr
                    yield from _eqns(v)
                elif hasattr(v, "jaxpr"):  # ClosedJaxpr
                    yield from _eqns(v.jaxpr)


def _assert_no_state_sized_pad_copies(jaxpr, state_dims):
    """No concatenate/pad result may have a state-sized leading dim: a
    padded copy of the state would show up as exactly that (the old
    wrappers concatenated a pad row onto every state array per call)."""
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name not in ("concatenate", "pad"):
            continue
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            assert not (shape and shape[0] in state_dims), (
                f"{eqn.primitive.name} materializes a state-sized copy: "
                f"{shape}"
            )


def _kv_state_dims(cfg):
    # live size, resident (+1), and would-be re-padded (+2) leading dims
    return {cfg.num_buckets, cfg.num_buckets + 1, cfg.num_buckets + 2,
            cfg.pool_size, cfg.pool_size + 1, cfg.pool_size + 2,
            cfg.cache_sets, cfg.cache_sets + 1, cfg.cache_sets + 2}


def _tx_state_dims(cfg):
    return {cfg.num_keys, cfg.num_keys + 1, cfg.num_keys + 2,
            cfg.log_capacity, cfg.log_capacity + 1, cfg.log_capacity + 2}


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_put_dispatch_materializes_no_padded_state_copy(backend):
    s = kv.make(KV_CFG)
    keys = jnp.ones((8, KV_CFG.key_words), I32)
    vals = jnp.ones((8, KV_CFG.val_words), I32)
    jx = jax.make_jaxpr(
        lambda st, k, v: kv.put(st, k, v, backend=backend)
    )(s, keys, vals)
    _assert_no_state_sized_pad_copies(jx.jaxpr, _kv_state_dims(KV_CFG))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_tx_commit_dispatch_materializes_no_padded_state_copy(backend):
    chain = tx.make_chain(TX_CFG)
    batch = jnp.zeros((6, tx.tx_words(TX_CFG)), I32).at[:, 0].set(1)
    jx = jax.make_jaxpr(
        lambda c, b: tx.chain_commit_local(c, b, TX_CFG,
                                           kernel_backend=backend)
    )(chain, batch)
    _assert_no_state_sized_pad_copies(jx.jaxpr, _tx_state_dims(TX_CFG))


def _aliased_calls(jaxpr):
    """Every pallas_call that declares input_output_aliases, each checked
    to take each aliased input exactly once among its operands."""
    aliased = [
        eqn for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
        and tuple(eqn.params.get("input_output_aliases") or ())
    ]
    for eqn in aliased:
        for i, _ in eqn.params["input_output_aliases"]:
            var = eqn.invars[i]
            n = sum(v is var for v in eqn.invars)
            kernel = eqn.params["jaxpr"].debug_info.func_name
            assert n == 1, (
                f"aliased operand {i} of {kernel} is passed {n} times: "
                f"XLA copies it whole before the call"
            )
    return aliased


def test_pallas_scatters_alias_state_in_and_out():
    """The whole point of the resident layout: the scatter kernels' declared
    input_output_aliases survive to the dispatched jaxpr (no interposed
    copy means the aliased operand IS the state buffer), and each aliased
    state array is that call's operand once and only once."""
    s = kv.make(KV_CFG)
    keys = jnp.ones((8, KV_CFG.key_words), I32)
    vals = jnp.ones((8, KV_CFG.val_words), I32)
    jx = jax.make_jaxpr(
        lambda st, k, v: kv.put(st, k, v, backend="pallas")
    )(s, keys, vals)
    # commit_buckets (bucket_keys+bucket_ptr) and write_rows (pool)
    assert len(_aliased_calls(jx.jaxpr)) >= 2, (
        "expected aliased scatter pallas_calls")

    chain = tx.make_chain(TX_CFG)
    batch = jnp.zeros((6, tx.tx_words(TX_CFG)), I32).at[:, 0].set(1)
    jx = jax.make_jaxpr(
        lambda c, b: tx.chain_commit_local(c, b, TX_CFG,
                                           kernel_backend="pallas")
    )(chain, batch)
    # the log scatter and the store scatter
    assert len(_aliased_calls(jx.jaxpr)) >= 2, (
        "expected the tx_commit pallas_calls to alias")


def test_row_scatter_patches_tiles_in_place_like_numpy():
    """``rows.scatter`` in interpret mode against numpy, R = 2 replicas of
    five 8-row tiles plus the sentinel row: runs that cross tile
    boundaries, several rows patched within one tile, replica 0's walk
    ending on its last tile (the sentinel run) right before replica 1's
    starts on its first, and untouched rows left as they were."""
    n, w = 5 * 8 + 1, 4  # row 40 is the sentinel, alone in tile 5
    idx = np.array([
        [1, 3, 7, 8, 9, 15, 16, 30, 31, 32, 38, 39, 40, 40, 40, 40],
        [0, 2, 5, 6, 7, 10, 17, 23, 24, 25, 31, 32, 33, 39, 40, 40],
    ], np.int32)
    rng = np.random.default_rng(3)
    dst = rng.integers(1, 1 << 30, (2, n, w)).astype(np.int32)
    dst[:, -1] = 0
    vals = rng.integers(1, 1 << 30, (2, idx.shape[1], w)).astype(np.int32)
    vals[idx == n - 1] = 0  # sentinel payloads are zero, as callers send
    want = dst.copy()
    for r in range(2):
        want[r, idx[r]] = vals[r]
    got = rows.scatter(jnp.asarray(dst), jnp.asarray(vals), jnp.asarray(idx),
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_commit_buckets_patches_blocks_in_place_like_numpy():
    """``hash_probe.commit_buckets`` in interpret mode against numpy: two
    ways of one bucket in one run, adjacent buckets sharing a pointer
    tile, a bucket alone in its tile, and the sentinel bucket's run of
    zero payloads; untouched ways keep their keys and pointers."""
    nb, w, kw = 20, 4, 2  # bucket 20 is the sentinel, in pointer tile 2
    tb = np.array([2, 2, 3, 5, 7, 8, 9, 15, 16, 20, 20], np.int32)
    tw = np.array([0, 3, 1, 2, 3, 0, 1, 2, 0, 0, 1], np.int32)
    rng = np.random.default_rng(4)
    bk = rng.integers(1, 1 << 30, (nb + 1, w, kw)).astype(np.int32)
    bp = rng.integers(0, 1 << 20, (nb + 1, w)).astype(np.int32)
    bk[-1], bp[-1] = 0, 0
    keys = rng.integers(1, 1 << 30, (tb.size, kw)).astype(np.int32)
    pv = rng.integers(0, 1 << 20, tb.size).astype(np.int32)
    keys[tb == nb], pv[tb == nb] = 0, 0
    want_k, want_p = bk.copy(), bp.copy()
    want_k[tb, tw], want_p[tb, tw] = keys, pv
    got_k, got_p = hash_probe.commit_buckets(
        jnp.asarray(bk), jnp.asarray(bp), jnp.asarray(keys), jnp.asarray(tb),
        jnp.asarray(tw), jnp.asarray(pv), interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_p), want_p)


def test_donated_state_aliases_through_put_commit():
    """With the state donated at the jit boundary, XLA can alias every
    state buffer input→output on the pallas path — the end-to-end
    donation the per-call pad copies used to defeat."""
    s = kv.make(KV_CFG)
    keys = jnp.ones((8, KV_CFG.key_words), I32)
    vals = jnp.ones((8, KV_CFG.val_words), I32)
    f = jax.jit(
        lambda st, k, v: kv.put(st, k, v, backend="pallas")[0],
        donate_argnums=0,
    )
    hlo = f.lower(s, keys, vals).compile().as_text()
    assert "input_output_alias" in hlo
    # all three O(state) arrays (bucket_keys, bucket_ptr, pool) alias
    n_alias = hlo.count("may-alias") + hlo.count("must-alias")
    assert n_alias >= 3, f"only {n_alias} aliased params in compiled HLO"


# --------------------------- sentinel hygiene ------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_kvs_sentinel_rows_stay_zero(seed):
    """Arbitrary PUT/GET traffic — duplicates, masked rows, way conflicts,
    drops, pool exhaustion — must leave the resident sentinel rows of all
    three KVS state arrays zero, on both backends."""
    cfg = kv.KVConfig(num_buckets=8, ways=2, key_words=2, val_words=4,
                      pool_size=24,  # tiny: forces spills + drops
                      cache_sets=3, cache_ways=2)  # tiny cache: evictions
    rng = np.random.default_rng(seed)
    for backend in ("ref", "pallas"):
        s = kv.make(cfg)
        put = jax.jit(lambda st, k, v, m: kv.put(st, k, v, m, backend=backend))
        get = jax.jit(lambda st, k: kv.get(st, k, backend=backend,
                                           with_state=True))
        for _ in range(4):
            keys = jnp.asarray(rng.integers(1, 30, (16, 2)), I32)
            vals = jnp.asarray(rng.integers(1, 99, (16, 4)), I32)
            mask = jnp.asarray(rng.random(16) < 0.8)
            s, _ = put(s, keys, vals, mask)
            # GETs only maintain the cache tier — buckets/pool untouched
            s, _, _ = get(s, keys)
        assert int(s.alloc) > 0  # traffic actually landed
        for arr in (s.bucket_keys, s.bucket_ptr, s.pool,
                    s.cache_keys, s.cache_vals, s.cache_meta):
            np.testing.assert_array_equal(
                np.asarray(arr[-1]), 0,
                err_msg=f"{backend}: sentinel row dirtied",
            )


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_tx_sentinel_rows_stay_zero(seed):
    """Arbitrary conflicted/masked commit rounds, including batches lapping
    the redo-log ring past ``log_capacity``, must leave the resident
    sentinel rows of log and store zero on every replica, both backends."""
    cfg = tx.TxConfig(num_keys=16, val_words=2, max_ops=3, chain_len=2,
                      log_capacity=4)  # batch 6 > LC 4: wraps within a call
    rng = np.random.default_rng(seed)
    w = tx.tx_words(cfg)
    for backend in ("ref", "pallas"):
        chain = tx.make_chain(cfg)
        commit = jax.jit(lambda c, b, m: tx.chain_commit_local(
            c, b, cfg, m, kernel_backend=backend))
        for _ in range(3):
            batch = np.zeros((6, w), np.int32)
            for i in range(6):
                n = int(rng.integers(1, cfg.max_ops + 1))
                batch[i, 0] = n
                for j in range(n):
                    base = 1 + j * (1 + cfg.val_words)
                    batch[i, base] = int(rng.integers(0, cfg.num_keys))
                    batch[i, base + 1: base + 3] = rng.integers(1, 99, 2)
            mask = jnp.asarray(rng.random(6) < 0.85)
            chain, _, _ = commit(chain, jnp.asarray(batch), mask)
        for arr in (chain.log, chain.store):
            np.testing.assert_array_equal(
                np.asarray(arr[:, -1]), 0,
                err_msg=f"{backend}: sentinel row dirtied",
            )
