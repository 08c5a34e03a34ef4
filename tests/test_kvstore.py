"""ORCA-KV: randomized differential testing against a dict model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import kvstore as kv


def test_get_put_roundtrip():
    cfg = kv.KVConfig(num_buckets=64, ways=4, key_words=2, val_words=4, pool_size=256)
    s = kv.make(cfg)
    keys = jnp.array([[1, 2], [3, 4]], jnp.int32)
    vals = jnp.array([[10, 11, 12, 13], [20, 21, 22, 23]], jnp.int32)
    s, ok = kv.put(s, keys, vals)
    assert bool(jnp.all(ok))
    got, found = kv.get(s, keys)
    assert bool(jnp.all(found))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals))
    _, nf = kv.get(s, jnp.array([[9, 9]], jnp.int32))
    assert not bool(nf[0])


def test_update_in_place():
    cfg = kv.KVConfig(num_buckets=16, ways=2, key_words=1, val_words=2, pool_size=64)
    s = kv.make(cfg)
    k = jnp.array([[7]], jnp.int32)
    s, _ = kv.put(s, k, jnp.array([[1, 1]], jnp.int32))
    alloc0 = int(s.alloc)
    s, _ = kv.put(s, k, jnp.array([[2, 2]], jnp.int32))
    assert int(s.alloc) == alloc0  # no new slab row for updates
    got, found = kv.get(s, k)
    assert bool(found[0]) and list(np.asarray(got)[0]) == [2, 2]


def test_in_batch_duplicates_last_writer_wins():
    cfg = kv.KVConfig(num_buckets=16, ways=4, key_words=1, val_words=1, pool_size=64)
    s = kv.make(cfg)
    keys = jnp.array([[5], [5], [5]], jnp.int32)
    vals = jnp.array([[1], [2], [3]], jnp.int32)
    s, ok = kv.put(s, keys, vals)
    got, found = kv.get(s, jnp.array([[5]], jnp.int32))
    assert bool(found[0]) and int(got[0, 0]) == 3
    assert int(s.alloc) == 1  # one slab row for one unique key


def test_drop_accounting_when_full():
    cfg = kv.KVConfig(num_buckets=2, ways=1, key_words=1, val_words=1, pool_size=64)
    s = kv.make(cfg)
    keys = jnp.arange(1, 9, dtype=jnp.int32)[:, None]
    s, ok = kv.put(s, keys, keys)
    assert int(s.dropped) == 8 - int(np.asarray(ok).sum())
    assert int(s.dropped) > 0  # 8 keys cannot fit in 2 ways + overflow


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_differential_vs_dict(seed):
    cfg = kv.KVConfig(num_buckets=32, ways=4, key_words=2, val_words=2, pool_size=256)
    s = kv.make(cfg)
    rng = np.random.default_rng(seed)
    ref: dict = {}
    put = jax.jit(kv.put)
    getf = jax.jit(kv.get)
    for _ in range(6):
        b = 16
        keys = rng.integers(1, 40, size=(b, 2)).astype(np.int32)
        vals = rng.integers(0, 99, size=(b, 2)).astype(np.int32)
        s, ok = put(s, jnp.array(keys), jnp.array(vals))
        ok = np.asarray(ok)
        last = {}
        for i in range(b):
            last[tuple(keys[i])] = (vals[i], ok[i])
        for kk, (vv, okk) in last.items():
            if okk:
                ref[kk] = vv
        qk = rng.integers(1, 60, size=(b, 2)).astype(np.int32)
        gv, gf = getf(s, jnp.array(qk))
        gv, gf = np.asarray(gv), np.asarray(gf)
        for i in range(b):
            kq = tuple(qk[i])
            if kq in ref:
                assert gf[i], (kq, seed)
                np.testing.assert_array_equal(gv[i], ref[kq])
            else:
                assert not gf[i], (kq, seed)


# ------------------------- hot-set cache coherence -------------------------

_CACHED_CFG = kv.KVConfig(num_buckets=16, ways=2, key_words=2, val_words=2,
                          pool_size=64, cache_sets=4, cache_ways=2)


def _check_cache_invariants(s):
    """The cache-tier safety net: sentinel row zero, meta within the CLOCK
    range, each key cached in at most one way (occupancy never exceeds
    capacity), and every cached value equal to the bucket-walk read of its
    key (no stale value survives an overwrite)."""
    from repro.kernels import ref as kref

    ck = np.asarray(s.cache_keys)
    cv = np.asarray(s.cache_vals)
    cm = np.asarray(s.cache_meta)
    assert not ck[-1].any() and not cv[-1].any() and not cm[-1].any()
    assert (cm >= 0).all() and (cm <= 1 + kv.CACHE_REF_MAX).all()
    valid = cm[:-1] > 0
    keys = ck[:-1][valid]
    vals = cv[:-1][valid]
    if not len(keys):
        return
    assert len({tuple(k) for k in keys}) == len(keys)  # one way per key
    kj = jnp.asarray(keys, jnp.int32)
    h1 = kv.hash_keys(kj, s.num_buckets)
    h2 = kv.hash_keys(kj, s.num_buckets, salt=0x9E3779B9)
    bv, bf = kref.hash_get(s.bucket_keys, s.bucket_ptr, s.pool, kj, h1, h2)
    assert np.asarray(bf).all()  # a cached key always exists in the store
    np.testing.assert_array_equal(vals, np.asarray(bv))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_cache_coherence(seed):
    """Any interleaving of (masked) PUT and GET batches over a cached
    store: a cached read equals the bucket-walk read, overwrites never
    leave a stale cached value, the sentinel row stays zero, and
    occupancy never exceeds capacity."""
    from repro.kernels import ref as kref

    s = kv.make(_CACHED_CFG)
    rng = np.random.default_rng(seed)
    b = 8
    for _ in range(5):
        keys = jnp.asarray(rng.integers(1, 12, (b, 2)), jnp.int32)
        vals = jnp.asarray(rng.integers(0, 99, (b, 2)), jnp.int32)
        mask = jnp.asarray(rng.random(b) < 0.8)
        s, _ = kv.put(s, keys, vals, mask, backend="ref")
        _check_cache_invariants(s)
        qk = jnp.asarray(rng.integers(1, 14, (b, 2)), jnp.int32)
        qmask = jnp.asarray(rng.random(b) < 0.8)
        s, gv, gf = kv.get(s, qk, qmask, backend="ref", with_state=True)
        _check_cache_invariants(s)
        h1 = kv.hash_keys(qk, s.num_buckets)
        h2 = kv.hash_keys(qk, s.num_buckets, salt=0x9E3779B9)
        bv, bf = kref.hash_get(s.bucket_keys, s.bucket_ptr, s.pool, qk,
                               h1, h2)
        np.testing.assert_array_equal(
            np.asarray(gf), np.asarray(bf & qmask)
        )
        live_found = np.asarray(gf)
        np.testing.assert_array_equal(
            np.asarray(gv)[live_found], np.asarray(bv)[live_found]
        )


def test_cache_overwrite_leaves_no_stale_value():
    """Directed version of the write-through guarantee: admit a key into
    the cache via a GET, overwrite it with a PUT, and the very next cached
    GET must serve the new value (and still count as a hit)."""
    s = kv.make(_CACHED_CFG)
    k = jnp.asarray([[4, 2]], jnp.int32)
    s, _ = kv.put(s, k, jnp.asarray([[7, 7]], jnp.int32), backend="ref")
    s, v, f = kv.get(s, k, backend="ref", with_state=True)  # cached now
    assert bool(f[0]) and list(np.asarray(v)[0]) == [7, 7]
    s, _ = kv.put(s, k, jnp.asarray([[9, 9]], jnp.int32), backend="ref")
    hits0 = int(s.cache_hits)
    s, v, f = kv.get(s, k, backend="ref", with_state=True)
    assert bool(f[0]) and list(np.asarray(v)[0]) == [9, 9]
    assert int(s.cache_hits) == hits0 + 1  # served from the cache tier
    _check_cache_invariants(s)


def test_engine_app_request_format():
    cfg = kv.KVConfig(num_buckets=16, ways=2, key_words=2, val_words=4, pool_size=64)
    s = kv.make(cfg)
    w = kv.request_words(cfg)
    put_req = jnp.zeros((1, w), jnp.int32).at[0, 0].set(kv.OP_PUT)
    put_req = put_req.at[0, 1:3].set(jnp.array([4, 5])).at[0, 3:7].set(jnp.array([9, 8, 7, 6]))
    s, resp = kv.app_step(s, put_req, jnp.array([True]), cfg)
    assert int(resp[0, 0]) == 1
    get_req = jnp.zeros((1, w), jnp.int32).at[0, 0].set(kv.OP_GET)
    get_req = get_req.at[0, 1:3].set(jnp.array([4, 5]))
    s, resp = kv.app_step(s, get_req, jnp.array([True]), cfg)
    assert int(resp[0, 0]) == 1
    assert list(np.asarray(resp[0, 1:5])) == [9, 8, 7, 6]


def _rank_oracle(ids, num):
    """The searchsorted formulation: each id's first sorted position, looked
    up in a table over every id in ``[0, num)``."""
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    first = jnp.searchsorted(sorted_ids, jnp.arange(num), side="left")
    rank_sorted = jnp.arange(n) - first[sorted_ids]
    return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


_NB1 = 2**18 + 1  # the benchmark table's bucket ids, sentinel included


def _rank_case(name):
    rng = np.random.default_rng(14)
    if name == "random":
        return rng.integers(0, _NB1, 256)
    if name == "all_equal":
        return np.full(256, 77)
    if name == "all_distinct":
        return rng.permutation(_NB1)[:256]
    if name == "mostly_sentinel":
        ids = np.full(256, _NB1 - 1)
        hot = rng.random(256) < 0.1
        ids[hot] = rng.integers(0, 8, hot.sum())
        return ids
    if name == "single":
        return np.array([5])
    if name == "load_batch":
        return rng.integers(0, _NB1, 16384)
    raise ValueError(name)


@pytest.mark.parametrize("case", ["random", "all_equal", "all_distinct",
                                  "mostly_sentinel", "single", "load_batch"])
def test_rank_within_matches_searchsorted(case):
    ids = jnp.asarray(_rank_case(case), jnp.int32)
    got = jax.jit(kv._rank_within)(ids)
    want = jax.jit(_rank_oracle, static_argnums=1)(ids, _NB1)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for p in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                yield inner


def _loop_carries(jaxpr):
    # a fixed-count loop (``jnp.searchsorted``'s) is a ``scan`` in the jaxpr;
    # both it and ``while`` compile to an HLO while
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            yield from (tuple(v.aval.shape) for v in eqn.invars)
        for sub in _sub_jaxprs(eqn):
            yield from _loop_carries(sub)


def test_plan_put_has_no_loop_over_every_bucket():
    # the PUT plan at the benchmark's table size, traced from shapes alone:
    # ranking its inserters must cost O(batch), never a loop over all ids
    cfg = kv.KVConfig(num_buckets=2**18, ways=8, key_words=6, val_words=256,
                      pool_size=2**20, cache_sets=1024, cache_ways=4)
    state = jax.eval_shape(lambda: kv.make(cfg))
    keys = jax.ShapeDtypeStruct((256, cfg.key_words), jnp.int32)
    mask = jax.ShapeDtypeStruct((256,), jnp.bool_)
    closed = jax.make_jaxpr(
        lambda s, k, m: kv.plan_put(s, k, m, backend="ref"))(state, keys, mask)
    carries = list(_loop_carries(closed.jaxpr))
    assert not [c for c in carries if cfg.num_buckets + 1 in c]
