"""The reference's bucket-occupancy model against the store itself, on a
table small enough that loads are refused: every acknowledgement agrees."""
from __future__ import annotations

import numpy as np
import pytest

from _util import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench.reference import kvs as ref


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_load_refusals_match_the_store(seed):
    import jax
    import jax.numpy as jnp
    from repro.core import kvstore as kv

    config = dict(num_buckets=16, ways=4, key_words=2, val_words=8,
                  pool_size=256, cache_sets=0, cache_ways=4)
    cfg = kv.KVConfig(**config)
    rng = np.random.default_rng(seed)
    n, batch = 96, 32
    keys = np.stack([np.arange(1, n + 1), rng.integers(1, 2 ** 31 - 1, n)],
                    1).astype(np.int32)
    put = jax.jit(lambda s, k, v: kv.put(s, k, v, backend="auto"))
    state = kv.make(cfg)
    acked = []
    for s in range(0, n, batch):
        k = keys[s:s + batch]
        v = ref.value_rows(np.arange(s, s + batch), np.zeros(batch), 99, 8)
        state, ok = put(state, jnp.asarray(k), jnp.asarray(v))
        acked.append(np.asarray(ok))
    acked = np.concatenate(acked)
    store = ref.Store(keys, config, 99)
    assert store.load([np.arange(s, s + batch) for s in range(0, n, batch)], acked) == 0
    assert (~acked).sum() > 0  # the model was put to the test
    # a GET of every key: found exactly where the model placed it
    vals, found = kv.get(state, jnp.asarray(keys), backend="auto")
    want_found, want_rows = store.expected(np.arange(n))
    assert np.array_equal(np.asarray(found), want_found)
    assert np.array_equal(np.asarray(vals), want_rows)
