"""The key-value store as the benchmark drives it: state built and loaded
on the device from the seed, requests encoded into the app's slots, and
the comparison of every answer with ``bench/reference/kvs.py``.

Record ``r`` has YCSB's key for it (``ycsb.key_names``) and is loaded with
its version-0 value row, computed on the device from the run's salt.
"""
from __future__ import annotations

import numpy as np

from bench.counts import cache_probe, hash_get, hash_put
from bench.generators.ycsb import OP_GET, OP_PUT, key_names
from bench.reference import kvs as ref
from bench.requests import rng_for


class App:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import kvstore as kv

        self.kv = kv
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cfg = kv.KVConfig(
            num_buckets=config["num_buckets"], ways=config["ways"],
            key_words=config["key_words"], val_words=config["val_words"],
            pool_size=config["pool_size"], cache_sets=config["cache_sets"],
            cache_ways=config["cache_ways"])
        self.words = kv.request_words(self.cfg)
        self.keep = 1 + self.cfg.val_words  # status and value
        self.keys = key_names(int(config["records"]), 4 * self.cfg.key_words)
        self.salt = int(rng_for(seed, 0).integers(0, 1 << 32))
        self.load_acked = None

    @property
    def app_step(self):
        return self.kv.app_step

    def build(self):
        """The loaded store on the device: ``records`` PUTs in batches of
        ``load_batch``, values made on the device (set-up)."""
        import jax
        import jax.numpy as jnp

        kv, cfg, salt = self.kv, self.cfg, self.salt
        batch = int(self.config["load_batch"])
        n = len(self.keys)
        keys = jax.device_put(self.keys)

        # the salt is an argument, not a constant: one program serves every seed
        def load(state, keys, start, salt):
            k = jax.lax.dynamic_slice(keys, (start, 0), (batch, cfg.key_words))
            v = ref.value_rows(start + jnp.arange(batch), jnp.zeros(batch, jnp.int32),
                               salt, cfg.val_words, xp=jnp)
            return kv.put(state, k, v, backend="auto")

        state = jax.jit(lambda: kv.make(cfg))()
        load = jax.jit(load, donate_argnums=0)
        acks = []
        for start in range(0, n - n % batch, batch):
            state, ok = load(state, keys, jnp.int32(start), jnp.uint32(salt))
            acks.append(ok)
        if n % batch:
            raise ValueError("records must be a multiple of load_batch")
        self.load_acked = np.concatenate([np.asarray(a) for a in acks])
        return state

    def encode(self, f: dict, serials: np.ndarray) -> np.ndarray:
        op, rec = f["op"], f["key"]
        rows = np.zeros((len(op), self.words), np.int32)
        rows[:, 0] = op
        kw = self.cfg.key_words
        rows[:, 1:1 + kw] = self.keys[rec]
        put = op == OP_PUT
        if put.any():
            rows[put, 1 + kw:] = ref.value_rows(rec[put], serials[put] + 1,
                                                self.salt, self.cfg.val_words)
        return rows

    def complete(self, answers: np.ndarray) -> np.ndarray:
        return np.ones(len(answers), bool)

    def extra(self, served: dict):
        """Read-back after the window: GETs of ``readback`` records drawn
        from the seed among those the run updated (all of them if fewer)."""
        recs = np.unique(served["key"][served["op"] == OP_PUT])
        n = min(int(self.traffic["readback"]), len(recs))
        pick = rng_for(self.seed, 3).choice(recs, n, replace=False)
        return {"op": np.full(n, OP_GET, np.int32), "key": pick.astype(np.int64)}

    def counters(self, state) -> dict:
        return {"cache_hits": int(state.cache_hits),
                "cache_misses": int(state.cache_misses)}

    def calls(self, rounds, fields_of, delta: dict, chips: int) -> dict:
        """Kernel work of the requests the traced rounds served, from
        ``bench/counts``, and the engine's cache counters over them."""
        shape = dict(self.config)
        op = np.concatenate([fields_of(r.serials)["op"] for r in rounds])
        gets, puts = int((op == OP_GET).sum()), int((op == OP_PUT).sum())
        hits, misses = delta["cache_hits"], delta["cache_misses"]
        w = {
            "hash_get": hash_get.needed(shape, {"walks": misses}),
            "hash_put": hash_put.needed(shape, {"puts": puts, "inserts": 0}),
            "cache_probe": cache_probe.needed(
                shape, {"gets": gets, "puts": puts, "hits": hits}),
        }
        # each request in through a ring slot and its answer out, written
        # and read: a GET's key and value, a PUT's key, value and status
        kw, vw = self.cfg.key_words, self.cfg.val_words
        ring = 4 * 2 * ((2 + kw + vw) * (gets + puts) + puts)
        w["step"] = (sum(b for b, _ in w.values()) + ring, 0)
        return {"work": w, "requests": gets + puts,
                "hit_rate": (hits, hits + misses)}

    def final(self, state) -> dict:
        return {}

    def check(self, rounds, fields_of, final: dict):
        store = ref.Store(self.keys, self.config, self.salt)
        batch = int(self.config["load_batch"])
        out = {"load_ack": store.load(
            [np.arange(s, s + batch) for s in range(0, len(self.keys), batch)],
            self.load_acked)}
        run, back = {}, {}
        for r in rounds:
            f = fields_of(r.serials)
            tally = store.step(r.serials, f["op"], f["key"], r.answers[:, 0],
                               r.answers[:, 1:1 + self.cfg.val_words],
                               OP_GET, OP_PUT)
            into = back if r.phase == "extra" else run
            for k, v in tally.items():
                into[k] = into.get(k, 0) + v
        for k, v in run.items():
            out[k] = v
        out["readback_value"] = back.get("get_value", 0) + back.get("get_found", 0)
        checked = sum(len(r.serials) for r in rounds)
        return out, checked
