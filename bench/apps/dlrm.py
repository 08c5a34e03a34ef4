"""DLRM-DCNv2 inference as the benchmark drives it: the tables filled on the
device from the run's salt, the dense weights drawn from the seed, one
sample encoded per request, and every logit compared with
``bench/reference/dlrm.py``, computed on the device after the window.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench.counts import embedding_reduce as er
from bench.reference import dlrm as ref
from bench.requests import rng_for


class App:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import dlrm

        stated = (config["table_dtype"], config["weight_dtype"])
        if stated != ("bfloat16", "float32"):
            raise ValueError(f"the app builds bf16 tables and f32 weights; the "
                             f"configuration states {stated}")
        dense = config["dense_arch_layer_sizes"]
        if dense[-1] != config["embedding_dim"]:
            raise ValueError("the dense arch must end at the embedding width")
        self.dlrm = dlrm
        self.config = config
        rows = tuple(config["num_embeddings_per_feature"])
        self.cfg = dlrm.DLRMConfig(
            num_tables=len(rows), dim=config["embedding_dim"],
            dense_features=config["num_dense_features"], bottom=tuple(dense[:-1]),
            top=tuple(config["over_arch_layer_sizes"]), table_rows=rows,
            pooling=tuple(config["multi_hot_sizes"]),
            interaction=config["interaction_type"],
            cross_layers=config["dcn_num_layers"], cross_rank=config["dcn_low_rank_dim"],
            matmul_precision=config["matmul_precision"])
        self.words = dlrm.request_words(self.cfg)
        self.keep = 2  # status and logit
        self.salt = int(rng_for(seed, 0).integers(0, 1 << 32))
        self.weights = ref.dense_weights(config, seed)

    @property
    def app_step(self):
        return self.dlrm.app_step

    def build(self):
        """The tables, filled on the device, the weights and the counters."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        off = self.dlrm.offsets(cfg)

        # the salt is an argument, not a constant: one program serves every seed
        def fill(salt):
            g = jnp.arange(sum(cfg.table_rows), dtype=jnp.int32)
            t = jnp.sum(g[:, None] >= jnp.asarray(off[1:], jnp.int32)[None, :], axis=1)
            r = g - jnp.asarray(off, jnp.int32)[t]
            return ref.table_values(t, r, salt, cfg.dim, xp=jnp).astype(jnp.bfloat16)

        w = jax.tree.map(jnp.asarray, self.weights)
        return {"tables": jax.jit(fill)(jnp.uint32(self.salt)),
                "bottom": [{"w": a, "b": b} for a, b in w["bottom"]],
                "cross": [{"v": v, "w": a, "b": b} for v, a, b in w["cross"]],
                "top": [{"w": a, "b": b} for a, b in w["top"]],
                **self.dlrm.counters()}

    def encode(self, f: dict, serials: np.ndarray) -> np.ndarray:
        n, d = len(serials), self.cfg.dense_features
        rows = np.empty((n, self.words), np.int32)
        rows[:, 0] = self.dlrm.OP_INFER
        rows[:, 1:1 + d] = f["dense"].view(np.int32)
        rows[:, 1 + d:] = f["ids"]
        return rows

    def complete(self, answers: np.ndarray) -> np.ndarray:
        return np.ones(len(answers), bool)

    def extra(self, served: dict):
        return None

    def counters(self, state) -> dict:
        return {"lookups": self.dlrm.ids_pooled(state, self.cfg),
                "malformed": int(state["malformed"])}

    def calls(self, rounds, fields_of, delta: dict, chips: int) -> dict:
        """Work of the samples the traced rounds served: the pooling's bytes
        from ``bench/counts``, and the whole step's (the pooling, the dense
        weights read once a step, each request's words in through a ring
        slot and its answer out, written and read) with its matmul FLOPs."""
        n = sum(len(r.serials) for r in rounds)
        sent = n * sum(self.cfg.pooling)
        print(f"dlrm: the traced rounds served {n} samples, {sent} ids; the "
              f"lookups counter rose {delta['lookups']}, malformed "
              f"{delta['malformed']}", file=sys.stderr)
        shape = {"embedding_dim": self.cfg.dim, "table_bytes": 2}
        w = {"embedding_reduce": er.needed(
            shape, {"lookups": delta["lookups"], "segments": n * self.cfg.num_tables})}
        arrays = [a for layers in self.weights.values() for layer in layers for a in layer]
        weights = 4 * sum(a.size for a in arrays)
        ring = 4 * 2 * (self.words + self.keep) * n
        flops = 2 * sum(a.size for a in arrays if a.ndim == 2) * n
        w["step"] = (w["embedding_reduce"][0] + weights * len(rounds) + ring, flops)
        return {"work": w, "requests": n}

    def final(self, state) -> dict:
        return self.counters(state)

    def check(self, rounds, fields_of, final: dict):
        """Every answer: its status, and its logit against the reference's
        for its sample."""
        t0 = time.perf_counter()
        serials = np.concatenate([r.serials for r in rounds])
        answers = np.concatenate([r.answers for r in rounds])
        f = fields_of(serials)
        want = ref.Model(self.config, self.weights, self.salt).logits(f["dense"], f["ids"])
        got = answers[:, 1].copy().view(np.float32)
        bad = ref.mismatches(got, want)
        gap = np.abs(got - want)
        share = gap / (ref.ATOL + ref.RTOL * np.abs(want))
        at = int(np.nanargmax(share)) if len(share) else 0
        print(f"dlrm: checked {len(serials)} answers in {time.perf_counter() - t0:.3f} s; "
              f"logit gap max {np.nanmax(gap, initial=0):.3e}, largest share of the "
              f"tolerance {np.nanmax(share, initial=0):.4f} (gap {gap[at] if len(gap) else 0:.3e} "
              f"at |logit| {abs(want[at]) if len(want) else 0:.3e}), |logit| median "
              f"{np.median(np.abs(want)) if len(want) else 0:.3e}; lookups counter "
              f"{final['lookups']} for {len(serials) * sum(self.cfg.pooling)} ids "
              f"answered", file=sys.stderr)
        out = {"status": int((answers[:, 0] != 1).sum()), "logit": int(bad.sum())}
        return out, len(serials)
