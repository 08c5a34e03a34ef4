"""Chain-replicated transactions as the benchmark drives them: the chain
built on the device, transactions encoded as redo-log records, and the
comparison of every answer and every replica with ``bench/reference/tx.py``.
"""
from __future__ import annotations

import numpy as np

from bench.counts import tx_commit
from bench.reference import tx as ref


class App:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import transaction as tx
        from repro.core import tx_app

        self.tx, self.tx_app = tx, tx_app
        self.config, self.traffic = config, traffic
        self.cfg = tx.TxConfig(
            num_keys=config["num_keys"], val_words=config["val_words"],
            max_ops=config["max_ops"], chain_len=config["chain_len"],
            log_capacity=config["log_capacity"])
        self.words = tx.tx_words(self.cfg)
        self.keep = 1  # the status word

    @property
    def app_step(self):
        return self.tx_app.app_step

    def build(self):
        """The empty chain, made on the device."""
        import jax

        return jax.jit(lambda: self.tx.make_chain(self.cfg))()

    def encode(self, f: dict, serials: np.ndarray) -> np.ndarray:
        return ref.records(serials, f["n_ops"], f["offsets"], self.cfg.val_words)

    def complete(self, answers: np.ndarray) -> np.ndarray:
        """A deferred transaction is sent again by its client."""
        return answers[:, 0] != ref.DEFERRED

    def extra(self, served: dict):
        return None

    def counters(self, chain) -> dict:
        return {"committed": int(np.asarray(chain.committed)[0])}

    def calls(self, rounds, fields_of, delta: dict, chips: int) -> dict:
        """Kernel work of the traced rounds' commits, from ``bench/counts``."""
        commits = delta["committed"]
        rows = ops = live = 0
        for r in rounds:
            f, commit = fields_of(r.serials), r.answers[:, 0] == ref.COMMITTED
            rows += self.rows_written(f, commit)
            ops += int(f["n_ops"][commit].sum())
            live += int(f["n_ops"].sum())
        answered = sum(len(r.serials) for r in rounds)
        per_chip = self.cfg.chain_len // chips
        w = {"tx_commit": tx_commit.needed(
            dict(self.config), {"commits": commits, "ops": ops, "rows": rows,
                                "replicas": per_chip})}
        # each answered record in through a ring slot and its status word
        # out, as long as its live ops make it; written and read
        ring = 4 * 2 * (answered * 2 + live * (1 + self.cfg.val_words)) // chips
        w["step"] = (w["tx_commit"][0] + ring, 0)
        return {"work": w, "requests": commits}

    def final(self, chain) -> dict:
        return {k: np.asarray(getattr(chain, k))
                for k in ("store", "log", "log_tail", "committed")}

    def check(self, rounds, fields_of, final: dict):
        c = self.cfg
        chain = ref.Chain(c.num_keys, c.val_words, c.max_ops, c.log_capacity)
        status = 0
        for r in rounds:
            f = fields_of(r.serials)
            want = chain.step(self.encode(f, r.serials))
            status += int((r.answers[:, 0] != want).sum())
        out = {"status": status}
        store, log = final["store"], final["log"]
        # every replica holds the reference rows, and a zero sentinel row
        out["store_rows"] = int(sum(
            (store[i, :-1] != chain.store).any(axis=1).sum()
            + store[i, -1].any() for i in range(c.chain_len)))
        out["log_rows"] = int(sum(
            (log[i, :-1] != chain.log).any(axis=1).sum() + log[i, -1].any()
            for i in range(c.chain_len)))
        out["log_tail"] = int(np.abs(final["log_tail"] - chain.tail).sum())
        out["commit_count"] = int(np.abs(final["committed"] - chain.tail).sum())
        return out, sum(len(r.serials) for r in rounds)

    @staticmethod
    def rows_written(f: dict, commit: np.ndarray) -> int:
        """Distinct rows the committed transactions write."""
        off, n = f["offsets"][commit], f["n_ops"][commit]
        live = np.arange(off.shape[1])[None, :] < n[:, None]
        srt = np.sort(np.where(live, off, -1), axis=1)
        fresh = np.concatenate([srt[:, :1] >= 0, (srt[:, 1:] != srt[:, :-1])
                                & (srt[:, 1:] >= 0)], axis=1)
        return int(fresh.sum())
