"""ORCA-TX transactions (§IV-B): a fixed number of reads and writes each,
as the paper's (reads, writes) mixes (0, 1) and (4, 2).

Traffic file fields read here: ``reads`` and ``writes`` per transaction.
A transaction's reads are served by the chain's head, outside the redo-log
record that goes down the chain (§IV-B; ``benchmarks/bench_tx.py`` builds
its batches so too), and the engine's TX app takes log records only, so
only the ``writes`` are sent; ``reads`` is carried for the record. Write
offsets are uniform over the configuration's ``num_keys`` rows, drawn with
replacement, so two transactions of one batch may conflict (the first
claimant commits, the other is deferred and retried by its client).

Transaction ``s`` is a pure function of ``(seed, s)``, drawn in chunks of
``CHUNK`` from seeded streams (see ``ycsb.Requests``)."""
from __future__ import annotations

import numpy as np

from bench.requests import rng_for

CHUNK = 1 << 14


class Requests:
    """``chunk(i)`` gives ``{"n_ops" (C,), "offsets" (C, max_ops)}``;
    offsets past ``n_ops`` are 0 and never read."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.seed = seed
        self.writes, self.reads = int(traffic["writes"]), int(traffic["reads"])
        self.max_ops = int(config["max_ops"])
        self.rows = int(config["num_keys"])
        if not 1 <= self.writes <= self.max_ops or self.reads < 0:
            raise ValueError("need 1 <= writes <= max_ops and reads >= 0")

    def chunk(self, i: int) -> dict:
        rng = rng_for(self.seed, 2, i)
        off = np.zeros((CHUNK, self.max_ops), np.int64)
        off[:, :self.writes] = rng.integers(0, self.rows, (CHUNK, self.writes))
        return {"n_ops": np.full(CHUNK, self.writes, np.int32), "offsets": off}
