"""Criteo 1TB multi-hot inference requests, as MLPerf's DLRM-DCNv2 reads
them: one sample a request, 13 dense features and, per table,
``multi_hot_sizes[t]`` ids (214 a sample), laid out table by table.

Per table and sample, the first id follows YCSB's scrambled zipfian
(constant 0.99, ``ycsb.scrambled_zipfian``) over the rows held on this chip,
and the rest are uniform over those rows, as MLPerf's multi-hot data set
adds them under ``multi_hot_distribution_type=uniform``. A dense feature is
``log(1 + x)`` with ``x`` geometric of mean ``dense_mean`` (traffic file).

Request ``s`` of a run is a pure function of ``(seed, s)``: requests are
drawn in chunks of ``CHUNK``, each from its own seeded stream.
"""
from __future__ import annotations

import numpy as np

from bench.generators.ycsb import scrambled_zipfian
from bench.requests import rng_for

CHUNK = 1 << 14


class Requests:
    """``chunk(i)`` gives requests ``[i * CHUNK, (i + 1) * CHUNK)`` as
    ``{"dense": (n, 13) float32, "ids": (n, 214) int32}``, each id local to
    its table's rows here."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        if traffic["first_id_distribution"] != "zipfian":
            raise ValueError(f"unknown first-id distribution "
                             f"{traffic['first_id_distribution']!r}")
        if traffic["multi_hot_distribution_type"] != "uniform":
            raise ValueError(f"unknown multi-hot distribution "
                             f"{traffic['multi_hot_distribution_type']!r}")
        self.seed = seed
        self.rows = np.asarray(config["num_embeddings_per_feature"], np.int64)
        self.pooling = np.asarray(config["multi_hot_sizes"], np.int64)
        self.dense = int(config["num_dense_features"])
        self.mean = float(traffic["dense_mean"])
        self.first = np.concatenate([[0], np.cumsum(self.pooling)[:-1]])
        self.col_rows = np.repeat(self.rows, self.pooling)

    def chunk(self, i: int) -> dict:
        rng = rng_for(self.seed, 1, i)
        ids = rng.integers(0, self.col_rows, (CHUNK, len(self.col_rows)))
        for t, rows in enumerate(self.rows):
            ids[:, self.first[t]] = scrambled_zipfian(rng, CHUNK, int(rows))
        x = rng.geometric(1 / self.mean, (CHUNK, self.dense))
        return {"dense": np.log1p(x).astype(np.float32), "ids": ids.astype(np.int32)}
