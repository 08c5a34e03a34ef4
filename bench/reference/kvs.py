"""Plain reference of the key-value store's semantics, in numpy.

A record is present once a PUT of it landed, and its value is the row
written by the last PUT of it that landed. Within one engine step every GET
sees the store as it was before the step, and PUTs of one key land in batch
order (the last one wins), which is what the engine's app step promises.
An update of a present record always lands. An insert lands unless both of
its candidate buckets are full: the store is a MICA-style table of
``num_buckets`` buckets of ``ways`` ways, a key may live in its primary
bucket (FNV-1a of its words) or its overflow bucket (the same hash salted
with ``SECOND_SALT``), and a batch places its inserts primary-first in batch
order, then spills the rest in batch order (and never past ``pool_size``
records). The reference keeps only how full each bucket is.

Values are a pure function of (record, version, the run's salt), so the
reference keeps one version number per record instead of the rows.
"""
from __future__ import annotations

import numpy as np

FNV_OFFSET, FNV_PRIME = 2166136261, 16777619
SECOND_SALT = 0x9E3779B9


def value_rows(rec, version, salt: int, width: int, xp=np):
    """Value rows of records ``rec`` at ``version`` (0 = as loaded, ``s + 1``
    = written by request ``s``) in a run salted ``salt`` (a uint32 drawn
    from the seed): ``(N, width)`` int32. ``xp`` is numpy or ``jax.numpy``;
    both give the same bits."""
    u = xp.uint32
    rec = xp.asarray(rec).astype(u)[:, None]
    ver = xp.asarray(version).astype(u)[:, None]
    col = xp.arange(width, dtype=u)[None, :]
    v = (rec * u(2654435761) + u(salt) * u(40503) + ver * u(2246822519)
         + col * u(97) + u(12345))
    v = v ^ (v >> u(15))
    return (v >> u(1)).astype(xp.int32)


def bucket(keys: np.ndarray, num_buckets: int, salt: int = 0) -> np.ndarray:
    """FNV-1a over the key words, modulo the bucket count."""
    h = np.full(len(keys), (FNV_OFFSET ^ salt) & 0xFFFFFFFF, np.uint32)
    for w in range(keys.shape[1]):
        h = (h ^ keys[:, w].astype(np.uint32)) * np.uint32(FNV_PRIME)
    return (h % np.uint32(num_buckets)).astype(np.int64)


def _rank(ids: np.ndarray) -> np.ndarray:
    """Rank of each entry among the entries with its id, in order."""
    order = np.argsort(ids, kind="stable")
    s = ids[order]
    first = np.searchsorted(s, s, side="left")
    rank = np.empty(len(ids), np.int64)
    rank[order] = np.arange(len(ids)) - first
    return rank


class Store:
    """Versions of the records whose key words are ``keys (R, key_words)``,
    in a run salted ``salt``."""

    def __init__(self, keys: np.ndarray, config: dict, salt: int):
        self.keys, self.salt = keys, salt
        self.width = config["val_words"]
        self.ways = config["ways"]
        self.pool_left = config["pool_size"]
        nb = config["num_buckets"]
        self.h1 = bucket(keys, nb)
        self.h2 = bucket(keys, nb, SECOND_SALT)
        self.count = np.zeros(nb, np.int64)  # occupied ways per bucket
        self.version = np.full(len(keys), -1, np.int64)  # -1 = absent

    def insert(self, rec: np.ndarray) -> np.ndarray:
        """Place absent records ``rec`` (distinct, batch order); returns
        which landed."""
        h1, h2 = self.h1[rec], self.h2[rec]
        fit1 = _rank(h1) < self.ways - self.count[h1]
        np.add.at(self.count, h1[fit1], 1)
        spill = np.flatnonzero(~fit1)
        fit2 = np.zeros(len(rec), bool)
        fit2[spill] = _rank(h2[spill]) < self.ways - self.count[h2[spill]]
        np.add.at(self.count, h2[fit2], 1)
        fit = fit1 | fit2
        if fit.sum() > self.pool_left:
            raise NotImplementedError("a full value pool is not modelled")
        self.pool_left -= int(fit.sum())
        return fit

    def load(self, batches, acked: np.ndarray) -> int:
        """Apply the load batches (lists of record ids); returns how many
        acknowledgements differ from what the table allows."""
        bad = 0
        for rec in batches:
            lands = self.insert(rec)
            self.version[rec[lands]] = 0
            bad += int((lands != acked[rec]).sum())
        return bad

    def expected(self, rec: np.ndarray):
        """(found (N,), rows (N, width)) for GETs of records ``rec``."""
        ver = self.version[rec]
        rows = value_rows(rec, np.maximum(ver, 0), self.salt, self.width)
        rows[ver < 0] = 0
        return ver >= 0, rows

    def step(self, serial, op, rec, status, vals, get_op: int, put_op: int):
        """Check one engine step's answers, given in batch order, then apply
        its writes. Returns a dict of mismatch counts."""
        is_get, is_put = op == get_op, op == put_op
        found, rows = self.expected(rec[is_get])
        out = {"get_found": int((status[is_get] != found).sum()),
               "get_value": int((vals[is_get] != rows).any(axis=1).sum())}
        # absent records the step PUTs try to insert, first instance first
        new = np.flatnonzero(is_put & (self.version[rec] < 0))
        if len(new):
            uniq, first = np.unique(rec[new], return_index=True)
            cand = uniq[np.argsort(first)]
            self.version[cand[self.insert(cand)]] = -2  # landed this step
        lands = is_put & (self.version[rec] != -1)
        out["put_ack"] = int((status[is_put] != lands[is_put]).sum())
        out["unknown_op"] = int((~(is_get | is_put)).sum())
        # last writer of each record in batch order wins
        w = np.flatnonzero(lands)
        if len(w):
            last = len(w) - 1 - np.unique(rec[w][::-1], return_index=True)[1]
            self.version[rec[w[last]]] = serial[w[last]] + 1
        return out
