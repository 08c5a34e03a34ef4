"""Plain reference of chain-replicated write transactions, in numpy.

A batch is applied in batch order under the paper's rule (§IV-B): a row may
be written by one outstanding transaction only, so a transaction commits
iff it is the first in its batch to claim every row it writes; the others
are deferred. A committed transaction appends its record to the redo log
ring and writes its rows in op order (a later op to the same row wins).
Every replica of the chain ends in this same state.

A log record is ``[n_ops | (offset, value[val_words]) * max_ops]`` with
unused ops zero; values are a pure function of (transaction, op, column).
"""
from __future__ import annotations

import numpy as np

COMMITTED, DEFERRED = 1, 2


def op_values(serial, max_ops: int, width: int) -> np.ndarray:
    """Values written by transactions ``serial (N,)``: (N, max_ops, width)."""
    u = np.uint32
    s = np.asarray(serial).astype(u)[:, None, None]
    j = np.arange(max_ops, dtype=u)[None, :, None]
    c = np.arange(width, dtype=u)[None, None, :]
    v = s * u(2246822519) + j * u(3266489917) + c * u(668265263) + u(374761393)
    v = v ^ (v >> u(13))
    return (v >> u(1)).astype(np.int32)


def records(serial, n_ops, offsets, width: int) -> np.ndarray:
    """Log records of transactions ``serial`` (N,) with ``n_ops`` (N,) and
    ``offsets`` (N, max_ops): (N, 1 + max_ops * (1 + width)) int32."""
    n, m = offsets.shape
    live = np.arange(m)[None, :] < np.asarray(n_ops)[:, None]
    body = np.zeros((n, m, 1 + width), np.int32)
    body[..., 0] = np.where(live, offsets, 0)
    body[..., 1:] = np.where(live[..., None], op_values(serial, m, width), 0)
    return np.concatenate([np.asarray(n_ops, np.int32)[:, None],
                           body.reshape(n, m * (1 + width))], axis=1)


class Chain:
    def __init__(self, num_keys: int, val_words: int, max_ops: int,
                 log_capacity: int):
        self.rows, self.width, self.m = num_keys, val_words, max_ops
        self.store = np.zeros((num_keys, val_words), np.int32)
        self.log = np.zeros((log_capacity, 1 + max_ops * (1 + val_words)), np.int32)
        self.tail = 0

    def step(self, recs: np.ndarray) -> np.ndarray:
        """Apply one batch of records in batch order; returns the status
        each transaction must be answered with (COMMITTED or DEFERRED)."""
        b = len(recs)
        n = recs[:, 0]
        body = recs[:, 1:].reshape(b, self.m, 1 + self.width)
        off, vals = body[..., 0], body[..., 1:]
        live = np.arange(self.m)[None, :] < n[:, None]
        idx = np.broadcast_to(np.arange(b)[:, None], off.shape)
        owner = np.full(self.rows, b)
        np.minimum.at(owner, off[live], idx[live])
        commit = np.all((owner[off] == idx) | ~live, axis=1)
        for j in range(self.m):  # op order: the later op to a row wins
            w = commit & live[:, j]
            self.store[off[w, j]] = vals[w, j]
        c = np.flatnonzero(commit)
        self.log[(self.tail + np.arange(len(c))) % len(self.log)] = recs[c]
        self.tail += len(c)
        return np.where(commit, COMMITTED, DEFERRED)
