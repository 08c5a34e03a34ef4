"""YCSB core-workload request stream (Cooper et al., SoCC 2010), as YCSB's
``CoreWorkload`` draws it.

Traffic file fields read here:

* ``read_share``: fraction of requests that are reads; the rest are
  updates of loaded records, as in YCSB's workloads A (0.5) and B (0.95).
* ``request_distribution``: ``"zipfian"``, YCSB's default for A and B:
  its ``ScrambledZipfianGenerator``, a Zipfian draw with constant 0.99 over
  10^10 items whose rank is hashed (FNV-1a, 64 bit) modulo the key space,
  so the popular records are scattered and the tail is nearly flat. The
  key space is ``records + 1`` wide and a draw of the last one is drawn
  again, as ``CoreWorkload.nextKeynum`` does when no inserts are expected.

Records are named as YCSB names them under its default ``insertorder=hashed``:
record ``r`` has the key ``"user" + str(fnvhash64(r))`` (``key_names``).

Request ``s`` of a run is a pure function of ``(seed, s)``: requests are
drawn in chunks of ``CHUNK``, each from its own seeded stream, so the
sequence does not depend on how fast the system under test consumes it.
"""
from __future__ import annotations

import numpy as np

from bench.requests import rng_for

CHUNK = 1 << 16
OP_GET, OP_PUT = 1, 2

# ScrambledZipfianGenerator: ITEM_COUNT, ZETAN (zeta(ITEM_COUNT + 1, 0.99),
# precomputed by YCSB) and USED_ZIPFIAN_CONSTANT
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
THETA = 0.99
FNV_OFFSET_64, FNV_PRIME_64 = 0xCBF29CE484222325, 1099511628211


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of non-negative longs: FNV-1a over the 8
    low-order bytes, 64-bit wrap-around, then ``Math.abs``."""
    v = np.asarray(v, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian_ranks(u: np.ndarray) -> np.ndarray:
    """``ZipfianGenerator.nextLong`` over ``ITEM_COUNT + 1`` items for
    uniform draws ``u`` in [0, 1) (Gray et al., SIGMOD 1994)."""
    items = ITEM_COUNT + 1
    zeta2 = 1.0 + 0.5 ** THETA
    alpha = 1.0 / (1.0 - THETA)
    eta = (1.0 - (2.0 / items) ** (1.0 - THETA)) / (1.0 - zeta2 / ZETAN)
    uz = u * ZETAN
    rank = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** THETA, 1, rank)
    return np.where(uz < 1.0, 0, rank)


def scrambled_zipfian(rng: np.random.Generator, n: int, records: int) -> np.ndarray:
    """``n`` record numbers in ``[0, records)`` as YCSB's A and B draw them."""
    out = np.zeros(0, np.int64)
    while len(out) < n:
        keynum = fnvhash64(zipfian_ranks(rng.random(n))) % (records + 1)
        out = np.concatenate([out, keynum[keynum < records]])
    return out[:n]


def key_names(records: int, width: int) -> np.ndarray:
    """YCSB's key of each record, ``"user" + decimal(fnvhash64(r))``, as
    ``width`` bytes zero-padded and read as little-endian int32 words:
    ``(records, width // 4)``."""
    h = fnvhash64(np.arange(records))
    digits = np.zeros((records, 19), np.uint8)  # a long has at most 19
    length = np.ones(records, np.int64)
    rest = h.copy()
    for i in range(19):
        digits[:, 18 - i] = ord("0") + rest % 10
        rest //= 10
        length = np.where(h >= 10 ** i, i + 1, length)
    if 4 + length.max() > width:
        raise ValueError(f"YCSB keys are up to {4 + length.max()} bytes; "
                         f"the store holds {width}")
    out = np.zeros((records, width), np.uint8)
    out[:, :4] = np.frombuffer(b"user", np.uint8)
    col = np.arange(19)[None, :]
    src = 19 - length[:, None] + col  # the number's digits, left-aligned
    take = col < length[:, None]
    out[:, 4:23] = np.where(take, np.take_along_axis(digits, np.minimum(src, 18), 1), 0)
    return out.view("<i4")


class Requests:
    """The stream of one run: ``chunk(i)`` gives requests
    ``[i * CHUNK, (i + 1) * CHUNK)`` as ``{"op", "key"}`` int arrays,
    where ``key`` is a record number in ``[0, records)``."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.seed = seed
        self.read_share = float(traffic["read_share"])
        self.records = int(config["records"])
        if traffic["request_distribution"] != "zipfian":
            raise ValueError(f"unknown request distribution "
                             f"{traffic['request_distribution']!r}")

    def chunk(self, i: int) -> dict:
        rng = rng_for(self.seed, 1, i)
        op = np.where(rng.random(CHUNK) < self.read_share, OP_GET, OP_PUT)
        key = scrambled_zipfian(rng, CHUNK, self.records)
        return {"op": op.astype(np.int32), "key": key}
