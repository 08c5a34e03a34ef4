"""The YCSB request generator against YCSB's own definitions, worked here
with Python's integers: ``Utils.fnvhash64``, the key names, and the
scrambled zipfian's draws."""
from __future__ import annotations

import numpy as np

from _util import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench.generators import orca_tx, ycsb


def fnvhash64_java(val: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        h = (h * 1099511628211) & (2 ** 64 - 1)
        val >>= 8
    h -= (h >> 63) << 64  # as a Java long
    return abs(h)


def test_fnvhash64_matches_java():
    vals = [0, 1, 2, 255, 256, 123456789, 2 ** 20 - 1, 10 ** 10]
    assert ycsb.fnvhash64(np.array(vals)).tolist() == [fnvhash64_java(v) for v in vals]


def test_key_names_are_ycsb_keys():
    words = ycsb.key_names(1000, 24)
    assert words.shape == (1000, 6) and words.dtype == np.int32
    for r in (0, 1, 7, 999):
        want = f"user{fnvhash64_java(r)}".encode()
        assert words[r].tobytes() == want.ljust(24, b"\0")
    assert len({w.tobytes() for w in words}) == 1000


def test_zipfian_ranks_follow_ycsb():
    # rank 0 takes 1/zetan of the draws, rank 1 the next 0.5^0.99/zetan
    u = np.array([0.0, 0.99 / ycsb.ZETAN, 1.01 / ycsb.ZETAN, 0.5, 0.999999])
    r = ycsb.zipfian_ranks(u)
    assert r[:3].tolist() == [0, 0, 1]
    items, theta = ycsb.ITEM_COUNT + 1, ycsb.THETA
    eta = (1 - (2 / items) ** (1 - theta)) / (1 - (1 + 0.5 ** theta) / ycsb.ZETAN)
    for x, got in zip(u[3:], r[3:]):
        assert got == int(items * (eta * x - eta + 1) ** (1 / (1 - theta)))


def test_scrambled_zipfian_spread():
    rng = np.random.default_rng(1)
    n, records = 200_000, 1 << 20
    keys = ycsb.scrambled_zipfian(rng, n, records)
    assert keys.min() >= 0 and keys.max() < records
    top = np.bincount(keys, minlength=records)
    # the hottest record is rank 0's: fnvhash64(0) mod (records + 1)
    assert top.argmax() == fnvhash64_java(0) % (records + 1)
    assert abs(top.max() / n - 1 / ycsb.ZETAN) < 0.003


def test_requests_are_a_function_of_the_seed():
    traffic = {"read_share": 0.95, "request_distribution": "zipfian"}
    a = ycsb.Requests(traffic, {"records": 4096}, 2 ** 31 + 5).chunk(3)
    b = ycsb.Requests(traffic, {"records": 4096}, 2 ** 31 + 5).chunk(3)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert abs((a["op"] == ycsb.OP_GET).mean() - 0.95) < 0.01


def test_orca_tx_writes():
    g = orca_tx.Requests({"reads": 4, "writes": 2}, {"max_ops": 8, "num_keys": 64}, 9)
    c = g.chunk(0)
    assert (c["n_ops"] == 2).all()
    assert (c["offsets"][:, 2:] == 0).all() and c["offsets"].max() < 64
