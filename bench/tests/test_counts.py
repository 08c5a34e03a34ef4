"""``bench/counts`` against byte counts worked by hand for one call of each
kernel at the configurations' widths."""
from __future__ import annotations

import json

from _util import ROOT
from bench.counts import cache_probe, hash_get, hash_put, tx_commit

KVS = json.loads((ROOT / "bench/configs/kvs-ycsb-1kb.json").read_text())
TX = json.loads((ROOT / "bench/configs/tx-chain4-64b.json").read_text())


def test_hash_get_one_walk():
    # key 24 B; two buckets of 8 ways: keys 192 B + pointers 32 B each = 448 B;
    # (found, pointer) written and read 16 B; value row read and written 2048 B
    assert hash_get.needed(KVS, {"walks": 1}) == (24 + 448 + 16 + 2048, 0)
    assert hash_get.needed(KVS, {"walks": 3})[0] == 3 * 2536


def test_hash_put_one_update_and_one_insert():
    # key 24 + buckets 448 + (found, pointer) 8 + value read and written 2048
    assert hash_put.needed(KVS, {"puts": 1, "inserts": 0}) == (2528, 0)
    # an insert also writes one way: key 24 B and pointer 4 B
    assert hash_put.needed(KVS, {"puts": 1, "inserts": 1}) == (2556, 0)


def test_cache_probe_lookups():
    # key 24 + the set's 4 keys 96 + 4 reference words 16 + (hit, way) 8 = 144
    assert cache_probe.needed(KVS, {"gets": 1, "puts": 0, "hits": 0}) == (144, 0)
    # a GET hit also reads the cached row and writes it out: 2048 B
    assert cache_probe.needed(KVS, {"gets": 1, "puts": 1, "hits": 1}) == (2 * 144 + 2048, 0)


def test_tx_commit_one_transaction_of_three_rows():
    # record of 1 + 3 x 17 words read and written 416 B; 3 rows of 64 B
    # written 192 B; per replica
    one = tx_commit.needed(TX, {"commits": 1, "ops": 3, "rows": 3, "replicas": 1})
    assert one == (416 + 192, 0)
    assert tx_commit.needed(TX, {"commits": 1, "ops": 3, "rows": 3,
                                 "replicas": 4})[0] == 4 * 608
