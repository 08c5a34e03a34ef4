"""Bytes one replica commit needs (``kernels.ops.tx_commit`` and
``tx_commit_chain``: redo-log append, then store scatter).

Per replica and committed transaction: its log record, as long as its live
ops make it (a count word and an (offset, value) tuple per op), read from
the batch and written to its ring slot; per row it writes (ops shadowed by
a later op of the same transaction excluded), the value written to the
store. Not counted: the unused op slots of the fixed-width record, the
8-row tiles the scatters move, the deferred transactions and dead ops aimed
at the sentinel row, and copies.
"""


def needed(shape: dict, count: dict):
    vw = shape["val_words"]
    record = count["commits"] + count["ops"] * (1 + vw)
    per_replica = 4 * (2 * record + vw * count["rows"])
    return per_replica * count["replicas"], 0
