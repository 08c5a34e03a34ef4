"""Bytes the DLRM pooling needs (``kernels.ops.embedding_reduce``: one grid
step per looked-up row, summed into its (sample, table) segment).

Per live lookup: its id and its table row read (``embedding_dim`` values of
``table_bytes``); per (sample, table): its float32 pooled row written. Not
counted: the segment ids, the 16-row tiles the kernel moves to reach one
row, and copies. No arithmetic to speak of: ``ops`` is 0, so the bound is
bandwidth.
"""


def needed(shape: dict, count: dict):
    d = shape["embedding_dim"]
    per_lookup = 4 + d * shape["table_bytes"]
    return per_lookup * count["lookups"] + 4 * d * count["segments"], 0
