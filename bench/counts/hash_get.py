"""Bytes one GET walk needs (``kernels.ops.hash_get``: probe, then fetch).

Per request that walks: its key, both candidate buckets (keys of every way
and the way pointers: the MICA walk the store implements reads the primary
and the overflow bucket), the (found, pointer) pair passed from the probe
to the fetch (written and read), and its value row read from the pool and
written to the answer. Not counted: the 8-row tiles the kernels move to
reach one row, requests the walk visits though the cache already answered
them, and layout copies around the call. No arithmetic to speak of:
``ops`` is 0, so the bound is bandwidth.
"""


def needed(shape: dict, count: dict):
    kw, w, vw = shape["key_words"], shape["ways"], shape["val_words"]
    per = 4 * (kw + 2 * (w * kw + w) + 2 * 2 + 2 * vw)
    return per * count["walks"], 0
