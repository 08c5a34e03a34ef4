"""Bytes one PUT needs (the existence probe ``kernels.ops.hash_probe`` and
the commit ``kernels.ops.hash_put``: bucket commit and value-row scatter).

Per PUT: its key and both candidate buckets (keys and pointers) for the
existence check, the (found, pointer) pair it yields, and its value row
read from the request and written to the pool. An insert also writes one
way of one bucket: a key and a pointer. Not counted: the 8-row tiles, the
rows the grid visits for GETs and sentinel targets, and layout copies.
"""


def needed(shape: dict, count: dict):
    kw, w, vw = shape["key_words"], shape["ways"], shape["val_words"]
    per_put = 4 * (kw + 2 * (w * kw + w) + 2 + 2 * vw)
    per_insert = 4 * (kw + 1)
    return per_put * count["puts"] + per_insert * count["inserts"], 0
