"""Bytes one hot-set cache lookup needs (``kernels.ops.cache_probe``).

Per lookup: the key, the keys and reference bits of every way of its set,
and the (hit, way) pair it yields. A GET that hits also reads the cached
value row and writes it to the answer. The PUT write-through lookup needs
no value. Not counted: the value rows of every way that the kernel reads
to pick one, rows the grid visits that are not live lookups, and copies.
"""


def needed(shape: dict, count: dict):
    kw, cw, vw = shape["key_words"], shape["cache_ways"], shape["val_words"]
    per = 4 * (kw + cw * kw + cw + 2)
    lookups = count["gets"] + count["puts"]
    return per * lookups + 4 * 2 * vw * count["hits"], 0
