"""Share of its roofline the ``hash_get`` kernel reached in the traced window:
the bytes the served requests need (``bench/counts/hash_get.py``) at peak HBM
bandwidth, over the kernel's device time (``kernels.json``)."""
from bench.metrics._ops import roofline


def read(tr):
    return roofline(tr, "hash_get")
