"""Share of its roofline the ``cache_probe`` kernel reached in the traced window:
the bytes the served requests need (``bench/counts/cache_probe.py``) at peak HBM
bandwidth, over the kernel's device time (``kernels.json``)."""
from bench.metrics._ops import roofline


def read(tr):
    return roofline(tr, "cache_probe")
