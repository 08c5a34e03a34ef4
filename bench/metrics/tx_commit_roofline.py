"""Share of its roofline the ``tx_commit`` kernel reached in the traced window:
the bytes the served requests need (``bench/counts/tx_commit.py``) at peak HBM
bandwidth, over the kernel's device time (``kernels.json``)."""
from bench.metrics._ops import roofline


def read(tr):
    return roofline(tr, "tx_commit")
