"""Percent of device busy time in XLA copies and transposes (layout
changes; the table in ``kernels.json``), outside any Pallas call."""
from bench.metrics._ops import COPY, is_pallas, share_of_busy
from bench.xplane import base_name


def read(tr):
    return share_of_busy(tr, lambda i, s: not is_pallas(tr, i)
                         and COPY.match(base_name(i)) is not None)
