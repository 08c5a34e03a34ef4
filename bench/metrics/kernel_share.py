"""Percent of device busy time inside Pallas calls (every
``tpu_custom_call`` of the step program)."""
from bench.metrics._ops import is_pallas, share_of_busy


def read(tr):
    return share_of_busy(tr, lambda i, s: is_pallas(tr, i))
