"""Percent of the traced window in which no program ran on the device
(averaged over the chips): 1 - union of program runs / window."""


def read(tr):
    return 100.0 * (1.0 - tr.mean_busy_ns() / tr.window_ns)
