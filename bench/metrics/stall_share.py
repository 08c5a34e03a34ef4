"""Percent of the measured window lost to stalled rounds: a round that
took more than twice the window's median round loses its time beyond the
median. Read from the host clock over every round of the window (not only
its traced seconds); in the runs measured so far the time is lost in the
``wait`` span, the host waiting on the device's answers."""

import numpy as np


def read(tr):
    rounds = np.asarray(tr.extra["round_s"])
    if not len(rounds):
        return None
    med = np.median(rounds)
    lost = rounds[rounds > 2 * med] - med
    return 100.0 * lost.sum() / rounds.sum()
