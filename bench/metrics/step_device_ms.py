"""Device time of one step program run (the engine step), averaged over
its runs and the chips."""


def read(tr):
    runs = [e - s for c in tr.chips for s, e in tr.runs(c, tr.extra["step_module"])]
    return 1e-6 * sum(runs) / len(runs) if runs else None
