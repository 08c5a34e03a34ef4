"""The whole step's share of the chip's peak: the bytes the served
requests need end to end (kernels' needs from ``bench/counts`` plus the
request and answer slots through the rings) over the traced window at peak
HBM bandwidth, per chip. The work is memory-bound, so bandwidth is the
peak that bounds it."""


def read(tr):
    nbytes, ops = tr.extra["work"]["step"]
    peak = tr.extra["peak"]
    least = max(nbytes / peak["hbm_bytes_per_s"], ops / peak["int8_ops_per_s"])
    return 100.0 * least / (tr.window_ns * 1e-9) if least > 0 else None
