"""Share of its roofline the ``embedding_reduce`` kernel reached in the
traced window: the bytes the served lookups need
(``bench/counts/embedding_reduce.py``) at peak HBM bandwidth, over the
kernel's device time. The kernel is the Pallas call whose instruction is
named ``embedding_reduce``."""
from bench.metrics._ops import is_pallas, mean_self_ns
from bench.xplane import base_name


def read(tr):
    work = tr.extra["work"].get("embedding_reduce")
    t = mean_self_ns(tr, lambda i, s: is_pallas(tr, i)
                     and base_name(i) == "embedding_reduce") * 1e-9
    if not work or t <= 0 or work[0] <= 0:
        return None
    return 100.0 * work[0] / tr.extra["peak"]["hbm_bytes_per_s"] / t
