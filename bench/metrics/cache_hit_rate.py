"""Percent of GETs the KVS hot-set cache answered over the traced window,
from the engine's counters (``cache_hits / (cache_hits + cache_misses)``)."""


def read(tr):
    hits, lookups = tr.extra.get("hit_rate", (0, 0))
    return 100.0 * hits / lookups if lookups else None
