"""Host time per round outside waiting on the device: the benchmark's own
spans that make payloads, inject, dispatch and record answers."""

HOST = ("make", "inject", "dispatch", "record")


def read(tr):
    busy = sum(e - s for n, s, e in tr.spans if n in HOST)
    rounds = tr.extra["rounds"]
    return 1e-6 * busy / rounds if rounds else None
