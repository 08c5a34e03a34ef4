"""Request bookkeeping shared by the drivers: the seeded request stream by
serial number, and the client-side record of every answer.

A request is known by its serial ``s``: the generator gives its fields as a
pure function of ``(seed, s)``, and the record keeps, per engine step (a
"round"), the serials answered in batch order with the words of their
answers, so the reference can replay the run afterwards. During the
measured window nothing is checked: answers are only kept.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng(np.random.SeedSequence([seed % (1 << 64), *tags]))


class Stream:
    """Fields of request serials. ``gen`` has ``CHUNK`` and ``chunk(i)``;
    ``prepare`` draws chunks ahead (in set-up, so the window draws none);
    ``register`` appends explicit requests (a read-back) at given serials."""

    def __init__(self, gen_module, requests):
        self.chunk_len = gen_module.CHUNK
        self.requests = requests
        self.chunks: dict = {}
        self.extra: list = []  # [(start, fields)]

    def _chunk(self, i):
        if i not in self.chunks:
            self.chunks[i] = self.requests.chunk(i)
        return self.chunks[i]

    def prepare(self, requests: int):
        """Draw every chunk that serials ``[0, requests)`` fall in."""
        for i in range(-(-int(requests) // self.chunk_len)):
            self._chunk(i)

    def register(self, start: int, fields: dict) -> int:
        self.extra.append((start, fields))
        return len(next(iter(fields.values())))

    def fields(self, serials: np.ndarray) -> dict:
        serials = np.asarray(serials, np.int64)
        if not len(serials):
            return {k: v[:0] for k, v in self._chunk(0).items()}
        out = None
        for start, f in self.extra:
            n = len(next(iter(f.values())))
            sel = (serials >= start) & (serials < start + n)
            if sel.any():
                out = self._fill(out, serials, sel, f, serials[sel] - start)
        ci = serials // self.chunk_len
        for i in np.unique(ci):
            sel = ci == i
            if out is not None:
                sel &= ~out["_done"]
            if sel.any():
                out = self._fill(out, serials, sel, self._chunk(int(i)),
                                 serials[sel] % self.chunk_len)
        out.pop("_done")
        return out

    @staticmethod
    def _fill(out, serials, sel, src, idx):
        if out is None:
            out = {k: np.zeros((len(serials),) + v.shape[1:], v.dtype)
                   for k, v in src.items()}
            out["_done"] = np.zeros(len(serials), bool)
        for k, v in src.items():
            out[k][sel] = v[idx]
        out["_done"] |= sel
        return out


@dataclass
class Round:
    phase: str  # warm | window | finish | extra
    serials: np.ndarray  # answered, in the engine's batch order
    answers: np.ndarray  # (n, words kept) int32
    t_done: float
    host_s: np.ndarray = None  # seconds in each of the driver's spans


@dataclass
class Record:
    """Every round of one run, and the first-inject time of every serial
    (``t_first``, indexed by serial; NaN until sent)."""

    rounds: list = field(default_factory=list)
    t_first: np.ndarray = field(default_factory=lambda: np.full(1 << 16, np.nan))
    done_serial: list = field(default_factory=list)
    done_t: list = field(default_factory=list)

    def reserve(self, serials: int):
        """Room for the first-inject times of ``serials`` requests."""
        if serials > len(self.t_first):
            grown = np.full(max(serials, 2 * len(self.t_first)), np.nan)
            grown[:len(self.t_first)] = self.t_first
            self.t_first = grown

    def sent(self, serials: np.ndarray, t: float):
        if len(serials):
            self.reserve(int(serials.max()) + 1)
            first = serials[np.isnan(self.t_first[serials])]
            self.t_first[first] = t

    def answered(self, phase, serials, answers, complete, t):
        """``complete`` marks answers that end their request (a deferred
        transaction is answered, then sent again)."""
        self.rounds.append(Round(phase, serials, answers, t))
        self.done_serial.append(serials[complete])
        self.done_t.append(np.full(int(complete.sum()), t))

    def window_stats(self, t0: float, t1: float):
        """Requests completed in [t0, t1] and their latencies (s)."""
        s = np.concatenate(self.done_serial) if self.done_serial else np.zeros(0, np.int64)
        t = np.concatenate(self.done_t) if self.done_t else np.zeros(0)
        sel = (t >= t0) & (t <= t1)
        return int(sel.sum()), t[sel] - self.t_first[s[sel]]


def now() -> float:
    return time.perf_counter()
