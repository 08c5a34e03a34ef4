"""Closed-loop clients driving ``engine.engine_step`` through its rings.

Each of ``queues`` clients keeps ``window`` requests outstanding, as ORCA's
credit-based rings bound them per connection (§III-A). A round is one
inject of every client's refill, one engine step of at most ``budget``
requests, and one drain of every response the step produced; the clients
then refill what was answered. A deferred answer (a transaction that lost
a row to an earlier one of its batch) is sent again ahead of new requests.
Latency runs from a request's first inject call to the drain that returns
its final answer, on the host clock. Traffic file fields: ``queues``,
``window``, ``budget``, ``warm_rounds``.

The engine forms its batch queue by queue in queue order, each queue's
entries oldest first (``ringbuf.gather_batch`` over the scheduler's
``selected_queues``); answers are recorded in that order, which is the
order the reference replays them in.
"""
from __future__ import annotations

import re

import numpy as np

from bench.requests import Record, now

SPANS = ("round", "make", "inject", "dispatch", "wait", "record")
PARTS = SPANS[1:]  # the spans of a round, in order (``Round.host_s``)
_PALLAS = re.compile(
    r'^\s*%?([\w.\-]+) = .*custom_call_target="tpu_custom_call".*?op_name="([^"]*)"')


def kernel_paths(hlo_text: str) -> dict:
    """Pallas call instruction name -> JAX op path, from a compiled program."""
    out = {}
    for line in hlo_text.splitlines():
        m = _PALLAS.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


class Driver:
    step_module = "step"
    spans = SPANS
    parts = PARTS

    def __init__(self, app, traffic: dict, stream, devices):
        self.app, self.stream = app, stream
        self.q, self.win = int(traffic["queues"]), int(traffic["window"])
        self.budget = int(traffic["budget"])
        self.warm = int(traffic["warm_rounds"])
        self.record = Record()
        self.pending = [np.zeros(0, np.int64) for _ in range(self.q)]
        self.retry = [np.zeros(0, np.int64) for _ in range(self.q)]
        self.next_serial, self.limit = 0, None
        self.chips = 1

    # ----------------------------------------------------------- set-up
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core import engine as eng

        app, q, win = self.app, self.q, self.win
        ecfg = eng.EngineConfig(num_queues=q, capacity=win, req_words=app.words,
                                resp_words=app.words, budget=self.budget,
                                kernel_backend="auto")
        app_fn = eng.bind_app(app.app_step, app.cfg, ecfg)
        qids = jnp.arange(q, dtype=jnp.int32)
        keep = app.keep

        def step(s):
            return eng.engine_step(s, app_fn, ecfg)

        def inject(s, pay, n):  # pay (Q, window, words); n (Q,) rows to send
            return jax.lax.fori_loop(
                0, jnp.max(n), lambda j, s: eng.inject(s, qids, pay[:, j],
                                                       mask=j < n), s)

        def drain(s):
            pay, counts, s = eng.drain_responses(s, win)
            return pay[..., :keep], counts, s

        state = jax.jit(lambda a: eng.make(ecfg, a), donate_argnums=0)(app.build())
        self.step = jax.jit(step, donate_argnums=0).lower(state).compile()
        self.paths = kernel_paths(self.step.as_text())
        mem = self.step.memory_analysis()  # HBM the step program holds
        self.program_bytes = mem and {"arguments": mem.argument_size_in_bytes,
                                      "temporaries": mem.temp_size_in_bytes}
        pay = jax.ShapeDtypeStruct((q, win, app.words), jnp.int32)
        n = jax.ShapeDtypeStruct((q,), jnp.int32)
        self.inject = jax.jit(inject, donate_argnums=0).lower(state, pay, n).compile()
        self.drain = jax.jit(drain, donate_argnums=0).lower(state).compile()
        self.state = state
        for _ in range(self.warm):
            self.round("warm")

    # ------------------------------------------------------------ rounds
    def _refill(self):
        """Serials each client sends now: its retries, then new requests up
        to its window (none past ``limit``)."""
        send = []
        for qi in range(self.q):
            room = self.win - len(self.pending[qi]) - len(self.retry[qi])
            if self.limit is not None:
                room = min(room, self.limit - self.next_serial)
            new = np.arange(self.next_serial, self.next_serial + max(room, 0))
            self.next_serial += len(new)
            send.append(np.concatenate([self.retry[qi], new]))
            self.retry[qi] = np.zeros(0, np.int64)
        return send

    def round(self, phase: str):
        import jax
        import jax.numpy as jnp

        span = jax.profiler.TraceAnnotation
        app = self.app
        marks = [now()]  # host time at the end of each span, for the record
        with span("round"):
            with span("make"):
                send = self._refill()
                flat = np.concatenate(send)
                rows = app.encode(self.stream.fields(flat), flat)
                pay = np.zeros((self.q, self.win, app.words), np.int32)
                n = np.array([len(s) for s in send], np.int32)
                at = 0
                for qi, s in enumerate(send):
                    pay[qi, :len(s)] = rows[at:at + len(s)]
                    at += len(s)
            marks.append(now())
            with span("inject"):
                t = now()
                self.state = self.inject(self.state, jnp.asarray(pay), jnp.asarray(n))
                self.record.sent(flat, t)
            marks.append(now())
            with span("dispatch"):
                self.state, _ = self.step(self.state)
                resp, counts, self.state = self.drain(self.state)
            marks.append(now())
            with span("wait"):
                resp, counts = np.asarray(resp), np.asarray(counts)
                t = now()
            marks.append(t)
            with span("record"):
                for qi in range(self.q):
                    self.pending[qi] = np.concatenate([self.pending[qi], send[qi]])
                got = [self.pending[qi][:counts[qi]] for qi in range(self.q)]
                answers = np.concatenate([resp[qi, :counts[qi]] for qi in range(self.q)])
                done = app.complete(answers)
                at = 0
                for qi in range(self.q):
                    c = int(counts[qi])
                    self.retry[qi] = got[qi][~done[at:at + c]]
                    self.pending[qi] = self.pending[qi][c:]
                    at += c
                self.record.answered(phase, np.concatenate(got), answers, done, t)
            marks.append(now())
        self.record.rounds[-1].host_s = np.diff(marks)

    def outstanding(self) -> int:
        return sum(len(p) + len(r) for p, r in zip(self.pending, self.retry))

    def counters(self) -> dict:
        return self.app.counters(self.state.app)

    # ------------------------------------------------------ after the window
    def finish(self, seconds_allowed: float) -> int:
        """Answer every outstanding request, then run the app's read-back.
        Returns the requests still unanswered when the allowance ran out."""
        t_end = now() + seconds_allowed
        self.limit = self.next_serial
        while self.outstanding() and now() < t_end:
            self.round("finish")
        served = np.concatenate([r.serials for r in self.record.rounds])
        extra = self.app.extra(self.stream.fields(served))
        if extra is not None and self.outstanding() == 0:
            self.limit = self.next_serial + self.stream.register(self.next_serial, extra)
            while (self.next_serial < self.limit or self.outstanding()) and now() < t_end:
                self.round("extra")
        return self.outstanding() + max(0, (self.limit or 0) - self.next_serial)

    def final(self) -> dict:
        return self.app.final(self.state.app)

    def release(self):
        del self.state, self.step, self.inject, self.drain
