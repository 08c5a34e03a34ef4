"""Faults planted under the timed path, to show that ``correct`` catches
them. Each replaces entry points of the system under test for the rest of
the process; ``plant`` returns the function that takes it out again.

* ``unchanged_state``: the app step answers, but returns its state as it
  was before the step.
* ``half_batch``: only the first half of each batch is served; the rest
  is answered as if empty.
* ``altered_answer``: one answer changed where it is produced: a word of
  the first answer of every app step.
* ``lost_write`` (the KVS control, breaking "every acknowledged write is
  read back"): PUTs are acknowledged, but the value pool is returned as it
  was.
* ``replica_skipped`` (the TX control, breaking "a committed transaction
  is applied on every replica"): the last replica keeps its store, log and
  counters from before the step.
"""
from __future__ import annotations

FAULTS = ("unchanged_state", "half_batch", "altered_answer", "lost_write",
          "replica_skipped")


def _app_step(real, fault):
    import jax.numpy as jnp

    def app_step(state, payloads, valid, cfg, **kw):
        if fault == "half_batch":
            keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
            return real(state, payloads, valid & keep, cfg, **kw)
        new, resp = real(state, payloads, valid, cfg, **kw)
        kvs = hasattr(new, "pool")
        if fault == "unchanged_state":
            return state, resp
        if fault == "altered_answer":  # a KVS value word, a TX status word
            return new, resp.at[0, 1 if kvs else 0].add(1)
        if fault == "lost_write" and kvs:
            return new._replace(pool=state.pool), resp
        if fault == "replica_skipped" and not kvs:
            keep_last = lambda old, cur: cur.at[-1].set(old[-1])  # noqa: E731
            return new._replace(
                store=keep_last(state.store, new.store),
                log=keep_last(state.log, new.log),
                log_tail=keep_last(state.log_tail, new.log_tail),
                committed=keep_last(state.committed, new.committed)), resp
        return new, resp
    return app_step


def plant(fault: str):
    """Install ``fault``; returns a function that removes it."""
    from repro.core import kvstore, tx_app

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    swaps = [(mod, "app_step", _app_step(mod.app_step, fault))
             for mod in (kvstore, tx_app)]  # (module, attribute, replacement)
    undo = [(mod, name, getattr(mod, name)) for mod, name, _ in swaps]
    for mod, name, new in swaps:
        setattr(mod, name, new)

    def remove():
        for mod, name, old in reversed(undo):
            setattr(mod, name, old)
    return remove
