"""Pallas kernels: ORCA-TX commit — redo-log append + store scatter
(§IV-B, the near-data transaction walk).

The jnp half of a transaction batch (parse, first-claimant concurrency
control, intra-tx write dedupe, log-slot ranking) runs ONCE in
``core.transaction.plan_commit``; this module is the memory half every
replica executes: append each proceeding transaction's log entry to its
ring slot and scatter its planned store writes, as two aliased in-place
row scatters (``rows.scatter``, the ``hash_probe.insert`` scatter style)
over the whole chain at once.

The plan guarantees live targets are unique — concurrency control keeps
proceeding transactions' write sets disjoint and the intra-tx dedupe keeps
one writer per (tx, offset). Rows move as aligned tiles, so each scatter
visits its targets in sorted order (one batch-sized argsort per replica)
and stages every tile once. Dead entries (deferred transactions, dead
ops, intra-tx shadowed writes) target the **resident** zero sentinel pad
row that ``ReplicaState`` permanently carries past the live extent
(``slot == LC`` / ``rows == NK``) — the same convention as the page
pool's zero sentinel page (``serving.kv_cache``) and the KVS bucket/pool
pad rows (``kernels.hash_probe``) — with their payloads zeroed, so
nothing is concatenated onto or stripped off the O(state) log/store per
replica commit.

Each scatter passes its log or store to ``pallas_call`` once, aliased to
the output, and updates it in place. The log and store are narrower than
128 lanes, so XLA still relayouts each of them from the runtime's compact
layout into the row-major one the call takes, and back, around every
commit until the state is stored lane-dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import rows as _rows


def _by_row(dst, vals, idx, interpret):
    """Sorted-target row scatter per replica: dst (R, N, W), vals (R, B, W),
    idx (R, B). The plan keeps live targets unique and sentinel payloads
    are zero, so the order among equal targets never matters."""
    order = jnp.argsort(idx, axis=1, stable=True)
    return _rows.scatter(
        dst, jnp.take_along_axis(vals, order[..., None], axis=1),
        jnp.take_along_axis(idx, order, axis=1), interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit(log, store, batch, values, slot, rows, *, interpret: bool = True):
    """Planned-transaction commit: log append + store scatter.

    log: (LC + 1, TW); store: (NK + 1, VW) — the sentinel-resident
    ``ReplicaState`` layout, last row = the zero sentinel; batch: (B, TW)
    raw log records; values: (B, M, VW) parsed op values; slot: (B,) int32
    absolute log slot (LC = the sentinel); rows: (B*M,) int32 store row
    per op (NK = the sentinel). Sentinel-targeted payloads are zeroed so
    dead duplicates write identical zeros (deterministic, sentinel stays
    zero). Returns the updated (log, store), same shapes in as out — each
    is passed to its scatter once and updated in place (XLA still
    relayouts both around the call until the state is lane-dense)."""
    log_o, store_o = commit_chain(
        log[None], store[None], batch, values, slot[None], rows,
        interpret=interpret,
    )
    return log_o[0], store_o[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit_chain(log, store, batch, values, slot, rows, *,
                 interpret: bool = True):
    """Whole-chain commit: one log scatter and one store scatter covering
    every replica of a local chain (grid (R, entries)) instead of a scan
    of per-replica calls — the scan's xs/ys staging moved each replica's
    whole log+store per round, which re-introduced the O(state) copies the
    resident sentinel layout exists to kill.

    log: (R, LC + 1, TW); store: (R, NK + 1, VW) — the sentinel-resident
    chain layout; batch: (B, TW) and values: (B, M, VW), shared by every
    replica; slot: (R, B) int32 absolute log slot per replica (LC = the
    sentinel; replicas advance in lockstep but per-replica tails are
    honoured); rows: (B*M,) int32 store row per op (NK = the sentinel)
    shared by every replica, or (R, B*M) per-replica rows — chain
    shortening (``transaction.chain_commit_apply``) points every op of a
    dead replica at its own sentinel row while live replicas still land.
    Returns the updated (log, store), same shapes, each passed to its
    scatter once and updated in place."""
    r, lcp, tw = log.shape
    _, nkp, vw = store.shape
    lc, nk = lcp - 1, nkp - 1
    b, m = values.shape[0], values.shape[1]
    if rows.ndim == 1:
        rows = jnp.broadcast_to(rows[None], (r, b * m))
    # per-replica zeroed payloads (batch-sized, never state-sized)
    batch_r = jnp.where(
        (slot >= lc)[..., None], 0,
        jnp.broadcast_to(batch[None], (r, b, tw)),
    )
    values_r = jnp.where(
        (rows >= nk)[..., None], 0,
        jnp.broadcast_to(values.reshape(1, b * m, vw), (r, b * m, vw)),
    )
    return (_by_row(log, batch_r, slot, interpret),
            _by_row(store, values_r, rows, interpret))
