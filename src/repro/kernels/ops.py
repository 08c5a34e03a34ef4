"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to auto: Pallas TPU kernels execute natively on a
TPU backend and in interpret mode (kernel body evaluated with jnp
semantics) on the CPU backend — which is how CPU test runs validate them.
Any other backend is refused rather than silently interpreted. The
pure-jnp oracles live in ``ref.py``; ``use_ref=True`` routes there (the
dry-run uses the reference path so its HLO is XLA-analysable end to end).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import embedding_reduce as _er
from repro.kernels import flash_attention as _fa
from repro.kernels import hash_probe as _hp
from repro.kernels import paged_attention as _pa
from repro.kernels import tx_commit as _tc


def _auto_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run natively on tpu and interpreted on cpu; "
            f"backend {backend!r} has neither path"
        )
    return backend == "cpu"


def resolve_backend(backend=None):
    """Map the engine's ``kernel_backend`` knob to ``(use_ref, interpret)``.

    ``auto`` (and None) and ``pallas`` both take the Pallas path — native on
    TPU, interpret mode on CPU (which is how CPU test runs validate the
    kernels); ``ref`` routes to the pure-jnp oracles in :mod:`ref`.
    """
    if backend in (None, "auto", "pallas"):
        return False, _auto_interpret()
    if backend == "ref":
        return True, False
    raise ValueError(
        f"unknown kernel_backend {backend!r} (expected auto | pallas | ref)"
    )


def embedding_reduce(table, idx, seg_ids, num_segments: int, *,
                     use_ref: bool = False, interpret=None):
    if use_ref:
        return _ref.embedding_reduce(table, idx, seg_ids, num_segments)
    it = _auto_interpret() if interpret is None else interpret
    out = _er.embedding_reduce(table, idx, seg_ids, num_segments, interpret=it)
    # segments with no entries are never visited by the grid: zero them
    counts = jax.ops.segment_sum(jnp.ones_like(seg_ids), seg_ids, num_segments)
    return jnp.where(counts[:, None] > 0, out, 0.0)


def hash_probe(bucket_keys, bucket_ptr, keys, h1, h2, *,
               use_ref: bool = False, interpret=None):
    """Two-bucket existence probe. Returns (found (B,), ptr (B,)).

    The first two memory accesses of both the GET walk and the PUT plan
    (``kvstore.plan_put``'s existence check) — one scalar-prefetch pass."""
    if use_ref:
        return _ref.hash_probe(bucket_keys, bucket_ptr, keys, h1, h2)
    it = _auto_interpret() if interpret is None else interpret
    return _hp.probe(bucket_keys, bucket_ptr, keys, h1, h2, interpret=it)


def cache_probe(cache_keys, cache_vals, cache_meta, keys, cset, *,
                use_ref: bool = False, interpret=None):
    """Hot-set cache lookup — the VMEM set probe ``kvstore.get`` runs
    before the bucket walk (and ``put`` before its write-through commit).
    Returns (hit (B,), way (B,), vals (B, VW)); both backends agree
    bit-for-bit (integer data, single-match sets)."""
    if use_ref:
        return _ref.cache_probe(cache_keys, cache_vals, cache_meta, keys,
                                cset)
    it = _auto_interpret() if interpret is None else interpret
    return _hp.cache_probe(cache_keys, cache_vals, cache_meta, keys, cset,
                           interpret=it)


def hash_get(bucket_keys, bucket_ptr, pool, keys, h1, h2, *,
             use_ref: bool = False, interpret=None):
    if use_ref:
        return _ref.hash_get(bucket_keys, bucket_ptr, pool, keys, h1, h2)
    it = _auto_interpret() if interpret is None else interpret
    return _hp.get(bucket_keys, bucket_ptr, pool, keys, h1, h2, interpret=it)


def hash_put(bucket_keys, bucket_ptr, pool, keys, vals, tb, tw, bptr_val, wp,
             bucket_order=None, row_order=None, *, use_ref: bool = False,
             interpret=None):
    """Commit phase of a planned batched PUT (``kvstore.plan_put`` output).

    State arrays are in the sentinel-resident ``KVState`` layout
    ((NB+1)/(NP+1) rows) and come back the same shape — neither backend
    materializes a padded copy. The Pallas path passes each state array to
    its scatter once and updates it in place; XLA still relayouts the
    narrow bucket arrays around the call from the runtime's compact layout
    until the state is lane-dense. ``bucket_order``/``row_order`` are the
    plan's precomputed target sort orders (Pallas staging only; the
    scatter oracle is order-independent). Returns the updated
    (bucket_keys, bucket_ptr, pool) arrays."""
    if use_ref:
        return _ref.hash_put(
            bucket_keys, bucket_ptr, pool, keys, vals, tb, tw, bptr_val, wp
        )
    it = _auto_interpret() if interpret is None else interpret
    return _hp.insert(
        bucket_keys, bucket_ptr, pool, keys, vals, tb, tw, bptr_val, wp,
        bucket_order, row_order, interpret=it,
    )


def tx_commit(log, store, batch, values, slot, rows, *,
              use_ref: bool = False, interpret=None):
    """Fused ORCA-TX replica commit: write-ahead log append + store scatter
    of a planned transaction batch (``core.transaction.plan_commit``).

    ``log``/``store`` are in the sentinel-resident ``ReplicaState`` layout
    ((LC+1)/(NK+1) rows) and come back the same shape. The Pallas path
    passes each to its scatter once and updates it in place; XLA still
    relayouts both around the call from the runtime's compact layout until
    the state is lane-dense. Returns the updated (log, store). Both
    backends zero sentinel-targeted payloads (slot == LC / rows == NK) and
    agree bit-for-bit."""
    if use_ref:
        return _ref.tx_commit(log, store, batch, values, slot, rows)
    it = _auto_interpret() if interpret is None else interpret
    return _tc.commit(log, store, batch, values, slot, rows, interpret=it)


def tx_commit_chain(log, store, batch, values, slot, rows, *,
                    use_ref: bool = False, interpret=None):
    """Whole-chain fused ORCA-TX commit: every replica of a local chain in
    one batched dual scatter (``transaction.chain_commit_apply``).

    log: (R, LC+1, TW); store: (R, NK+1, VW) — sentinel-resident chain
    layout, same shapes out, each passed once to its scatter and updated
    in place on the Pallas path; slot: (R, B) per-replica log slots; rows:
    (B*M,) shared store rows, or (R, B*M) per-replica rows (chain
    shortening retargets a dead replica's ops at its sentinel). Both
    backends agree bit-for-bit with a per-replica :func:`tx_commit` loop."""
    if use_ref:
        return _ref.tx_commit_chain(log, store, batch, values, slot, rows)
    it = _auto_interpret() if interpret is None else interpret
    return _tc.commit_chain(
        log, store, batch, values, slot, rows, interpret=it
    )


def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    use_ref: bool = False, interpret=None):
    if use_ref:
        return _ref.paged_attention(q, k_pages, v_pages, page_table, lengths)
    it = _auto_interpret() if interpret is None else interpret
    return _pa.paged_attention(
        q, k_pages, v_pages, page_table, lengths, interpret=it
    )


def paged_attention_stats(q, k_pages, v_pages, page_table, lengths, *,
                          use_ref: bool = False, interpret=None):
    """Online-softmax stats (acc, m, l) over the first ``lengths`` pool
    tokens — the read-only decode path LSE-merges the current token's
    fresh k/v into these instead of writing the pool inside the scan."""
    if use_ref:
        return _ref.paged_attention_stats(
            q, k_pages, v_pages, page_table, lengths
        )
    it = _auto_interpret() if interpret is None else interpret
    return _pa.paged_attention_stats(
        q, k_pages, v_pages, page_table, lengths, interpret=it
    )


def flash_attention(q, k, v, *, window: int = 0, block_q: int = 128,
                    block_k: int = 128, use_ref: bool = False, interpret=None):
    if use_ref:
        return _ref.flash_attention(q, k, v, window=window)
    it = _auto_interpret() if interpret is None else interpret
    return _fa.flash_attention(
        q, k, v, window=window, block_q=block_q, block_k=block_k, interpret=it
    )
