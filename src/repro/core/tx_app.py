"""ORCA-TX as an engine application: transactions through the same
ring-buffer → cpoll → scheduler → APU pipeline as the KVS (§IV-B end to
end).

Request slot layout = the redo-log entry format (count header + (offset,
value) tuples); the response carries [committed | deferred] so the client
retries deferred transactions — the paper's "buffered in the queue in the
order of arrival" behaviour lands on the client side of the credit loop,
which preserves arrival order per connection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import status as stc
from repro.core import transaction as tx

I32 = jnp.int32

RESP_COMMITTED = 1
RESP_DEFERRED = 2


def request_words(cfg: tx.TxConfig) -> int:
    return tx.tx_words(cfg)


def app_step(chain: tx.ReplicaState, payloads, valid, cfg: tx.TxConfig, *,
             kernel_backend="auto"):
    """Engine hook. payloads: (B, >= tx_words); any trailing words past the
    log-entry layout (e.g. the engine's deadline word) are ignored. A zero
    count header = no-op.

    Returns (chain, responses (B, W)) where responses carry the
    commit/deferred status in word 0 — or ``status.MALFORMED`` when
    payload validation fails (op-count overflow/negative, or a live op's
    raw offset outside the store): a malformed transaction is masked out
    of the commit walk entirely, NACKed instead of clipped into scattering
    garbage at whatever row ``parse_tx``'s clamp would pick.
    ``kernel_backend`` dispatches the replica commit walk
    (``auto``/``pallas`` = the fused ``kernels/tx_commit.py`` log-append +
    store-scatter kernel, ``ref`` = the jnp oracle; bit-for-bit identical)
    — the APU default, like ``kvstore.app_step``."""
    body = payloads[:, : tx.tx_words(cfg)]
    with jax.named_scope(tx.PLAN):
        n_raw = body[:, 0]
        raw_ops = body[:, 1:].reshape(
            body.shape[0], cfg.max_ops, 1 + cfg.val_words
        )
        raw_off = raw_ops[..., 0]
        n_clip = jnp.clip(n_raw, 0, cfg.max_ops)
        live_op = jnp.arange(cfg.max_ops)[None, :] < n_clip[:, None]
        bad = valid & (
            (n_raw < 0) | (n_raw > cfg.max_ops)
            | jnp.any(live_op & ((raw_off < 0) | (raw_off >= cfg.num_keys)),
                      axis=1)
        )
        live = valid & ~bad & (n_raw > 0)
    chain, committed, deferred = tx.chain_commit_local(
        chain, body, cfg, live, kernel_backend=kernel_backend
    )
    status = jnp.where(
        committed, RESP_COMMITTED, jnp.where(deferred, RESP_DEFERRED, 0)
    ).astype(I32)
    status = jnp.where(bad, stc.MALFORMED, status)
    resp = jnp.zeros_like(payloads)
    resp = resp.at[:, 0].set(status)
    return chain, resp
