"""ORCA-TX (§IV-B): chain-replicated multi-op transactions with
accelerator-side concurrency control.

HyperLoop (the paper's baseline) replicates each key-value *operation* as its
own group-RDMA message down the chain, so a (r, w)-op transaction costs
``(r + w)`` chain traversals. ORCA packs the whole transaction into ONE log
entry — ``[n_ops | (offset, value) * max_ops]`` with the count in the first
word, exactly the §IV-B log format — and the accelerator executes the
transaction near-data, so the chain is traversed once per transaction.

Concurrency control (paper: "any single key-value pair can only be accessed
by one outstanding transaction; the others are buffered in order"): within a
batch, a transaction proceeds iff it is the lowest-indexed claimant of every
offset it writes; the rest are deferred back to the client queue (retry).

Execution follows the plan/commit split of ``kvstore.plan_put``:
:func:`plan_commit` runs the ALU half ONCE per batch (parse, concurrency
control, intra-tx write dedupe, log-slot ranking) and emits a flat
:class:`TxCommitPlan`; each replica then only runs :func:`replica_commit`,
which dispatches the memory half — the write-ahead log append + store
scatter — through ``kernels.ops.tx_commit`` (the fused Pallas kernel in
``kernels/tx_commit.py``, or its jnp oracle, per the ``kernel_backend``
knob; both agree bit-for-bit).

Two executions with identical semantics:
* :func:`chain_commit_local` — the replica chain as a leading array axis,
  committed with ONE batched dual scatter over the replica axis
  (:func:`chain_commit_apply`; single-device tests/benchmarks).
* :func:`chain_commit_spmd` — replicas sharded over a mesh axis; the log
  batch travels by ``lax.ppermute`` (one collective hop per replica) and the
  ACK back-propagates on the same ring, as in Fig. 6; each rank runs
  :func:`replica_commit` on its resident shard.

State arrays follow the sentinel-resident layout (see
:class:`ReplicaState`): the commit scatters never materialize a padded
copy of the log or store, so per-commit cost is O(touched rows), not
O(state).

The store is offset-addressed like HyperLoop's NVM space; the redo-log ring
is the persistence domain and is what the checkpointer (fault layer) saves.

Durability classification (``fault.recovery``): the **redo-log ring +
``log_tail`` are the durable truth** — every store write is logged first
(write-ahead order inside ``ops.tx_commit``), so the store is *derivable*
by :func:`replay_records` from any consistent (store, log_tail) base plus
the log records past it. ``committed`` advances in lockstep with
``log_tail`` and ``live`` is host-side liveness policy re-imposed at
restart. The WAL-delta flush mode persists exactly the log records past a
per-replica high-water mark; ``fault.chain.resync_replica`` (replica →
replica) and ``fault.recovery.recover`` (disk → engine) are the same replay
loop, both built on :func:`replay_records`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro import compat
from repro.kernels import ops as kops

I32 = jnp.int32

# Named scopes of the APU's TX phases (op metadata only, see
# ``engine.SCOPES``): validation and the batch plan, then the chain commit.
PLAN = "tx.plan"
COMMIT = "tx.commit"
SCOPES = (PLAN, COMMIT)


class TxConfig(NamedTuple):
    num_keys: int = 4096  # offset-addressed NVM region (rows)
    val_words: int = 4
    max_ops: int = 8  # max (read,write) ops per transaction
    chain_len: int = 2  # replicas
    log_capacity: int = 1024


class ReplicaState(NamedTuple):
    """Sentinel-resident layout (the ``kvstore.KVState`` convention, which
    in turn mirrors the page pool's zero sentinel page): ``store`` and
    ``log`` each carry one permanent all-zero pad row past the live
    extent. Dead commit targets scatter zeroed payloads there, so the
    commit kernels never concatenate/strip an O(state) padded copy per
    replica. ``live_store``/``live_log`` view the live rows (chain states
    with a leading replica axis included)."""

    store: jax.Array  # (NK + 1, VW) int32 — the NVM region; row NK = sentinel
    log: jax.Array  # (LC + 1, 1 + max_ops*(1+VW)) int32; row LC = sentinel
    log_tail: jax.Array  # () int32
    committed: jax.Array  # () int32
    # Chain-shortening liveness mask (chain replication's defining fault
    # mode): () bool per replica, (R,) on a chain. A dead replica is
    # skipped by the commit walks with jit-stable shapes — its log/store
    # scatters retarget the sentinel row and its counters freeze, so the
    # array axis keeps its slot while the *protocol* chain shortens around
    # it. Kill/revive + log-replay resync live host-side in ``fault.chain``
    # (ChainMonitor / resync_replica).
    live: jax.Array

    @property
    def num_keys(self) -> int:
        """Live store rows (the resident sentinel row excluded)."""
        return self.store.shape[-2] - 1

    @property
    def log_capacity(self) -> int:
        """Live redo-log ring slots (the resident sentinel row excluded)."""
        return self.log.shape[-2] - 1

    @property
    def live_store(self) -> jax.Array:
        return self.store[..., :-1, :]

    @property
    def live_log(self) -> jax.Array:
        return self.log[..., :-1, :]


def tx_words(cfg: TxConfig) -> int:
    """[n_write_ops | (offset, value)*max_ops] — §IV-B log entry layout."""
    return 1 + cfg.max_ops * (1 + cfg.val_words)


def make_replica(cfg: TxConfig) -> ReplicaState:
    return ReplicaState(
        store=jnp.zeros((cfg.num_keys + 1, cfg.val_words), I32),
        log=jnp.zeros((cfg.log_capacity + 1, tx_words(cfg)), I32),
        log_tail=jnp.zeros((), I32),
        committed=jnp.zeros((), I32),
        live=jnp.ones((), bool),
    )


def make_chain(cfg: TxConfig):
    """Chain as a leading axis (local emulation); every replica starts
    live (``live`` broadcasts to an all-True (R,) mask)."""
    one = make_replica(cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.chain_len,) + x.shape), one
    )


def parse_tx(batch, cfg: TxConfig):
    """batch: (B, tx_words) -> (n_ops (B,), offsets (B,M), values (B,M,VW))."""
    b = batch.shape[0]
    n = jnp.clip(batch[:, 0], 0, cfg.max_ops)
    rest = batch[:, 1:].reshape(b, cfg.max_ops, 1 + cfg.val_words)
    offsets = jnp.clip(rest[..., 0], 0, cfg.num_keys - 1)
    values = rest[..., 1:]
    return n, offsets, values


def concurrency_control(n_ops, offsets, cfg: TxConfig, mask=None):
    """First-claimant-wins conflict detection.

    Returns proceed (B,) — tx i proceeds iff for every live op offset, the
    minimum batch index claiming that offset is i (reads are free: the chain
    already serializes them, §IV-B)."""
    b, m = offsets.shape
    live = jnp.arange(m)[None, :] < n_ops[:, None]  # (B, M)
    if mask is not None:
        live &= mask[:, None]
    idx = jnp.arange(b, dtype=I32)[:, None]
    claim_off = jnp.where(live, offsets, cfg.num_keys)
    owner = jnp.full((cfg.num_keys + 1,), b, I32).at[claim_off].min(
        jnp.broadcast_to(idx, (b, m))
    )
    mine = owner[claim_off] == idx
    ok = jnp.all(mine | ~live, axis=1)
    if mask is not None:
        ok &= mask
    return ok


class TxCommitPlan(NamedTuple):
    """The ALU half of a transaction batch, computed ONCE per batch (not
    once per replica): everything a replica commit needs except its own
    ``log_tail``. Sentinels follow the scatter convention of
    ``kvstore.PutPlan`` — ``store_rows == num_keys`` means no store write;
    a non-proceeding transaction's log slot resolves to ``log_capacity``
    inside :func:`replica_commit` (both backends drop sentinels)."""

    batch: jax.Array  # (B, TW) raw log records (what the ring persists)
    values: jax.Array  # (B, M, VW) parsed op values
    store_rows: jax.Array  # (B*M,) target store row per op, NK = dead
    log_rank: jax.Array  # (B,) rank among proceeding txs (log-slot offset)
    proceed: jax.Array  # (B,) bool — the live mask
    n_commit: jax.Array  # () int32 — log_tail / committed bump


def plan_commit(batch, cfg: TxConfig, mask=None, proceed=None) -> TxCommitPlan:
    """Plan a transaction batch without touching any replica: parse,
    first-claimant concurrency control, intra-tx write dedupe, log-slot
    ranking. Every replica then only runs :func:`replica_commit` — the
    chain scan no longer re-derives any of this per replica.

    ``proceed`` overrides concurrency control when the decision was made
    elsewhere (the SPMD chain forwards the head's decision down the ring).

    Within one transaction, duplicate write offsets resolve
    last-writer-wins (serial op order, §IV-B); shadowed ops get the drop
    sentinel. Combined with concurrency control keeping proceeding
    transactions' write sets disjoint, every live store row is unique —
    which is what lets the commit be a conflict-free dual scatter."""
    b = batch.shape[0]
    m = cfg.max_ops
    n, off, val = parse_tx(batch, cfg)
    if proceed is None:
        proceed = concurrency_control(n, off, cfg, mask)
    live = (jnp.arange(m)[None, :] < n[:, None]) & proceed[:, None]  # (B, M)
    # intra-tx dedupe: op j writes iff no later live op in the same tx
    # targets the same offset (last-writer-wins = serial op order)
    j = jnp.arange(m)
    shadowed = jnp.any(
        (off[:, :, None] == off[:, None, :])
        & live[:, None, :]
        & (j[None, None, :] > j[None, :, None]),
        axis=-1,
    )
    write = live & ~shadowed
    store_rows = jnp.where(write, off, cfg.num_keys).reshape(b * m)
    log_rank = jnp.cumsum(proceed.astype(I32)) - 1
    return TxCommitPlan(
        batch, val, store_rows, log_rank, proceed,
        jnp.sum(proceed.astype(I32)),
    )


def replica_commit(state: ReplicaState, plan: TxCommitPlan, *,
                   use_ref: bool = True, interpret=None) -> ReplicaState:
    """Execute the planned memory half on one replica: redo-log append +
    store scatter (write-ahead ordering), fused in ``ops.tx_commit``. The
    state flows through in its sentinel-resident layout — the dispatch
    hands ``ops.tx_commit`` the (LC+1)/(NK+1) arrays as-is and gets the
    same shapes back, aliased in place on the Pallas path."""
    lc = state.log_capacity
    # a batch committing more than LC transactions laps the ring within one
    # scatter: two ranks share a slot iff they differ by a multiple of LC,
    # so keeping only the last LC ranks IS sequential append order — and
    # keeps the duplicate-free scatter deterministic on every backend
    # (a jnp scatter with duplicate indices has unspecified update order)
    survives = plan.log_rank >= plan.n_commit - lc
    # a dead replica (chain shortening) commits nothing: every slot aims at
    # the sentinel, the store rows are masked, and the counters freeze
    slot = jnp.where(
        plan.proceed & survives & state.live,
        (state.log_tail + plan.log_rank) % lc, lc,
    )
    store_rows = jnp.where(state.live, plan.store_rows, state.num_keys)
    log, store = kops.tx_commit(
        state.log, state.store, plan.batch, plan.values, slot,
        store_rows, use_ref=use_ref, interpret=interpret,
    )
    bump = jnp.where(state.live, plan.n_commit, 0)
    return ReplicaState(
        store, log, state.log_tail + bump, state.committed + bump,
        state.live,
    )


def replay_records(state: ReplicaState, records, cfg: TxConfig, *,
                   use_ref: bool = True) -> ReplicaState:
    """Replay raw redo-log records (in log order) into one replica through
    the normal plan/commit path — the generic WAL-replay loop shared by
    replica→replica resync (``fault.chain.resync_replica``) and
    disk→engine crash recovery (``fault.recovery.recover``).

    ``proceed`` is forced True per record: the log only ever holds
    transactions that proceeded, so re-planning re-derives the very store
    scatter, log-ring slot, and counter bumps the original commit executed
    — one record at a time, hence bit-for-bit reproduction of the source's
    store and log ring. The caller guarantees the records are consecutive
    from ``state.log_tail`` (a gap wider than the ring means the replay
    window is gone — restore by full copy instead)."""
    for record in records:
        plan = plan_commit(
            jnp.asarray(record, I32)[None, :], cfg,
            proceed=jnp.ones((1,), bool),
        )
        state = replica_commit(state, plan, use_ref=use_ref)
    return state


# ---------------------------------------------------------------------------
# Local (batched-over-replicas) chain
# ---------------------------------------------------------------------------

def chain_commit_apply(chain: ReplicaState, plan: TxCommitPlan, *,
                       use_ref: bool = True, interpret=None) -> ReplicaState:
    """Apply a precomputed plan to every replica of a local chain with ONE
    batched dual scatter over the replica axis (``ops.tx_commit_chain``).

    The old replica scan staged each replica's whole log+store through the
    scan's xs/ys — an O(state) copy per replica per round that survived
    the sentinel-resident layout; batching the scatter over the (R, ...)
    chain arrays touches only the planned rows, so the chain state can
    stay resident across engine steps. Per-replica ``log_tail`` values are
    honoured (replicas advance in lockstep from :func:`make_chain`, but a
    hand-built chain with skewed tails commits exactly like a
    :func:`replica_commit` loop would). Dead replicas (``chain.live``
    False — mask-based chain shortening) are skipped with jit-stable
    shapes: their log slots retarget the sentinel row and their
    ``log_tail``/``committed`` freeze, so a revived replica's resync gap
    is exactly the survivors' tail minus its own (``fault.chain``)."""
    lc = chain.log_capacity
    survives = plan.log_rank >= plan.n_commit - lc
    slot = jnp.where(
        (plan.proceed & survives)[None, :] & chain.live[:, None],
        (chain.log_tail[:, None] + plan.log_rank[None, :]) % lc,
        lc,
    )
    store_rows = jnp.where(
        chain.live[:, None], plan.store_rows[None, :], chain.num_keys
    )
    log, store = kops.tx_commit_chain(
        chain.log, chain.store, plan.batch, plan.values, slot,
        store_rows, use_ref=use_ref, interpret=interpret,
    )
    bump = jnp.where(chain.live, plan.n_commit, 0)
    return ReplicaState(
        store, log, chain.log_tail + bump, chain.committed + bump,
        chain.live,
    )


def chain_commit_local(chain: ReplicaState, batch, cfg: TxConfig, mask=None,
                       *, kernel_backend: Optional[str] = "auto"):
    """Commit a batch through the whole chain. Returns (chain, committed,
    deferred). ``committed[i]`` True once every replica applied tx i.

    The plan is computed once; the commit is one whole-chain dual scatter
    (:func:`chain_commit_apply`), dispatched per ``kernel_backend``.
    Default ``auto`` — the fused Pallas kernel (native on TPU, interpret
    elsewhere), matching ``tx_app.app_step``'s APU default; ``ref`` = the
    jnp oracle. Both agree bit-for-bit."""
    with jax.named_scope(PLAN):
        plan = plan_commit(batch, cfg, mask)
    use_ref, interpret = kops.resolve_backend(kernel_backend or "auto")
    with jax.named_scope(COMMIT):
        new_chain = chain_commit_apply(
            chain, plan, use_ref=use_ref, interpret=interpret
        )
    proceed = plan.proceed
    deferred = (mask if mask is not None else jnp.ones_like(proceed)) & ~proceed
    return new_chain, proceed, deferred


def chain_hops(cfg: TxConfig, n_ops: int, per_op: bool) -> int:
    """Chain traversals (forward + ACK) per transaction: the latency model
    behind Fig. 11. HyperLoop: one traversal per op; ORCA: one per tx."""
    traversals = n_ops if per_op else 1
    return traversals * 2 * (cfg.chain_len - 1)


# ---------------------------------------------------------------------------
# SPMD (ppermute) chain
# ---------------------------------------------------------------------------

def chain_commit_spmd(chain: ReplicaState, batch, cfg: TxConfig, mesh,
                      axis: str = "data", mask=None,
                      *, kernel_backend: Optional[str] = "auto"):
    """Replicas sharded over ``axis`` (leading dim == chain_len). The head
    (rank 0) runs concurrency control; the log batch ppermutes down the
    chain; every rank commits the forwarded plan; the ACK ppermutes back
    (counted, not carried: the commit flag returns to the head after
    2*(R-1) hops). ``kernel_backend`` is API-equal to
    :func:`chain_commit_local` — each rank plans from the forwarded batch
    + decision (free in wall-clock: ranks are parallel devices) and runs
    the same dispatched commit."""
    r = cfg.chain_len
    mask_arr = mask if mask is not None else jnp.ones((batch.shape[0],), bool)
    use_ref, interpret = kops.resolve_backend(kernel_backend or "auto")

    def inner(rep, bb, mk):
        # shard_map blocks carry a leading chain dim of 1 — strip it
        rep = jax.tree_util.tree_map(lambda x: x[0], rep)
        me = jax.lax.axis_index(axis)
        n, off, _ = parse_tx(bb, cfg)
        proceed = concurrency_control(n, off, cfg, mk)
        # broadcast head's decision down the chain, hop by hop
        def fwd(i, carry):
            b_cur, p_cur = carry
            perm = [(j, j + 1) for j in range(r - 1)]
            b_nxt = jax.lax.ppermute(b_cur, axis, perm)
            p_nxt = jax.lax.ppermute(p_cur, axis, perm)
            take = me == (i + 1)
            return (
                jnp.where(take, b_nxt, b_cur),
                jnp.where(take, p_nxt, p_cur),
            )

        bb_f, pr_f = jax.lax.fori_loop(0, r - 1, fwd, (bb, proceed))
        plan = plan_commit(bb_f, cfg, proceed=pr_f)
        new_rep = replica_commit(
            rep, plan, use_ref=use_ref, interpret=interpret
        )
        # ACK back-propagation: tail -> head
        ack = pr_f
        def bwd(i, a):
            perm = [(j + 1, j) for j in range(r - 1)]
            return jax.lax.ppermute(a, axis, perm)

        ack = jax.lax.fori_loop(0, r - 1, bwd, ack)
        new_rep = jax.tree_util.tree_map(lambda x: x[None], new_rep)
        return new_rep, ack, mk & ~pr_f

    rep_specs = jax.tree_util.tree_map(lambda _: P(axis), chain)
    fn = compat.shard_map(
        inner, mesh=mesh,
        in_specs=(rep_specs, P(), P()),
        out_specs=(rep_specs, P(), P()),
        check_vma=False,
    )
    return fn(chain, batch, mask_arr)
