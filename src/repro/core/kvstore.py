"""ORCA-KV (§IV-A): MICA-style set-associative in-memory hash KVS.

Layout follows the paper: a set-associative hash table whose entries hold
pointers into a slab-allocated value pool; hash collisions spill into one
overflow bucket (the chained-bucket analogue), so a GET costs at most three
memory accesses (primary bucket, overflow bucket, value row) and a PUT four
— matching the MICA/KV-Direct access counts cited in §IV-A.

Everything is batched and functional: a batch of requests is one vectorized
walk, the TPU analogue of the APU's 256-outstanding-request memory-level
parallelism. The Pallas ``hash_probe`` kernels accelerate the same walk with
explicit VMEM staging; the jnp implementations here are their oracles, and
``get``/``put`` dispatch between the two via the ``backend`` knob
(``auto | pallas | ref``; the engine threads ``EngineConfig.kernel_backend``
through ``app_step``). PUT splits into :func:`plan_put` (hashes, dedupe,
way ranking — ALU work, always jnp) and a commit phase that either backend
applies identically, so the paths agree bit-for-bit.

Hot-set cache tier (§IV-A's "serve the hot last mile from cache" bet,
measured instead of modeled): ``KVConfig.cache_sets > 0`` adds a small
set-associative cache — key/value/meta arrays resident in ``KVState``
under the same sentinel convention — that GET probes *before* the bucket
walk (``kernels.hash_probe.cache_probe`` / its ``kernels.ref`` oracle: one
VMEM set lookup) and falls through to the bucket walk only for the miss
subset. Eviction is frequency-decay (CLOCK-style reference bits in
``cache_meta``); PUT commits write-through (update-on-hit, admit-on-miss)
so no stale value ever survives and both backends stay bit-for-bit. All
cache maintenance is ALU work shared by the backends, like the PUT plan.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

I32 = jnp.int32
U32 = jnp.uint32

# Named scopes of the APU's KVS phases (op metadata only, see
# ``engine.SCOPES``): the GET walk with its cache maintenance, the PUT plan,
# and the PUT commit with its cache write-through.
GET = "kvs.get"
PLAN_PUT = "kvs.plan_put"
COMMIT_PUT = "kvs.commit_put"
SCOPES = (GET, PLAN_PUT, COMMIT_PUT)


class KVConfig(NamedTuple):
    num_buckets: int = 1024  # power of two
    ways: int = 8
    key_words: int = 2
    val_words: int = 16  # 64 B values like the paper's workload
    pool_size: int = 8192
    cache_sets: int = 0  # hot-set cache sets; 0 disables the cache tier
    cache_ways: int = 4  # associativity of the hot-set cache


# Hot-set cache reference bits (CLOCK-style frequency decay).
# cache_meta values: 0 = never-used way; >= 1 = valid entry whose value is
# its remaining reference count. A probe hit refreshes to the ceiling, an
# admission starts one notch above the floor, and an admission attempt
# that finds no victim sweeps its set's counters down by one (floor 1, so
# a valid entry decays to "evictable" but never back to "empty"). Victims
# are ways with meta <= 1: empty first, then fully-decayed cold entries.
# The ceiling sets scan resistance: a hot entry survives ~CACHE_REF_MAX
# pressured admission rounds between re-hits. 15 holds the zipf-0.9 head
# stable at a 5%-of-pool cache (measured ~0.65 hit rate, near the
# conflict-adjusted ideal); at 3 the mid-hot ranks churn out faster than
# they recur and the measured rate drops under 0.6.
CACHE_REF_MAX = 15  # refresh: meta = 1 + CACHE_REF_MAX
CACHE_ADMIT_REF = 1  # admission: meta = 1 + CACHE_ADMIT_REF
CACHE_SALT = 0x85EBCA6B  # set hash salt (distinct from both bucket salts)


class KVState(NamedTuple):
    """Sentinel-resident layout: every scatter-target array carries one
    permanent all-zero pad row past its live extent (the shared convention
    of ``serving.kv_cache``'s zero sentinel page) — dropped/no-op writes
    land there as zeros instead of the kernel wrappers concatenating and
    stripping an O(state) padded copy around every commit.

    Durability classification (``fault.recovery``): the KVS keeps **no
    write-ahead log** — *every* field here is durable truth (buckets,
    bucket→pool pointers, the value pool, the bump allocator, the cache
    tier and all counters); nothing is derivable from anything else after
    a crash. The WAL-delta flush mode therefore persists a *materialized
    dirty-row delta*: a host-side row diff of :data:`DURABLE_ROW_ARRAYS`
    against the shadow copy of the last flush (the measured dirty bytes
    that also drive the adaptive full-vs-delta policy), plus the scalar
    counters verbatim. Sentinel rows are all-zero in every reachable state
    (the hygiene property tests) so they never appear dirty."""

    bucket_keys: jax.Array  # (NB + 1, W, KW) int32; row NB = zero sentinel
    bucket_ptr: jax.Array  # (NB + 1, W) int32 value-pool row, -1 = empty
    pool: jax.Array  # (NP + 1, VW) int32; row NP = zero sentinel
    alloc: jax.Array  # () int32 bump allocator
    dropped: jax.Array  # () int32 PUTs rejected (both buckets full)
    # hot-set cache tier (sentinel-resident like the buckets; row CS = zero
    # sentinel forever — cache_sets=0 keeps only the sentinel row resident)
    cache_keys: jax.Array  # (CS + 1, CW, KW) int32 cached keys
    cache_vals: jax.Array  # (CS + 1, CW, VW) int32 cached values
    cache_meta: jax.Array  # (CS + 1, CW) int32 CLOCK bits; 0 = empty way
    cache_hits: jax.Array  # () int32 GETs served from the cache tier
    cache_misses: jax.Array  # () int32 GETs that fell through to the walk
    cache_evictions: jax.Array  # () int32 valid-but-decayed entries replaced

    @property
    def num_buckets(self) -> int:
        """Live bucket rows (the resident sentinel row excluded)."""
        return self.bucket_keys.shape[0] - 1

    @property
    def pool_size(self) -> int:
        """Live value-pool rows (the resident sentinel row excluded)."""
        return self.pool.shape[0] - 1

    @property
    def cache_sets(self) -> int:
        """Live cache set rows (0 = cache tier disabled)."""
        return self.cache_keys.shape[0] - 1

    @property
    def cache_ways(self) -> int:
        return self.cache_keys.shape[1]


# KVState fields that are large row-indexed arrays (axis 0 = row), diffed
# row-wise by the durability tier's WAL-delta flush; every other field is a
# scalar counter persisted verbatim in the delta record's control section.
DURABLE_ROW_ARRAYS = (
    "bucket_keys", "bucket_ptr", "pool", "cache_keys", "cache_vals",
    "cache_meta",
)


def make(cfg: KVConfig) -> KVState:
    # the sentinel row of bucket_ptr is 0 (not -1) so every sentinel row in
    # the state is all-zero — the hygiene invariant the property tests pin
    if cfg.cache_sets:
        from repro.core import placement

        cache_bytes = placement.kvs_cache_bytes(
            cfg.cache_sets, cfg.cache_ways, cfg.key_words, cfg.val_words
        )
        if cache_bytes > placement.VMEM_BUDGET:
            raise ValueError(
                f"hot-set cache ({cache_bytes} B) exceeds the VMEM budget "
                f"({placement.VMEM_BUDGET} B) — shrink cache_sets/cache_ways"
            )
    return KVState(
        bucket_keys=jnp.zeros(
            (cfg.num_buckets + 1, cfg.ways, cfg.key_words), I32
        ),
        bucket_ptr=jnp.full(
            (cfg.num_buckets + 1, cfg.ways), -1, I32
        ).at[cfg.num_buckets].set(0),
        pool=jnp.zeros((cfg.pool_size + 1, cfg.val_words), I32),
        alloc=jnp.zeros((), I32),
        dropped=jnp.zeros((), I32),
        cache_keys=jnp.zeros(
            (cfg.cache_sets + 1, cfg.cache_ways, cfg.key_words), I32
        ),
        cache_vals=jnp.zeros(
            (cfg.cache_sets + 1, cfg.cache_ways, cfg.val_words), I32
        ),
        cache_meta=jnp.zeros((cfg.cache_sets + 1, cfg.cache_ways), I32),
        cache_hits=jnp.zeros((), I32),
        cache_misses=jnp.zeros((), I32),
        cache_evictions=jnp.zeros((), I32),
    )


def hash_keys(keys, num_buckets: int, salt: int = 0):
    """FNV-1a over key words -> bucket id. keys: (..., KW) int32."""
    h = jnp.full(keys.shape[:-1], jnp.uint32(2166136261 ^ salt))
    for w in range(keys.shape[-1]):
        h = (h ^ keys[..., w].astype(U32)) * jnp.uint32(16777619)
    return (h % jnp.uint32(num_buckets)).astype(I32)


def get(state: KVState, keys, mask=None, *, backend: Optional[str] = "auto",
        with_state: bool = False):
    """Batched GET. keys: (B, KW). Returns (vals (B, VW), found (B,)) —
    or (state, vals, found) under ``with_state=True``, where the returned
    state carries the hot-set cache maintenance (reference-bit refresh on
    hits, admission of found misses, hit/miss counters). Bucket arrays and
    the pool are never modified by a GET.

    With the cache tier enabled the walk is: one ``cache_probe`` VMEM set
    lookup first, then the bucket walk (primary bucket, overflow bucket,
    value pool) only for the miss subset — hit rows retarget the resident
    sentinel bucket, and an all-hit batch skips the bucket walk entirely
    (``lax.cond``). ``backend`` picks the probe/walk implementation
    (``auto``/``pallas`` = kernels, the same default ``app_step`` threads
    from the engine; ``ref`` = the ``kernels.ref`` oracles); results are
    identical (integer data, single-match buckets/sets)."""
    nb = state.num_buckets
    use_ref, interpret = kops.resolve_backend(backend or "auto")
    if state.cache_sets == 0:
        h1 = hash_keys(keys, nb)
        h2 = hash_keys(keys, nb, salt=0x9E3779B9)
        vals, found = kops.hash_get(
            state.bucket_keys, state.bucket_ptr, state.pool, keys, h1, h2,
            use_ref=use_ref, interpret=interpret,
        )
        if mask is not None:
            found = found & mask
        return (state, vals, found) if with_state else (vals, found)

    live = jnp.ones(keys.shape[:1], bool) if mask is None else mask
    cset = hash_keys(keys, state.cache_sets, salt=CACHE_SALT)
    hit, way, cvals = kops.cache_probe(
        state.cache_keys, state.cache_vals, state.cache_meta, keys, cset,
        use_ref=use_ref, interpret=interpret,
    )

    # miss-subset fallthrough: hit rows retarget the resident sentinel
    # bucket (one hot line instead of a scattered walk), and a batch whose
    # live rows all hit skips the bucket walk entirely — hashing included:
    # h1/h2 are computed inside the cond branch, so the served-from-cache
    # fast path pays one set hash + one VMEM probe, nothing else
    def _walk(_):
        h1m = jnp.where(hit, nb, hash_keys(keys, nb))
        h2m = jnp.where(hit, nb, hash_keys(keys, nb, salt=0x9E3779B9))
        return kops.hash_get(
            state.bucket_keys, state.bucket_ptr, state.pool, keys, h1m, h2m,
            use_ref=use_ref, interpret=interpret,
        )

    def _skip(_):
        return jnp.zeros_like(cvals), jnp.zeros_like(hit)

    bvals, bfound = jax.lax.cond(jnp.all(hit | ~live), _skip, _walk, None)
    found_raw = hit | bfound
    vals = jnp.where(
        found_raw[:, None], jnp.where(hit[:, None], cvals, bvals), 0
    )
    found = found_raw & live
    if not with_state:
        return vals, found if mask is not None else found_raw

    # maintenance: refresh reference bits on live hits; admit live misses
    # the bucket walk found (deduped — a batch can GET one key twice)
    refresh = live & hit
    admit = _first_live(keys, live & ~hit & bfound)
    ck, cv, cm, n_evict = _cache_commit(
        state, keys, cset, refresh, way, admit, bvals
    )
    state = state._replace(
        cache_keys=ck, cache_vals=cv, cache_meta=cm,
        cache_hits=state.cache_hits + jnp.sum((live & hit).astype(I32)),
        cache_misses=state.cache_misses + jnp.sum((live & ~hit).astype(I32)),
        cache_evictions=state.cache_evictions + n_evict,
    )
    return state, vals, found


def _rank_within(ids, num: int):
    """Stable rank of each element among equal ids (dispatch helper)."""
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    first = jnp.searchsorted(sorted_ids, jnp.arange(num), side="left")
    rank_sorted = jnp.arange(n) - first[sorted_ids]
    return jnp.zeros((n,), I32).at[order].set(rank_sorted.astype(I32))


def _nth_empty_way(bp_rows, rank):
    """bp_rows: (B, W) pointers; rank: (B,). Index of the rank-th empty way
    (W if fewer empties than rank+1)."""
    empty = bp_rows < 0  # (B, W)
    csum = jnp.cumsum(empty.astype(I32), axis=-1)
    target = rank[:, None] + 1
    is_nth = empty & (csum == target)
    has = jnp.any(is_nth, axis=-1)
    way = jnp.argmax(is_nth, axis=-1).astype(I32)
    return jnp.where(has, way, bp_rows.shape[-1])


def _first_live(keys, rows):
    """Keep only the first instance of each key among ``rows`` (the cache
    admission dedupe — same lexsort-run trick as ``plan_put``, so duplicate
    GETs of one key admit once instead of taking two ways)."""
    b = keys.shape[0]
    order = jnp.lexsort(
        tuple(keys[:, w] for w in reversed(range(keys.shape[1])))
        + ((~rows).astype(I32),)
    )
    sk = keys[order]
    sr = rows[order]
    boundary = jnp.any(sk[1:] != sk[:-1], axis=-1) | (sr[1:] != sr[:-1])
    first_sorted = jnp.concatenate([jnp.ones((1,), bool), boundary])
    is_first = jnp.zeros((b,), bool).at[order].set(first_sorted)
    return rows & is_first


def _cache_commit(state, keys, cset, refresh, way, admit, admit_vals,
                  upd_vals=None):
    """One batch of hot-set cache maintenance — ALU work shared by both
    backends (like ``plan_put``), so ref == pallas stays bit-for-bit.

    ``refresh`` rows bump (cset, way) to the reference ceiling and — when
    ``upd_vals`` is given (the PUT write-through) — overwrite the cached
    value in place. ``admit`` rows must carry unique keys (callers dedupe);
    each takes the rank-th victim way of its set (meta <= 1 after the CLOCK
    decay: empty first, then fully-decayed entries), so live scatter
    targets never collide. No-op rows aim one past the sentinel row and
    ``mode="drop"`` discards them — the sentinel row itself stays zero.

    Returns (cache_keys, cache_vals, cache_meta, n_evictions)."""
    cs = state.cache_sets
    cw = state.cache_ways
    meta = state.cache_meta

    # CLOCK hand: an admission attempt sweeps its set's counters down one
    # notch (floor 1 — valid entries decay to evictable, never empty), but
    # ONLY under pressure, i.e. when the set has no victim way left (every
    # way live with meta > 1). Like the real CLOCK hand, which stops at the
    # first ref=0 frame: sets with an empty or fully-decayed way admit into
    # it without touching the survivors, so hot entries age only while
    # their set is full of protected entries — not on every tail-key miss
    # that happens to hash nearby (scan resistance; unconditional decay
    # measurably drains the zipf mid-hot ranks faster than they re-hit).
    att = jnp.zeros((cs + 1,), I32).at[
        jnp.where(admit, cset, cs + 1)
    ].add(1, mode="drop") > 0
    pressured = att & ~jnp.any(meta <= 1, axis=1)
    meta = jnp.where(pressured[:, None] & (meta > 0),
                     jnp.maximum(meta - 1, 1), meta)

    rset = jnp.where(refresh, cset, cs + 1)
    rway = jnp.where(refresh, jnp.clip(way, 0, cw - 1), 0)
    meta = meta.at[rset, rway].set(1 + CACHE_REF_MAX, mode="drop")
    cache_vals = state.cache_vals
    if upd_vals is not None:
        cache_vals = cache_vals.at[rset, rway].set(upd_vals, mode="drop")

    # ranked admission: the r-th admitting key of a set takes the r-th
    # victim way; sets with more admissions than victims drop the excess
    r = _rank_within(jnp.where(admit, cset, cs), cs + 1)
    victim_ok = jnp.where(meta <= 1, -1, 0)  # _nth_empty_way convention
    vict = _nth_empty_way(victim_ok[cset], r)
    can = admit & (vict < cw)
    vclip = jnp.clip(vict, 0, cw - 1)
    n_evict = jnp.sum((can & (meta[cset, vclip] == 1)).astype(I32))
    aset = jnp.where(can, cset, cs + 1)
    away = jnp.where(can, vclip, 0)
    cache_keys = state.cache_keys.at[aset, away].set(keys, mode="drop")
    cache_vals = cache_vals.at[aset, away].set(admit_vals, mode="drop")
    meta = meta.at[aset, away].set(1 + CACHE_ADMIT_REF, mode="drop")
    return cache_keys, cache_vals, meta, n_evict


class PutPlan(NamedTuple):
    """The ALU half of a batched PUT: where every write lands.

    Sentinels follow the scatter convention: ``tb == NB`` means no bucket
    write, ``wp == NP`` means no value write — both backends aim them at
    the state's resident zero sentinel row and zero the payload, so the
    sentinel stays zero and no padded state copy is ever materialized.

    The target sort orders (``bucket_order``/``row_order``) are part of the
    plan — ALU staging, computed once here so the Pallas commit's
    same-target VMEM-block sharing never re-sorts per dispatch."""

    tb: jax.Array  # (B,) target bucket row
    tw: jax.Array  # (B,) target way within the bucket
    bptr_val: jax.Array  # (B,) pool pointer committed at (tb, tw)
    wp: jax.Array  # (B,) pool row receiving the value
    alloc: jax.Array  # () updated bump allocator
    dropped: jax.Array  # () updated drop counter
    ok: jax.Array  # (B,) per-request success
    bucket_order: jax.Array  # (B,) argsort(tb): bucket-commit issue order
    row_order: jax.Array  # (B,) argsort(wp): value-write issue order


def plan_put(state: KVState, keys, mask=None, *,
             backend: Optional[str] = "auto") -> PutPlan:
    """Plan a batched PUT/UPDATE (dedupe, match, way ranking) without
    touching the store. The commit phase (``ref``/Pallas) applies it.

    The way ranking and dedupe are ALU work and stay jnp, but the
    existence check — the PUT's first two memory accesses — dispatches to
    the Pallas ``probe`` kernel under ``backend in (auto, pallas)``, so a
    kernel-backed PUT touches memory through kernels end to end (probe,
    probe, bucket commit, value write)."""
    b = keys.shape[0]
    if mask is None:
        mask = jnp.ones((b,), bool)
    nb = state.num_buckets
    np_ = state.pool_size
    h1 = hash_keys(keys, nb)
    h2 = hash_keys(keys, nb, salt=0x9E3779B9)
    use_ref, interpret = kops.resolve_backend(backend or "auto")

    # dedupe identical keys in the batch: only the first LIVE instance
    # inserts, and only the last LIVE instance writes the value row
    # (last-writer-wins). Lexicographic sort on the full key words — a
    # hashed tag can collide for distinct keys and silently drop one (found
    # by hypothesis). Masked rows sort behind the live section and runs
    # split at the live/masked boundary, so a masked row sharing a key with
    # a live PUT can steal neither the run's insert nor its value write
    # (the engine masks GET rows out of the PUT walk every step).
    order = jnp.lexsort(
        tuple(keys[:, w] for w in reversed(range(keys.shape[1])))
        + ((~mask).astype(I32),)
    )
    sorted_keys = keys[order]
    live_sorted = mask[order]
    run_boundary = jnp.any(sorted_keys[1:] != sorted_keys[:-1], axis=-1) | (
        live_sorted[1:] != live_sorted[:-1]
    )
    is_first_sorted = jnp.concatenate([jnp.ones((1,), bool), run_boundary])
    is_first = jnp.zeros((b,), bool).at[order].set(is_first_sorted)

    # existence check (memory accesses 1+2): probe kernel or jnp oracle —
    # both return ptr only where found, which is the only place it is read
    exists, ptr_existing = kops.hash_probe(
        state.bucket_keys, state.bucket_ptr, keys, h1, h2,
        use_ref=use_ref, interpret=interpret,
    )

    # --- inserts: two-phase so primary and spill writers never collide ---
    # phase 1: primary-bucket inserters rank among themselves per bucket
    inserting = mask & is_first & ~exists
    r1 = _rank_within(jnp.where(inserting, h1, nb), nb + 1)
    w1 = _nth_empty_way(state.bucket_ptr[h1], r1)
    fits1 = inserting & (w1 < state.bucket_ptr.shape[1])
    spill = inserting & ~fits1

    # provisional pool rows (final pool_ok applied after phase 2)
    # phase 1 commit of bucket_ptr occupancy with sentinel rows, so phase 2
    # sees primaries as occupied (a batch can feed one bucket through BOTH
    # h1 and h2 — found by hypothesis). nb + 1 (not nb): the occupancy temp
    # must not scribble on the resident sentinel row, so non-fitting rows
    # aim past the array and mode="drop" discards them
    tb1 = jnp.where(fits1, h1, nb + 1)
    occ_ptr = state.bucket_ptr.at[tb1, jnp.where(fits1, w1, 0)].set(
        jnp.iinfo(jnp.int32).max, mode="drop"
    )

    # phase 2: spill inserters rank against the UPDATED occupancy
    r2 = _rank_within(jnp.where(spill, h2, nb), nb + 1)
    w2 = _nth_empty_way(occ_ptr[h2], r2)
    fits2 = spill & (w2 < state.bucket_ptr.shape[1])
    drop = spill & ~fits2

    fits_struct = fits1 | fits2
    new_rank = jnp.cumsum(fits_struct.astype(I32)) - 1
    new_ptr = state.alloc + new_rank
    pool_ok = new_ptr < np_
    fits1 &= pool_ok
    fits2 &= pool_ok
    drop = drop | (fits_struct & ~pool_ok)

    tb = jnp.where(fits1, h1, jnp.where(fits2, h2, nb))  # nb = dropped row
    tw = jnp.where(fits1, w1, jnp.where(fits2, w2, 0))
    bptr_val = jnp.where(fits1 | fits2, new_ptr, -1)

    # --- value writes: updates + inserts, last-writer-wins ---------------
    # scatters with duplicate indices are unordered, so among duplicate
    # keys only the LAST batch instance writes its value, to the
    # pool row the FIRST instance resolved (existing hit or fresh insert).
    first_ptr = jnp.where(
        exists, ptr_existing, jnp.where(fits1 | fits2, new_ptr, -1)
    )
    run_id_sorted = jnp.cumsum(is_first_sorted) - 1  # (B,) run index, sorted
    run_ptr = jnp.full((b,), -1, I32).at[run_id_sorted].max(
        jnp.where(is_first_sorted, first_ptr[order], -1)
    )
    eff_ptr_sorted = run_ptr[run_id_sorted]
    eff_ptr = jnp.zeros((b,), I32).at[order].set(eff_ptr_sorted)
    last_in_sorted = jnp.concatenate([run_boundary, jnp.ones((1,), bool)])
    is_last = jnp.zeros((b,), bool).at[order].set(last_in_sorted)
    row_live = mask & is_last & (eff_ptr >= 0)
    wp = jnp.where(row_live, eff_ptr, np_)

    alloc = state.alloc + jnp.maximum(jnp.sum((fits1 | fits2).astype(I32)), 0)
    dropped = state.dropped + jnp.sum(drop.astype(I32))
    ok = mask & (exists | fits1 | fits2)
    return PutPlan(
        tb, tw, bptr_val, wp, alloc, dropped, ok,
        bucket_order=jnp.argsort(tb, stable=True),
        row_order=jnp.argsort(wp, stable=True),
    )


def put(state: KVState, keys, vals, mask=None, *,
        backend: Optional[str] = "auto"):
    """Batched PUT/UPDATE. keys: (B,KW), vals: (B,VW). Returns (state, ok).

    In-batch duplicate keys resolve last-writer-wins on the value row;
    insertion conflicts are resolved exactly via per-bucket ranking (each new
    key takes the rank-th empty way). Keys that fit in neither bucket are
    dropped and counted (the chained-allocation path of the paper, reported
    rather than allocated).

    With the cache tier enabled the commit is write-through: the final
    writer of every landed key updates any cached copy in place (so no
    stale value ever survives an overwrite) and misses are admission
    attempts gated by the reference bits — a PUT flood cannot wipe a hot
    GET working set.

    ``backend`` picks the plan's existence probe, the cache probe, and the
    commit — ``auto``/``pallas`` (the scalar-prefetch probe + VMEM-staged
    scatter kernels: all four PUT memory accesses kernelized; the default,
    matching ``app_step``) or ``ref`` (oracle gathers/scatters). Both
    backends write identical values, so they agree bit-for-bit.
    """
    with jax.named_scope(PLAN_PUT):
        plan = plan_put(state, keys, mask, backend=backend)
    use_ref, interpret = kops.resolve_backend(backend or "auto")
    with jax.named_scope(COMMIT_PUT):
        bucket_keys, bucket_ptr, pool = kops.hash_put(
            state.bucket_keys, state.bucket_ptr, state.pool, keys, vals,
            plan.tb, plan.tw, plan.bptr_val, plan.wp,
            plan.bucket_order, plan.row_order,
            use_ref=use_ref, interpret=interpret,
        )
        state = state._replace(
            bucket_keys=bucket_keys, bucket_ptr=bucket_ptr, pool=pool,
            alloc=plan.alloc, dropped=plan.dropped,
        )
        if state.cache_sets > 0:
            state = _put_write_through(
                state, keys, vals, plan, use_ref, interpret
            )
    return state, plan.ok


def _put_write_through(state: KVState, keys, vals, plan: PutPlan, use_ref,
                       interpret) -> KVState:
    """Cache side of a committed PUT: the rows that wrote their run's final
    value (``plan.wp`` targets a live pool row — unique keys by
    construction) update-on-hit / admit-on-miss, so the cached copy always
    equals the pool row just written. Dropped, masked, and superseded
    duplicate rows aim at the drop target and never touch the cache."""
    rows = plan.wp < state.pool_size
    cset = hash_keys(keys, state.cache_sets, salt=CACHE_SALT)
    hit, way, _ = kops.cache_probe(
        state.cache_keys, state.cache_vals, state.cache_meta, keys, cset,
        use_ref=use_ref, interpret=interpret,
    )
    ck, cv, cm, n_evict = _cache_commit(
        state, keys, cset, rows & hit, way, rows & ~hit, vals, upd_vals=vals
    )
    return state._replace(
        cache_keys=ck, cache_vals=cv, cache_meta=cm,
        cache_evictions=state.cache_evictions + n_evict,
    )


# ---------------------------------------------------------------------------
# Request-level interface (engine app): HERD-style fixed-width RPC slots.
# word0 = op (0 nop / 1 GET / 2 PUT), words[1:1+KW] = key, rest = value.
# Response: word0 = status (1 found/ok), rest = value.
# ---------------------------------------------------------------------------

OP_NOP, OP_GET, OP_PUT = 0, 1, 2


def request_words(cfg: KVConfig) -> int:
    return 1 + cfg.key_words + cfg.val_words


def app_step(state: KVState, payloads, valid, cfg: KVConfig, *,
             kernel_backend: Optional[str] = "auto"):
    """Engine hook: payloads (B, 1+KW+VW) int32 -> (state, responses).

    ``kernel_backend`` is the engine's dispatch knob — the APU walk runs
    through the Pallas kernels by default (native on TPU, interpret mode
    elsewhere); ``ref`` keeps the jnp oracle path."""
    from repro.core import status as stc

    op = payloads[:, 0]
    keys = payloads[:, 1 : 1 + cfg.key_words]
    vals = payloads[:, 1 + cfg.key_words : 1 + cfg.key_words + cfg.val_words]
    # payload validation (core/status.py): an unknown opcode NACKs as
    # MALFORMED instead of silently resolving to a zero-status no-op —
    # the row is masked out of both walks, so it cannot scatter garbage
    bad = valid & ~((op == OP_NOP) | (op == OP_GET) | (op == OP_PUT))
    # GETs read the store from before this batch's PUTs; the returned state
    # carries the cache maintenance (hit refresh, admissions, counters).
    # Invalid and MALFORMED rows are masked out of both walks, so they
    # neither scatter garbage nor touch the cache (no admission, no
    # reference-bit bump).
    with jax.named_scope(GET):
        state, get_vals, found = get(
            state, keys, mask=valid & (op == OP_GET), backend=kernel_backend,
            with_state=True,
        )
    state, put_ok = put(
        state, keys, vals, mask=valid & ~bad & (op == OP_PUT),
        backend=kernel_backend,
    )
    status = jnp.where(
        op == OP_GET, found.astype(I32), jnp.where(op == OP_PUT, put_ok.astype(I32), 0)
    )
    status = jnp.where(bad, stc.MALFORMED, status)
    resp = jnp.concatenate(
        [status[:, None], jnp.where((op == OP_GET)[:, None], get_vals, 0)], axis=1
    )
    pad = payloads.shape[1] - resp.shape[1]
    if pad > 0:
        resp = jnp.pad(resp, ((0, 0), (0, pad)))
    return state, resp
