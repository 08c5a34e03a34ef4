"""Pure-jnp oracles for every kernel in this package (the ground truth the
per-kernel allclose tests sweep against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30


def embedding_reduce(table, idx, seg_ids, num_segments: int):
    """(R, D), (N,), (N,) -> (num_segments, D) f32 segment sums."""
    return jax.ops.segment_sum(
        table[idx].astype(F32), seg_ids, num_segments
    )


def dlrm_embedding_reduce(tables, idx):
    """DLRM-shaped reduction oracle: (T, R', D), (B, T, L) -> (B, T, D) f32.

    Lookups are accumulated sequentially (an explicit add chain XLA keeps in
    order) — the same association order as a per-row walk over the lookup
    list, so results match both a host-side ``table[idx].sum(0)`` loop and
    the Pallas kernel's per-segment VMEM accumulator bit-for-bit on f32.
    """
    t_ids = jnp.arange(tables.shape[0])[None, :, None]
    g = tables[t_ids, idx].astype(F32)  # (B, T, L, D)
    out = g[:, :, 0]
    for l in range(1, g.shape[2]):
        out = out + g[:, :, l]
    return out


def dlrm_pool(table, rows, pooling):
    """Ragged DLRM pooling oracle: (R, D), (B, sum(pooling)) rows laid out
    table by table -> (B, T, D) f32, each table's rows summed in lookup
    order (an add chain, as in :func:`dlrm_embedding_reduce`)."""
    g = table[rows].astype(F32)  # (B, N, D)
    out, col = [], 0
    for p in pooling:
        acc = g[:, col]
        for l in range(col + 1, col + p):
            acc = acc + g[:, l]
        out.append(acc)
        col += p
    return jnp.stack(out, axis=1)


def hash_put(bucket_keys, bucket_ptr, pool, keys, vals, tb, tw, bptr_val, wp):
    """Commit phase of a planned batched PUT (see ``kvstore.plan_put``).

    The state arrays carry their resident zero sentinel row (``KVState``
    layout: bucket arrays (NB+1, ...), pool (NP+1, VW)). tb/tw: (B,)
    target bucket/way (tb == NB = the sentinel, no live bucket write);
    bptr_val: (B,) pool pointer to store; wp: (B,) pool row for the value
    write (wp == NP = the sentinel). Sentinel-targeted payloads are zeroed
    before the scatter so dropped duplicates all write the same zeros —
    deterministic on every backend, and the sentinel row stays zero.
    """
    nb = bucket_keys.shape[0] - 1
    np_ = pool.shape[0] - 1
    drop_b = tb >= nb
    keys = jnp.where(drop_b[:, None], 0, keys)
    bptr_val = jnp.where(drop_b, 0, bptr_val)
    vals = jnp.where((wp >= np_)[:, None], 0, vals)
    bucket_keys = bucket_keys.at[tb, tw].set(keys, mode="drop")
    bucket_ptr = bucket_ptr.at[tb, tw].set(bptr_val, mode="drop")
    pool = pool.at[wp].set(vals, mode="drop")
    return bucket_keys, bucket_ptr, pool


def tx_commit(log, store, batch, values, slot, rows):
    """Fused ORCA-TX replica commit (see ``core.transaction.plan_commit``):
    write-ahead log append + planned store scatter, in one pass.

    log: (LC + 1, TW); store: (NK + 1, VW) — the ``ReplicaState``
    sentinel-resident layout (last row = the zero sentinel). batch:
    (B, TW) raw log records; values: (B, M, VW); slot: (B,) absolute log
    slot (LC = the sentinel); rows: (B*M,) store row per op (NK = the
    sentinel). The plan guarantees live targets are unique, so both
    scatters are conflict-free; sentinel-targeted payloads are zeroed so
    dead duplicates write identical zeros and the sentinel rows stay zero.
    """
    lc = log.shape[0] - 1
    nk = store.shape[0] - 1
    batch = jnp.where((slot >= lc)[:, None], 0, batch)
    vals = values.reshape(-1, values.shape[-1])
    vals = jnp.where((rows >= nk)[:, None], 0, vals)
    log = log.at[slot].set(batch, mode="drop")
    store = store.at[rows].set(vals, mode="drop")
    return log, store


def tx_commit_chain(log, store, batch, values, slot, rows):
    """Whole-chain commit oracle: the batched-over-replicas form of
    :func:`tx_commit` — one dual scatter over the (R, ...) chain arrays
    instead of a per-replica loop, so nothing ever stages a single
    replica's O(state) log/store.

    log: (R, LC + 1, TW); store: (R, NK + 1, VW); batch: (B, TW) and
    values: (B, M, VW) shared by every replica; slot: (R, B) per-replica
    absolute log slot (LC = the sentinel); rows: (B*M,) store row per op
    (NK = the sentinel) shared by every replica, or (R, B*M) per-replica
    rows (chain shortening points a dead replica's ops at its sentinel).
    """
    r = log.shape[0]
    lc = log.shape[1] - 1
    nk = store.shape[1] - 1
    batch_r = jnp.where(
        (slot >= lc)[..., None], 0,
        jnp.broadcast_to(batch[None], (r,) + batch.shape),
    )
    vals = values.reshape(-1, values.shape[-1])
    if rows.ndim == 1:
        rows = jnp.broadcast_to(rows[None], (r, rows.shape[0]))
    vals_r = jnp.where(
        (rows >= nk)[..., None], 0,
        jnp.broadcast_to(vals[None], (r,) + vals.shape),
    )
    ridx = jnp.arange(r)[:, None]
    log = log.at[ridx, slot].set(batch_r, mode="drop")
    store = store.at[ridx, rows].set(vals_r, mode="drop")
    return log, store


def hash_probe(bucket_keys, bucket_ptr, keys, h1, h2):
    """Two-bucket existence probe (the first two of a GET/PUT's memory
    accesses). Returns (found (B,) bool, ptr (B,) int32 — 0 where missed),
    mirroring the Pallas ``hash_probe.probe`` kernel exactly."""
    def one(bids):
        bk = bucket_keys[bids]
        bp = bucket_ptr[bids]
        eq = jnp.all(bk == keys[:, None, :], axis=-1) & (bp >= 0)
        hit = jnp.any(eq, axis=-1)
        ptr = jnp.max(jnp.where(eq, bp, -1), axis=-1)
        return hit, ptr

    hit1, p1 = one(h1)
    hit2, p2 = one(h2)
    found = hit1 | hit2
    ptr = jnp.where(hit1, p1, p2)
    return found, jnp.where(found, ptr, 0)


def cache_probe(cache_keys, cache_vals, cache_meta, keys, cset):
    """Hot-set cache lookup (the VMEM set probe that precedes the bucket
    walk). cache_keys: (CS + 1, CW, KW); cache_vals: (CS + 1, CW, VW);
    cache_meta: (CS + 1, CW) — the sentinel-resident ``KVState`` cache
    layout (meta == 0 marks an empty way, so the zero sentinel row can
    never hit); keys: (B, KW); cset: (B,) set ids.

    Returns (hit (B,) bool, way (B,) int32 — 0 where missed, vals (B, VW)
    — 0 where missed), mirroring ``hash_probe.cache_probe`` exactly: the
    way is the max matching index and the value is that way's line (at
    most one way matches a key — ``kvstore`` admits each key once, so the
    kernel's masked sum over ways selects the same line). The oracle
    gathers only the matching way — the serve path reads one VW-word line,
    not the whole set — and masks misses to zero (way 0's line is the
    gather target but ``hit`` gates it out)."""
    ck = cache_keys[cset]  # (B, CW, KW)
    cm = cache_meta[cset]  # (B, CW)
    eq = jnp.all(ck == keys[:, None, :], axis=-1) & (cm > 0)
    hit = jnp.any(eq, axis=-1)
    cw = cm.shape[1]
    way = jnp.max(jnp.where(eq, jnp.arange(cw, dtype=jnp.int32)[None, :], -1),
                  axis=-1)
    way = jnp.where(hit, way, 0)
    vals = jnp.where(hit[:, None], cache_vals[cset, way], 0)
    return hit, way, vals


def hash_get(bucket_keys, bucket_ptr, pool, keys, h1, h2):
    """Two-bucket probe + value fetch. Returns (vals, found).

    Misses read the pool's resident zero sentinel row (last row), matching
    the Pallas walk — never a live row."""
    found, ptr = hash_probe(bucket_keys, bucket_ptr, keys, h1, h2)
    np_ = pool.shape[0] - 1
    vals = pool[jnp.where(found, jnp.clip(ptr, 0, np_), np_)]
    return jnp.where(found[:, None], vals, 0), found


def paged_attention_stats(q, k_pages, v_pages, page_table, lengths):
    """Online-softmax stats over the paged pool, mirroring the Pallas
    kernel's raw state: (acc = Σ exp(s - m) v, m = row max, l = Σ exp(s - m)).

    q: (B, KVH, G, hd) pre-scaled; pages: (NP, PS, KVH, hd); page_table
    entries < 0 (unmapped) resolve to the last physical page — the pool's
    zero sentinel — matching the kernel's index-map mask. A zero-length
    sequence yields (0, NEG_INF, 0): the empty softmax, safe to LSE-merge.
    """
    b, kvh, g, hd = q.shape
    np_, ps = k_pages.shape[0], k_pages.shape[1]
    maxp = page_table.shape[1]
    pt = jnp.where(page_table < 0, np_ - 1, jnp.clip(page_table, 0, np_ - 1))
    # materialize per-sequence K/V: (B, MaxP*PS, KVH, hd)
    kk = k_pages[pt].reshape(b, maxp * ps, kvh, hd)
    vv = v_pages[pt].reshape(b, maxp * ps, kvh, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q.astype(F32), kk.astype(F32))
    pos = jnp.arange(maxp * ps)[None, :]
    valid = (pos < lengths[:, None])[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    # exp through the mask, not the raw scores: an all-masked row has
    # m == NEG_INF, where exp(s - m) would be exp(0) = 1 per position
    pexp = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(pexp, axis=-1)
    acc = jnp.einsum("bkgs,bskh->bkgh", pexp, vv.astype(F32))
    return acc, m, l


def paged_attention(q, k_pages, v_pages, page_table, lengths):
    """Normalized paged decode attention (stats oracle + final divide)."""
    acc, _, l = paged_attention_stats(q, k_pages, v_pages, page_table, lengths)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def flash_attention(q, k, v, *, window: int = 0):
    """Causal (optionally windowed) attention. q: (B,H,S,hd); k/v GQA."""
    b, h, s, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qf = q.astype(F32).reshape(b, kvh, g, s, hd) * (hd ** -0.5)
    sc = jnp.einsum("bkgqh,bksh->bkgqs", qf, k.astype(F32))
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqs,bksh->bkgqh", p, v.astype(F32))
    return out.reshape(b, h, s, hd).astype(q.dtype)
