"""Pallas kernels: batched hash-table GET walk + PUT commit (ORCA-KV §IV-A).

The APU's data-structure walker does three dependent memory accesses per GET
(primary bucket, overflow bucket, value row) and four per PUT. On TPU the
GET walk splits into two pipelined passes, each a scalar-prefetch gather so
the next request's bucket is in flight while the current one is compared:

  pass 1 (``probe``):  buckets in, resolved pool pointer + found flag out
  pass 2 (``fetch``):  value rows gathered at the resolved pointers

The PUT commit (``insert``) is the scatter mirror: the jitted wrapper plans
the batch (hashes, dedupe, way ranking — ALU work; see
``kvstore.plan_put``), then two scalar-prefetch scatter passes stream the
planned writes through VMEM with ``input_output_aliases`` so untouched rows
stay where they are. Each destination is passed to its ``pallas_call``
once, as the blocked operand aliased to the output, and updated in place
(a second, read-only pass of the same array would make XLA copy all of it
before every call). The narrow bucket arrays are still relayouted by XLA
around each call, from the runtime's compact layout into the row-major
one a ``tpu_custom_call`` takes and back, until the state is lane-dense:

  pass 1 (``commit_buckets``): bucket rows gathered at the target bucket,
      the chosen way overwritten in VMEM, written back in place — entries
      are pre-sorted by target bucket so same-bucket writers share one
      staged block (the DDIO-style "hot line stays in cache" path);
  pass 2 (``write_rows``):     value rows streamed to their pool slots
      (``rows.scatter``).

Single rows of 2-D arrays (a query key, a bucket's pointer row, a pool
row) move as the aligned tile that holds them — the layout Mosaic can
stage on a TPU (see ``rows``).

Dropped/no-op entries target the state's **resident** zero sentinel row
(the ``mode="drop"`` analogue): ``KVState`` permanently carries one pad
row past the live extent — the same convention as the page pool's zero
sentinel page (``serving.kv_cache``) and the TX log/store pad rows
(``kernels.tx_commit``) — so these wrappers never concatenate or strip an
O(state) padded copy per call; sentinel-targeted payloads are zeroed and
the sort order comes precomputed from ``kvstore.plan_put``. Operand
memory spaces come from ``core.placement`` — the per-region TPH decision
applied at kernel construction time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import placement
from repro.kernels import rows


# Placement-fed BlockSpec memory spaces: per-step staged blocks are
# small + hot (every grid step touches them).
_spaces = placement.block_spaces

# A bucket's (W, KW) keys and a cache set's lines are whole trailing dims
# of their arrays; a request's key, a bucket's pointer row and a set's
# meta row are single rows of 2-D arrays, so they move as the (_S, width)
# tile holding them and the kernel picks row ``index % _S``.
_S = rows.sublanes(jnp.int32)


def _col(row):
    """(1, W) -> (W, 1) by a diagonal mask: a transpose Mosaic lowers as
    broadcasts and a lane reduction."""
    w = row.shape[1]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    return jnp.max(jnp.where(diag, row, jnp.iinfo(jnp.int32).min), axis=1,
                   keepdims=True)


def _match(ways_keys, q):
    """(W, KW) == (1, KW) on every key word -> (W, 1) bool."""
    return jnp.min(jnp.where(ways_keys == q, 1, 0), axis=1, keepdims=True) > 0


def _bucket_ptr(bk, bp_row, q):
    """Matched live pointer of one bucket as (1, 1); -1 on a miss."""
    bp = _col(bp_row)
    eq = _match(bk, q) & (bp >= 0)
    return jnp.max(jnp.where(eq, bp, -1), axis=0, keepdims=True)


def _probe_kernel(h1_ref, h2_ref, keys_ref, bk1_ref, bp1_ref, bk2_ref, bp2_ref,
                  out_ref):
    i = pl.program_id(0)
    q = keys_ref[pl.ds(i % _S, 1), :]  # (1, KW)
    p1 = _bucket_ptr(bk1_ref[0], bp1_ref[pl.ds(h1_ref[i] % _S, 1), :], q)
    p2 = _bucket_ptr(bk2_ref[0], bp2_ref[pl.ds(h2_ref[i] % _S, 1), :], q)
    found = (p1 >= 0) | (p2 >= 0)
    ptr = jnp.where(found, jnp.where(p1 >= 0, p1, p2), 0)  # (1, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
    out_ref[pl.ds(i % _S, 1), :] = jnp.where(lane == 0, found.astype(jnp.int32),
                                             ptr)


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe(bucket_keys, bucket_ptr, keys, h1, h2, *, interpret: bool = True):
    """bucket_keys: (NB + 1, W, KW); bucket_ptr: (NB + 1, W) — the
    sentinel-resident ``KVState`` layout (h1/h2 only ever index the NB
    live rows); keys: (B, KW); h1/h2: (B,) bucket ids.
    Returns (found (B,) bool, ptr (B,) int32)."""
    b = keys.shape[0]
    w, kw = bucket_keys.shape[1], bucket_keys.shape[2]
    sp = _spaces(
        {"query": _S * kw * 4, "bucket": w * kw * 4, "bptr": _S * w * 4,
         "out": _S * 8}
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # h1, h2
        grid=(b,),
        in_specs=[
            pl.BlockSpec((_S, kw), lambda i, h1, h2: (i // _S, 0),
                         memory_space=sp["query"]),
            pl.BlockSpec((1, w, kw), lambda i, h1, h2: (h1[i], 0, 0),
                         memory_space=sp["bucket"]),
            pl.BlockSpec((_S, w), lambda i, h1, h2: (h1[i] // _S, 0),
                         memory_space=sp["bptr"]),
            pl.BlockSpec((1, w, kw), lambda i, h1, h2: (h2[i], 0, 0),
                         memory_space=sp["bucket"]),
            pl.BlockSpec((_S, w), lambda i, h1, h2: (h2[i] // _S, 0),
                         memory_space=sp["bptr"]),
        ],
        out_specs=pl.BlockSpec((_S, 2), lambda i, h1, h2: (i // _S, 0),
                               memory_space=sp["out"]),
    )
    out = pl.pallas_call(
        _probe_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 2), jnp.int32),
        interpret=interpret,
    )(h1, h2, keys, bucket_keys, bucket_ptr, bucket_keys, bucket_ptr)
    return out[:, 0].astype(bool), out[:, 1]


def _cache_probe_kernel(cset_ref, keys_ref, ck_ref, cv_ref, cm_ref,
                        hw_ref, val_ref):
    i = pl.program_id(0)
    q = keys_ref[pl.ds(i % _S, 1), :]  # (1, KW)
    cm = _col(cm_ref[pl.ds(cset_ref[i] % _S, 1), :])  # (CW, 1)
    eq = _match(ck_ref[0], q) & (cm > 0)  # (CW, 1)
    way_ids = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 0)
    way = jnp.max(jnp.where(eq, way_ids, -1), axis=0, keepdims=True)  # (1, 1)
    hit = way >= 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
    hw_ref[pl.ds(i % _S, 1), :] = jnp.where(lane == 0, hit.astype(jnp.int32),
                                            jnp.maximum(way, 0))
    # masked sum over ways: at most one way matches (kvstore admits each
    # key once), so the sum IS the matched value — and zero on a miss
    val_ref[pl.ds(i % _S, 1), :] = jnp.sum(jnp.where(eq, cv_ref[0], 0), axis=0,
                                           keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_probe(cache_keys, cache_vals, cache_meta, keys, cset, *,
                interpret: bool = True):
    """Hot-set cache lookup: one scalar-prefetch VMEM set probe per request
    — the access that precedes (and on a hit replaces) the bucket walk.

    cache_keys: (CS + 1, CW, KW); cache_vals: (CS + 1, CW, VW);
    cache_meta: (CS + 1, CW) — the sentinel-resident ``KVState`` cache
    layout (cset only ever indexes the CS live rows; meta == 0 marks an
    empty way so the zero sentinel can never hit); keys: (B, KW);
    cset: (B,) set ids. Returns (hit (B,) bool, way (B,) int32,
    vals (B, VW) — way/vals zero where missed)."""
    b, kw = keys.shape
    cw, vw = cache_vals.shape[1], cache_vals.shape[2]
    sp = _spaces(
        {"query": _S * kw * 4, "cset_keys": cw * kw * 4,
         "cset_vals": cw * vw * 4, "cset_meta": _S * cw * 4,
         "out_hw": _S * 8, "out_val": _S * vw * 4}
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # cset
        grid=(b,),
        in_specs=[
            pl.BlockSpec((_S, kw), lambda i, cset: (i // _S, 0),
                         memory_space=sp["query"]),
            pl.BlockSpec((1, cw, kw), lambda i, cset: (cset[i], 0, 0),
                         memory_space=sp["cset_keys"]),
            pl.BlockSpec((1, cw, vw), lambda i, cset: (cset[i], 0, 0),
                         memory_space=sp["cset_vals"]),
            pl.BlockSpec((_S, cw), lambda i, cset: (cset[i] // _S, 0),
                         memory_space=sp["cset_meta"]),
        ],
        out_specs=[
            pl.BlockSpec((_S, 2), lambda i, cset: (i // _S, 0),
                         memory_space=sp["out_hw"]),
            pl.BlockSpec((_S, vw), lambda i, cset: (i // _S, 0),
                         memory_space=sp["out_val"]),
        ],
    )
    hw, vals = pl.pallas_call(
        _cache_probe_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, 2), jnp.int32),
                   jax.ShapeDtypeStruct((b, vw), cache_vals.dtype)],
        interpret=interpret,
    )(cset, keys, cache_keys, cache_vals, cache_meta)
    return hw[:, 0].astype(bool), hw[:, 1], vals


def fetch(pool, ptr, *, interpret: bool = True):
    """pool: (NP + 1, VW), row NP = the zero sentinel; ptr: (B,) int32
    (pre-clamped — misses resolve to the sentinel row). Returns (B, VW)."""
    return rows.gather(pool, ptr, interpret=interpret)


def get(state_bucket_keys, state_bucket_ptr, state_pool, keys, h1, h2, *,
        interpret: bool = True):
    """Full GET walk. Returns (vals (B, VW), found (B,)).

    Misses fetch the pool's resident zero sentinel row (never a live row —
    the page pool's dead-walk convention); hits are always in live range."""
    found, ptr = probe(
        state_bucket_keys, state_bucket_ptr, keys, h1, h2, interpret=interpret
    )
    np_ = state_pool.shape[0] - 1
    ptr_safe = jnp.where(found, jnp.clip(ptr, 0, np_), np_)
    vals = fetch(state_pool, ptr_safe, interpret=interpret)
    return jnp.where(found[:, None], vals, 0), found


def _commit_kernel(tb_ref, tw_ref, pv_ref, key_ref, bk_ref, bp_ref, ko_ref,
                   po_ref):
    i = pl.program_id(0)
    tb, prev = tb_ref[i], tb_ref[jnp.maximum(i - 1, 0)]
    # first writer of a bucket (of a pointer tile) stages the current block;
    # later writers (consecutive after the wrapper's sort) reuse the VMEM copy
    @pl.when(jnp.logical_or(i == 0, tb != prev))
    def _():
        ko_ref[...] = bk_ref[...]

    @pl.when(jnp.logical_or(i == 0, tb // _S != prev // _S))
    def _():
        po_ref[...] = bp_ref[...]

    w = ko_ref.shape[1]
    q = key_ref[pl.ds(i % _S, 1), :]  # (1, KW)
    wsel = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0) == tw_ref[i]
    ko_ref[0] = jnp.where(wsel, q, ko_ref[0])
    r = pl.ds(tb % _S, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    po_ref[r, :] = jnp.where(lane == tw_ref[i], pv_ref[i], po_ref[r, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit_buckets(bucket_keys, bucket_ptr, keys, tb, tw, bptr_val, *,
                   interpret: bool = True):
    """Scatter pass 1: set way ``tw[i]`` of bucket row ``tb[i]`` to
    (keys[i], bptr_val[i]). ``bucket_keys``/``bucket_ptr`` carry their
    resident sentinel pad row at index NB that absorbs dropped entries
    (payloads pre-zeroed by ``insert``); ``tb`` must be sorted (the plan
    sorts) so duplicate buckets — and pointer tiles — are consecutive.

    ``bucket_keys`` and ``bucket_ptr`` are each passed once, as the blocked
    operand aliased to its output, and updated in place: a bucket block
    (pointer tile) is read by the first writer of its run and written back
    when the run ends, and the sort keeps every block in one run, so no
    block is read after its own write-back."""
    b, kw = keys.shape
    w = bucket_ptr.shape[1]
    sp = _spaces(
        {"key": _S * kw * 4, "bucket": w * kw * 4, "bptr": _S * w * 4}
    )
    bucket = pl.BlockSpec((1, w, kw), lambda i, tb, tw, pv: (tb[i], 0, 0),
                          memory_space=sp["bucket"])
    bptr = pl.BlockSpec((_S, w), lambda i, tb, tw, pv: (tb[i] // _S, 0),
                        memory_space=sp["bptr"])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tb, tw, bptr_val
        grid=(b,),
        in_specs=[
            pl.BlockSpec((_S, kw), lambda i, tb, tw, pv: (i // _S, 0),
                         memory_space=sp["key"]),
            bucket,  # bucket_keys, aliased to output 0
            bptr,  # bucket_ptr, aliased to output 1
        ],
        out_specs=[bucket, bptr],
    )
    return pl.pallas_call(
        _commit_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(bucket_keys.shape, bucket_keys.dtype),
            jax.ShapeDtypeStruct(bucket_ptr.shape, bucket_ptr.dtype),
        ],
        # aliases index the full pallas_call operand list (prefetch included)
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(tb, tw, bptr_val, keys, bucket_keys, bucket_ptr)


def write_rows(pool, vals, wp, *, interpret: bool = True):
    """Scatter pass 2: stream value row ``vals[i]`` to pool row ``wp[i]``
    (``wp`` sorted). ``pool`` carries its resident sentinel pad row at
    index NP for no-write entries (payloads pre-zeroed by ``insert``)."""
    return rows.scatter(pool[None], vals[None], wp[None],
                        interpret=interpret)[0]


def insert(state_bucket_keys, state_bucket_ptr, state_pool, keys, vals,
           tb, tw, bptr_val, wp, bucket_order=None, row_order=None, *,
           interpret: bool = True):
    """Full planned PUT commit (see ``kvstore.plan_put`` for the plan).

    The state arrays arrive in the sentinel-resident ``KVState`` layout
    ((NB+1)-bucket / (NP+1)-pool rows), so no padded copy is materialized:
    dropped entries (tb == NB / wp == NP) scatter zeroed payloads onto the
    resident sentinel row, entries issue in target-sorted order so
    duplicate targets share a staged VMEM block (``bucket_order`` /
    ``row_order`` come precomputed from the plan; recomputed here only for
    direct calls), and the aliased scatter passes update the state in
    place: each state array is passed to its call once. XLA still
    relayouts the narrow bucket arrays around the call until the state is
    lane-dense. Returns (bucket_keys, bucket_ptr, pool), same shapes in as
    out.
    """
    nb = state_bucket_keys.shape[0] - 1
    np_ = state_pool.shape[0] - 1
    keys = jnp.where((tb >= nb)[:, None], 0, keys)
    bptr_val = jnp.where(tb >= nb, 0, bptr_val)
    vals = jnp.where((wp >= np_)[:, None], 0, vals)
    ob = jnp.argsort(tb, stable=True) if bucket_order is None else bucket_order
    op = jnp.argsort(wp, stable=True) if row_order is None else row_order
    bk, bp = commit_buckets(
        state_bucket_keys, state_bucket_ptr, keys[ob], tb[ob], tw[ob],
        bptr_val[ob], interpret=interpret,
    )
    pool = write_rows(state_pool, vals[op], wp[op], interpret=interpret)
    return bk, bp, pool
