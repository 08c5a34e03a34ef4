"""Pallas kernel: decode attention over a paged KV cache.

The serving engine's KV cache is the "large working set in server memory"
of the paper; the page table is the data-structure walker's index. The grid
walks (batch, page): page ``p+1`` of a sequence is DMA'd HBM→VMEM while
page ``p`` is being reduced (online softmax), the same
memory-level-parallelism pattern as the other walkers. A page moves whole,
all KV heads at once — its block ``(PS, KVH, hd)`` ends in the pool's own
trailing dims, which is what Mosaic can stage — and the MXU sees one
(KVH·G, hd) × (hd, PS·KVH) matmul per page, masked to each query head's
own KV head (GQA groups ride along their KV head).

The kernel emits its raw online-softmax state — unnormalized accumulator
``acc = Σ exp(s - m) v``, row max ``m``, and normalizer ``l = Σ exp(s - m)``
— so callers can either normalize (:func:`paged_attention`) or LSE-merge
the stats with contributions the pool does not hold yet
(:func:`paged_attention_stats`): the read-only decode path attends over the
*stale* pool and folds the current token's fresh k/v in afterwards, which
is what lets the layer scan stop carrying the pool entirely.

Dead page-table entries (-1, or pages past the sequence length) are masked
in the scalar-prefetch index map: they resolve to the **last physical
page** — the pool's zero sentinel when the caller allocates one
(``serving.kv_cache.make`` does) — rather than silently refetching live
page 0. Compute for dead pages is skipped either way via the length mask;
the index-map mask keeps the dead DMA off other sequences' live data.
A zero-length sequence yields (acc=0, m=NEG_INF, l=0), the empty online
softmax, which merges safely.

Operand memory spaces come from ``core.placement.block_spaces`` — the
per-region TPH/DDIO decision applied at kernel construction time: the tiny
q/output blocks and the per-step staged KV page are VMEM-tier (hot,
touched every grid step); the pool itself stays compiler-placed with the
index map doing the explicit page DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import placement

NEG_INF = -1e30


def _kernel(pt_ref, len_ref, q_ref, qh_ref, kh_ref, kp_ref, k_ref, v_ref,
            acc_out, m_out, l_out, m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)
    _, ps, kvh, hd = k_ref.shape

    @pl.when(p == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    page_start = p * ps
    live = page_start < length

    @pl.when(live)
    def _():
        q = q_ref[0]  # (KVH*G, hd) f32, row = (kv head, group)
        # the page's (PS, KVH, hd) block flattened to (PS*KVH, hd) rows,
        # column c = (position c // KVH, kv head c % KVH)
        k = k_ref[0].astype(jnp.float32).reshape(ps * kvh, hd)
        v = v_ref[0].astype(jnp.float32).reshape(ps * kvh, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        # every query row scores every head's keys; keep its own head's
        mask = (qh_ref[...] == kh_ref[...]) & (page_start + kp_ref[...] < length)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + pexp @ v
        m_ref[...] = m_new

    @pl.when(p == np_ - 1)
    def _():
        acc_out[0] = acc_ref[...]
        m_out[0] = m_ref[...]
        l_out[0] = l_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_stats(q, k_pages, v_pages, page_table, lengths, *,
                          interpret: bool = True):
    """q: (B, KVH, G, hd) pre-scaled; pages: (NP, PS, KVH, hd);
    page_table: (B, MaxP) int32, -1 = unmapped; lengths: (B,).
    Returns online-softmax stats over the first ``lengths`` pool tokens:
    (acc (B, KVH, G, hd), m (B, KVH, G), l (B, KVH, G)), all f32.
    """
    b, kvh, g, hd = q.shape
    n_pages, ps = k_pages.shape[0], k_pages.shape[1]
    maxp = page_table.shape[1]
    rows = kvh * g
    cols = ps * kvh

    def pt_idx(bb, p, pt, ln):
        # dead entries (-1 / past the sequence length) resolve to the last
        # physical page — the zero sentinel when the pool allocates one —
        # instead of refetching live page 0; compute is skipped regardless.
        page = pt[bb, p]
        dead = (page < 0) | (p * ps >= ln[bb])
        return (jnp.where(dead, n_pages - 1, jnp.clip(page, 0, n_pages - 1)),
                0, 0, 0)

    # head of each query row / of each flattened key column, and the key
    # column's position in its page
    q_head = (jnp.arange(rows, dtype=jnp.int32) // g)[:, None]
    col = jnp.arange(cols, dtype=jnp.int32)[None, :]
    k_head, k_pos = col % kvh, col // kvh

    sp = placement.block_spaces(
        {
            "q": rows * hd * 4,
            "ids": (rows + 2 * cols) * 4,
            "page": cols * hd * k_pages.dtype.itemsize,
            "out": rows * (hd + 2) * 4,
        }
    )
    whole = lambda shape: pl.BlockSpec(
        shape, lambda bb, p, pt, ln: (0,) * len(shape), memory_space=sp["ids"])
    per_seq = lambda w: pl.BlockSpec(
        (1, rows, w), lambda bb, p, pt, ln: (bb, 0, 0), memory_space=sp["out"])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, lengths
        grid=(b, maxp),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda bb, p, pt, ln: (bb, 0, 0),
                         memory_space=sp["q"]),
            whole((rows, 1)),
            whole((1, cols)),
            whole((1, cols)),
            pl.BlockSpec((1, ps, kvh, hd), pt_idx, memory_space=sp["page"]),
            pl.BlockSpec((1, ps, kvh, hd), pt_idx, memory_space=sp["page"]),
        ],
        out_specs=[per_seq(hd), per_seq(1), per_seq(1)],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
        ),
        interpret=interpret,
    )(page_table, lengths, q.astype(jnp.float32).reshape(b, rows, hd),
      q_head, k_head, k_pos, k_pages, v_pages)
    return (acc.reshape(b, kvh, g, hd), m.reshape(b, kvh, g),
            l.reshape(b, kvh, g))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    interpret: bool = True):
    """Normalized paged decode attention (the stats kernel + final divide).
    Returns (B, KVH, G, hd) f32."""
    acc, _, l = paged_attention_stats(
        q, k_pages, v_pages, page_table, lengths, interpret=interpret
    )
    return acc / jnp.maximum(l, 1e-30)[..., None]
