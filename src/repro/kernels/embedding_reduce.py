"""Pallas kernel: gather + segment-sum embedding reduction (ORCA-DLRM §IV-C).

The APU's "64 outstanding memory requests per query" becomes TPU software
pipelining: the grid walks the (pre-sorted) index list, the table row for
step ``i+1`` is DMA'd HBM→VMEM while step ``i`` accumulates — Pallas's
BlockSpec pipeline emitter provides the double buffering. The output block
index follows the *segment* id; consecutive steps hitting the same segment
keep the accumulator resident in VMEM (the DDIO-style "hot line stays in
cache" path of C4).

Rows move as aligned tiles — Mosaic cannot stage a single row of an
``(R, D)`` array — so step ``i`` brings in the ``(S, D)`` table tile that
holds row ``idx[i]`` and adds that row into output row ``seg_ids[i]`` of
the resident ``(S, D)`` output tile (``S`` = ``rows.sublanes``). The tile
is widened to f32 in a VMEM scratch first: Mosaic cannot load one row of a
16-bit tile at a dynamic offset (two rows share a sublane), so a bf16
table's row is picked from the f32 copy.

The ids and segment ids are prefetched into the 1 MiB of scalar memory,
8 bytes a lookup: a call takes fewer than 131,072 lookups (a DLRM-DCNv2 step of
256 samples has 54,784; ``tests/test_tpu_compile.py`` compiles it).

Requirements: ``seg_ids`` must be non-decreasing (the natural (b, t, l)
query layout already is), so each output tile is visited in one run and
zeroed when the run starts. Output rows of segments with no entries are
left unwritten — ``ops.embedding_reduce`` zeroes them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import rows


def _kernel(idx_ref, seg_ref, table_ref, out_ref, tile_ref):
    i = pl.program_id(0)
    s_in, s_out = table_ref.shape[0], out_ref.shape[0]
    seg = seg_ref[i]
    tile_start = jnp.logical_or(
        i == 0, seg // s_out != seg_ref[jnp.maximum(i - 1, 0)] // s_out
    )

    @pl.when(tile_start)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    r = pl.ds(seg % s_out, 1)
    tile_ref[...] = table_ref[...].astype(tile_ref.dtype)
    out_ref[r, :] = out_ref[r, :] + tile_ref[pl.ds(idx_ref[i] % s_in, 1), :]


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def embedding_reduce(table, idx, seg_ids, num_segments: int, *, interpret: bool = True):
    """table: (R, D); idx: (N,) int32 rows; seg_ids: (N,) int32 sorted.

    Returns (num_segments, D) f32 segment sums (rows of empty segments
    unspecified).
    """
    n = idx.shape[0]
    d = table.shape[1]
    s_in, s_out = rows.sublanes(table.dtype), rows.sublanes(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # idx, seg_ids
        grid=(n,),
        in_specs=[
            pl.BlockSpec((s_in, d), lambda i, idx_ref, seg_ref: (idx_ref[i] // s_in, 0)),
        ],
        out_specs=pl.BlockSpec(
            (s_out, d), lambda i, idx_ref, seg_ref: (seg_ref[i] // s_out, 0)
        ),
        scratch_shapes=[pltpu.VMEM((s_in, d), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        interpret=interpret,
    )(idx, seg_ids, table)
