"""Pallas row gather/scatter over tile-aligned blocks — the value-row and
log-row accesses shared by the KVS walk (``hash_probe``) and the TX commit
(``tx_commit``).

Mosaic stages a block only when its last two dimensions are multiples of
the (sublane, lane) tiling or equal to the array's own, and a manual DMA
may not slice a row out of a tile either. One row of an ``(N, W)`` array
is neither, so every access here moves the aligned ``(S, W)`` tile that
holds the row (``S`` = :func:`sublanes` of the dtype) and picks or patches
the row inside VMEM:

  ``gather``:  out tile ``i // S`` row ``i % S``  <-  src tile ``idx // S``
               row ``idx % S`` (reads only, any order);
  ``scatter``: dst tile ``idx // S`` staged once, row ``idx % S`` patched
               in VMEM, written back when the walk leaves the tile. The
               targets must be sorted so each tile is visited in one run:
               the read of a tile is then never issued after a write-back
               of the same tile.

The destination is passed to ``pallas_call`` once, as the blocked operand
aliased to the output, and is updated in place: passed a second time, as a
separate read operand, it would make XLA copy the whole destination before
every call so the read could not see the call's own writes. The runtime
keeps a narrow array (a last dimension below 128 lanes) in a compact
layout, so XLA still relayouts it into the row-major tiled layout a
``tpu_custom_call`` takes and back around each call, until the state is
stored lane-dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def sublanes(dtype) -> int:
    """Rows of one native VMEM tile: 8 for 32-bit, 16 for 16-bit dtypes."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _gather_kernel(idx_ref, src_ref, out_ref):
    i = pl.program_id(0)
    s_in, s_out = src_ref.shape[0], out_ref.shape[0]
    out_ref[pl.ds(i % s_out, 1), :] = src_ref[pl.ds(idx_ref[i] % s_in, 1), :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather(src, idx, *, interpret: bool = True):
    """src: (N, W); idx: (B,) int32 in [0, N). Returns src[idx], (B, W)."""
    b = idx.shape[0]
    w = src.shape[1]
    s = sublanes(src.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((s, w), lambda i, idx: (idx[i] // s, 0))],
        out_specs=pl.BlockSpec((s, w), lambda i, idx: (i // s, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w), src.dtype),
        interpret=interpret,
    )(idx, src)


def _scatter_kernel(idx_ref, vals_ref, tile_ref, out_ref):
    k, i = pl.program_id(0), pl.program_id(1)
    b = pl.num_programs(1)
    s, sv = out_ref.shape[1], vals_ref.shape[1]
    row = idx_ref[k * b + i]
    prev = idx_ref[k * b + jnp.maximum(i - 1, 0)]

    @pl.when(jnp.logical_or(i == 0, row // s != prev // s))
    def _():
        out_ref[...] = tile_ref[...]

    out_ref[0, pl.ds(row % s, 1), :] = vals_ref[0, pl.ds(i % sv, 1), :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter(dst, vals, idx, *, interpret: bool = True):
    """dst: (R, N, W); vals: (R, B, W); idx: (R, B) int32, each replica's
    row list sorted ascending. Writes ``dst[r, idx[r, i]] = vals[r, i]``
    in place (a repeated row keeps its last value) and returns dst.

    ``dst`` is the one operand aliased to the output: its tile is read by
    the first entry of a run and written back when the run ends, and the
    sorted targets keep every tile in one run, so no tile is read after
    its own write-back."""
    r, n, w = dst.shape
    b = idx.shape[1]
    s = sublanes(dst.dtype)
    tile = pl.BlockSpec((1, s, w), lambda k, i, idx: (k, idx[k * b + i] // s, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, b),
        in_specs=[
            pl.BlockSpec((1, s, w), lambda k, i, idx: (k, i // s, 0)),
            tile,  # dst, aliased to the output
        ],
        out_specs=tile,
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        # aliases index the full pallas_call operand list (prefetch included)
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.reshape(r * b), vals, dst)
