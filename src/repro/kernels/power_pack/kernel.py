"""Power-submatrix pack/scatter kernels (the sync path's memory hot-spot).

TPU Pallas has no general dynamic gather, and Mosaic moves HBM data in
(8, 128) tiles: no BlockSpec or DMA may address a single [1, K] row of a
[W, K] matrix.  So the two-step selection is realized TPU-natively:

  - the *row* gather (power words): scalar-prefetched row ids drive the
    BlockSpec index maps, so the DMA engine fetches the aligned 8-row
    tile holding each selected row (``RB`` of them per grid step, one
    operand each) and the row is picked out of its tile with a sublane
    compare-select;
  - the *column* gather (power topics, per row): ``Pk`` compare-select
    passes over the [RB, K] row block — branch-free, exact, no MXU.

The inverse scatter aliases the destination matrix in place and takes
one selected row per grid step, rows sorted (ops.py) so that rows
sharing a tile are consecutive steps: the tile stays resident in VMEM
across them (the first visit adds to the fetched tile, later visits to
the resident output block) and is written back once.

Rows in the matrix's last, partial tile (``row >= W8``, W8 = W rounded
down to 8) are skipped here; the ops layer moves those few rows with XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels as K_

RB = 8          # selected rows per grid step of the gather: one sublane tile
TILE = 8        # rows of the HBM tile a DMA fetches


def _column(x, lane, q):
    """Column q of x [R, Pk] as [R, 1] (compare-select + lane sum)."""
    return jnp.sum(jnp.where(lane == q, x, 0.0), axis=1, keepdims=True)


def _row_of(tile, off):
    """Row ``off`` of an [R, C] tile as [1, C] (sublane compare-select)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(sub == off, tile, 0.0), axis=0, keepdims=True)


def _tile_of(row, n_tiles: int):
    """Index of the aligned tile holding ``row``; tail rows clamp to the
    last full tile (their kernel result is discarded)."""
    return jnp.minimum(row // TILE, n_tiles - 1)


def _pack_kernel(sel_w_ref, sel_k_ref, *refs, n_tiles: int):
    tiles, out_ref = refs[:RB], refs[RB]
    p0 = pl.program_id(0) * RB
    block = jnp.concatenate(
        [_row_of(tiles[j][...],
                 sel_w_ref[p0 + j] - TILE * _tile_of(sel_w_ref[p0 + j],
                                                     n_tiles))
         for j in range(RB)], axis=0)                   # [RB, K]
    sel = sel_k_ref[...].astype(jnp.float32)            # [RB, Pk], exact ints
    iota_k = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1
                                      ).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 1)

    def body(q, acc):
        hit = iota_k == _column(sel, lane, q)           # [RB, K]
        val = jnp.sum(jnp.where(hit, block, 0.0), axis=1, keepdims=True)
        return jnp.where(lane == q, val, acc)

    out_ref[...] = jax.lax.fori_loop(0, sel.shape[1], body,
                                     jnp.zeros(sel.shape, jnp.float32))


def _scatter_add_kernel(sel_w_ref, sel_k_ref, vals_ref, mat_ref, out_ref, *,
                        n_sel: int, n_tiles: int):
    p = pl.program_id(0)
    row = sel_w_ref[p]
    tile = _tile_of(row, n_tiles)
    first = jnp.logical_or(
        p == 0, tile != _tile_of(sel_w_ref[jnp.maximum(p - 1, 0)], n_tiles))
    live = jnp.logical_and(p < n_sel, row < n_tiles * TILE)

    # this step's sel_k / vals row out of the [RB, Pk] blocks
    sel = _row_of(sel_k_ref[...].astype(jnp.float32), p % RB)   # [1, Pk]
    vals = _row_of(vals_ref[...], p % RB)
    shape = mat_ref.shape                                       # [8, K]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, shape, 1
                                      ).astype(jnp.float32)
    on_row = jnp.logical_and(
        jax.lax.broadcasted_iota(jnp.int32, shape, 0) == row - TILE * tile,
        live)
    lane = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 1)

    def body(q, acc):
        hit = jnp.logical_and(on_row, iota_k == _column(sel, lane, q))
        return acc + jnp.where(hit, _column(vals, lane, q), 0.0)

    contrib = jax.lax.fori_loop(0, sel.shape[1], body,
                                jnp.zeros(shape, jnp.float32))

    @pl.when(first)
    def _fetched():
        out_ref[...] = mat_ref[...] + contrib

    @pl.when(jnp.logical_not(first))
    def _resident():
        out_ref[...] += contrib


def pack_rows_pallas(mat_wk: jnp.ndarray, sel_w: jnp.ndarray,
                     sel_k: jnp.ndarray) -> jnp.ndarray:
    """out[p, j] = mat[sel_w[p], sel_k[p, j]] — [P, Pk] packed submatrix,
    for rows below W8 (others come back unspecified).

    Caller guarantees P % RB == 0 (ops.py pads) and W >= TILE.
    """
    P, Pk = sel_k.shape
    W, K = mat_wk.shape
    n_tiles = W // TILE
    tab = pl.BlockSpec((RB, Pk), lambda p, sel_w: (p, 0))
    tiles = [pl.BlockSpec(
        (TILE, K),
        lambda p, sel_w, j=j: (_tile_of(sel_w[p * RB + j], n_tiles), 0))
        for j in range(RB)]
    return pl.pallas_call(
        functools.partial(_pack_kernel, n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(P // RB,),
            in_specs=[tab] + tiles, out_specs=tab),
        out_shape=jax.ShapeDtypeStruct((P, Pk), jnp.float32),
        compiler_params=K_.compiler_params(K_.vmem_budget()),
        interpret=K_.INTERPRET,
    )(sel_w, sel_k, *([mat_wk] * RB))


def scatter_add_rows_pallas(mat_wk: jnp.ndarray, sel_w: jnp.ndarray,
                            sel_k: jnp.ndarray, vals: jnp.ndarray,
                            n_sel: int) -> jnp.ndarray:
    """mat[sel_w[p], sel_k[p, j]] += vals[p, j], in place (aliased), for
    rows below W8.  ``sel_w`` must be sorted ascending (ops.py sorts)
    and padded to a multiple of RB; ``n_sel`` counts the real rows."""
    P, Pk = sel_k.shape
    W, K = mat_wk.shape
    n_tiles = W // TILE
    tab = pl.BlockSpec((RB, Pk), lambda p, sel_w: (p // RB, 0))
    tile = pl.BlockSpec((TILE, K),
                        lambda p, sel_w: (_tile_of(sel_w[p], n_tiles), 0))
    return pl.pallas_call(
        functools.partial(_scatter_add_kernel, n_sel=n_sel, n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(P,),
            in_specs=[tab, tab, tile], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct((W, K), jnp.float32),
        # input indices count the scalar-prefetch operand: sel_w=0, sel_k=1,
        # vals=2, mat=3 -> alias mat onto the (sole) output.
        input_output_aliases={3: 0},
        compiler_params=K_.compiler_params(K_.vmem_budget()),
        interpret=K_.INTERPRET,
    )(sel_w, sel_k, vals, mat_wk)
