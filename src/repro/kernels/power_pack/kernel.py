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

The inverse scatter aliases the destination matrix in place and walks
*tile visits*, one grid step each: a visit is an aligned 8-row tile and
at most one selected row per sublane (``row % 8``), given as an [8, Pk]
block of topic ids and one of values (ids outside [0, K) on sublanes
with no row).  The step builds the tile's [8, K] delta with every
sublane live, one compare-select-add over the tile per power-topic
column (static lane slices, no loop-carried lane reduction), and adds
it.  A row selected twice takes a second visit of its tile.  Visits of
one tile are consecutive steps (ops.py lays them out from the sorted
rows), so the tile stays resident in VMEM across them (the first visit
fetches it, later ones add to the resident output block) and is written
back once; a step with no row leaves its tile as it is.

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


def _scatter_add_kernel(visit_ref, ids_ref, vals_ref, mat_ref, out_ref):
    v = pl.program_id(0)
    code = visit_ref[v]
    first = jnp.logical_or(
        v == 0, code // 2 != visit_ref[jnp.maximum(v - 1, 0)] // 2)

    @pl.when(first)
    def _fetched():
        out_ref[...] = mat_ref[...]

    @pl.when(code % 2 == 1)
    def _apply():
        ids = ids_ref[...]                                      # [8, Pk]
        vals = vals_ref[...]
        iota_k = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        delta = jnp.zeros(out_ref.shape, jnp.float32)           # [8, K]
        for j in range(ids.shape[1]):
            delta += jnp.where(iota_k == ids[:, j:j + 1], vals[:, j:j + 1],
                               0.0)
        out_ref[...] += delta


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


def scatter_add_rows_pallas(mat_wk: jnp.ndarray, visit: jnp.ndarray,
                            ids: jnp.ndarray, vals: jnp.ndarray
                            ) -> jnp.ndarray:
    """Apply tile visits to ``mat_wk`` [W, K] in place (aliased).

    Visit ``v`` adds ``vals[8v + s, j]`` at row ``8 * tile + s``, column
    ``ids[8v + s, j]`` (ids outside [0, K) add nothing), where ``visit[v]
    = 2 * tile + live``; a visit that is not live leaves its tile as it
    is.  Visits of one tile must be consecutive (ops.py orders them)."""
    V = visit.shape[0]
    Pk = ids.shape[1]
    W, K = mat_wk.shape
    tab = pl.BlockSpec((TILE, Pk), lambda v, visit: (v, 0))
    tile = pl.BlockSpec((TILE, K), lambda v, visit: (visit[v] // 2, 0))
    return pl.pallas_call(
        _scatter_add_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(V,),
            in_specs=[tab, tab, tile], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct((W, K), jnp.float32),
        # input indices count the scalar-prefetch operand: visit=0, ids=1,
        # vals=2, mat=3 -> alias mat onto the (sole) output.
        input_output_aliases={3: 0},
        compiler_params=K_.compiler_params(K_.vmem_budget()),
        interpret=K_.INTERPRET,
    )(visit, ids, vals, mat_wk)
