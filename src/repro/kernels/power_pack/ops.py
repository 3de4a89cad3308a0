"""jit'd wrappers for power_pack: row padding, ordering and the tail rows.

The gather pads its selected rows to a multiple of ``RB`` (padded rows
select nothing).  The scatter sorts its entries by row and lays them out
as *tile visits* (kernel.py): the k-th entry of a row goes to its 8-row
tile's k-th visit, at sublane ``row % 8``, so a visit holds at most one
entry per sublane and a repeated row takes one more visit of the same
tile.  A tile's visits take the grid steps of its run of sorted entries,
so they are consecutive and the tile stays in VMEM across them; a step
with no entry adds nothing.  The layout is index arithmetic and gathers
over the [P] and [P, Pk] entries, never a scatter into [W, K].  The
matrix itself is passed through untouched (no [W, K] copy per call).
A topic id outside [0, K) adds nothing: the kernel's compare matches no
column and the tail's scatter drops it.  That is how a topic shard's
selection passes the slots other shards own (the sentinel ``K``).

Rows in the matrix's last, partial 8-row tile (``row >= W8``, W8 = W
rounded down to 8), which the kernels cannot address: the gather takes
them from one XLA gather; the scatter's entries there sort last and are
added to those (at most 7) rows alone, in windows of as many entries,
and the rows are written back in place.  A matrix of fewer than 8 rows
has no full tile and takes XLA alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import power as pw
from repro.kernels import pad_axis as _pad_axis
from repro.kernels.power_pack.kernel import (RB, TILE, pack_rows_pallas,
                                             scatter_add_rows_pallas)


def _tail(sel_w, n_rows: int):
    """Mask of selected rows inside the matrix's partial last tile."""
    return sel_w >= n_rows // TILE * TILE


@jax.jit
def pack_rows(mat_wk: jnp.ndarray, sel_w: jnp.ndarray,
              sel_k: jnp.ndarray) -> jnp.ndarray:
    P, Pk = sel_k.shape
    W = mat_wk.shape[0]
    mat = mat_wk.astype(jnp.float32)
    if W < TILE:
        return pw.pack_rows(mat, sel_w, sel_k).astype(mat_wk.dtype)
    out = pack_rows_pallas(mat, _pad_axis(sel_w.astype(jnp.int32), 0, RB),
                           _pad_axis(sel_k.astype(jnp.int32), 0, RB))[:P]
    if W % TILE:
        tail = _tail(sel_w, W)
        out = jnp.where(tail[:, None],
                        pw.pack_rows(mat, jnp.where(tail, sel_w, 0), sel_k),
                        out)
    return out.astype(mat_wk.dtype)


def _run_start(x):
    """Index of the first entry of each entry's run of equal values in
    ``x`` [P] (sorted)."""
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    new = jnp.concatenate([jnp.ones((1,), bool), x[1:] != x[:-1]])
    return jax.lax.cummax(jnp.where(new, pos, 0))


def _tile_visits(rows: jnp.ndarray, n_rows: int):
    """The tile-visit layout of sorted rows ``rows`` [P] of an
    ``n_rows``-row matrix: ``visit`` [P], ``2 * tile + live`` per grid
    step, and ``src`` [8P], the entry each (step, sublane) applies, or P
    for none.  Rows in the partial last tile get no entry."""
    P = rows.shape[0]
    pos = jnp.arange(P, dtype=jnp.int32)
    tile = rows // TILE
    step = _run_start(tile) + pos - _run_start(rows)
    slot = jnp.where(rows < n_rows // TILE * TILE, step * TILE + rows % TILE,
                     P * TILE)
    src = jnp.full((P * TILE,), P, jnp.int32).at[slot].set(pos, mode="drop")
    live = jnp.any(src.reshape(P, TILE) < P, axis=1).astype(jnp.int32)
    return 2 * jnp.minimum(tile, n_rows // TILE - 1) + live, src


def _add_tail(out, rows, ks, vs):
    """Add the entries of rows in ``out``'s partial last tile (they sort
    last in ``rows``) to those rows alone; ``ks``/``vs`` carry at least
    ``W % 8`` padding entries past the real ones."""
    W, Pk = out.shape[0], ks.shape[1]
    W8, n = W // TILE * TILE, W % TILE
    n_body = jnp.sum(rows < W8, dtype=jnp.int32)
    at_row = jnp.concatenate([rows - W8, jnp.full((n,), n, jnp.int32)])

    def window(i, tail):
        at = n_body + i * n
        r = jax.lax.dynamic_slice(at_row, (at,), (n,))
        k = jax.lax.dynamic_slice(ks, (at, 0), (n, Pk))
        x = jax.lax.dynamic_slice(vs, (at, 0), (n, Pk))
        return tail.at[r[:, None], k].add(x, mode="drop")

    n_win = (rows.shape[0] - n_body + n - 1) // n
    return jax.lax.dynamic_update_slice(
        out, jax.lax.fori_loop(0, n_win, window, out[W8:]), (W8, 0))


@jax.jit
def scatter_add_rows(mat_wk: jnp.ndarray, sel_w: jnp.ndarray,
                     sel_k: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    P, Pk = sel_k.shape
    W = mat_wk.shape[0]
    mat = mat_wk.astype(jnp.float32)
    vals = vals.astype(jnp.float32)
    if W < TILE:
        return pw.scatter_add_rows(mat, sel_w, sel_k, vals).astype(
            mat_wk.dtype)
    order = jnp.argsort(sel_w)        # stable: equal rows keep their order
    rows = sel_w.astype(jnp.int32)[order]
    # entries P and on: an id that matches no column, a zero value
    pad = max(1, W % TILE)
    ks = jnp.concatenate([sel_k.astype(jnp.int32)[order],
                          jnp.full((pad, Pk), -1, jnp.int32)])
    vs = jnp.concatenate([vals[order], jnp.zeros((pad, Pk), jnp.float32)])
    visit, src = _tile_visits(rows, W)
    out = scatter_add_rows_pallas(mat, visit, ks[src], vs[src])
    if W % TILE:
        out = _add_tail(out, rows, ks, vs)
    return out.astype(mat_wk.dtype)
