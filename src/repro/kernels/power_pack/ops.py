"""jit'd wrappers for power_pack: row padding, ordering and the tail rows.

Selected rows pad to a multiple of ``RB`` (padded rows select nothing);
the scatter takes its rows sorted so that rows sharing an 8-row tile are
consecutive grid steps.  The matrix itself is passed through untouched
(no [W, K] copy per call).  Rows in the matrix's last, partial 8-row tile
— which the kernels cannot address — move with one XLA gather/scatter
here; a matrix of fewer than 8 rows has no full tile and takes XLA alone.
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
    order = jnp.argsort(sel_w)
    rows = sel_w.astype(jnp.int32)[order]
    # pad with the last (largest) row: padded steps revisit its tile
    rows_p = jnp.concatenate(
        [rows, jnp.broadcast_to(rows[-1:], ((-P) % RB,))])
    out = scatter_add_rows_pallas(
        mat, rows_p, _pad_axis(sel_k.astype(jnp.int32)[order], 0, RB),
        _pad_axis(vals[order], 0, RB), n_sel=P)
    if W % TILE:
        out = pw.scatter_add_rows(
            out, sel_w, sel_k,
            jnp.where(_tail(sel_w, W)[:, None], vals, 0.0))
    return out.astype(mat_wk.dtype)
