"""jit'd wrapper for power_sweep: TPU tile padding + dispatch.

Padding contract (keeps the fused math exact — see kernel.py):
  - Pk -> lane multiple (128): mu/pt/phi pad 0, theta pads -alpha so the
    padded columns contribute u == 0 to the in-tile renormalization;
  - packed rows -> sublane multiple (8) past the P+1 guard row, zero rows;
  - T -> tile multiple: padded tokens carry p_tok == P (guard) and c == 0,
    so they update nothing and scatter exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pad_axis as _pad_axis
from repro.kernels.power_sweep.kernel import power_sweep_tokens


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "wbeta"))
def power_sweep(p_tok: jnp.ndarray, counts_t: jnp.ndarray,
                mu_sel: jnp.ndarray, theta_sel: jnp.ndarray,
                pt_sel: jnp.ndarray, phi_pack: jnp.ndarray, *,
                alpha: float, beta: float, wbeta: float):
    """Fused selective sweep over pre-gathered token tiles.

    p_tok [T] int32 in [0, P] (P => token not selected); counts_t [T, 1];
    mu_sel/theta_sel/pt_sel [T, Pk] gathered at the token's power topic
    coords; phi_pack [P, Pk] packed effective phi.
    Returns (mu_new_sel [T, Pk], d_pack [P, Pk], r_pack [P, Pk]).
    """
    T0, Pk = mu_sel.shape
    P = phi_pack.shape[0]
    f32 = jnp.float32

    mu_p = _pad_axis(mu_sel.astype(f32), 1, 128)
    th_p = _pad_axis(theta_sel.astype(f32), 1, 128, value=-alpha)
    pt_p = _pad_axis(pt_sel.astype(f32), 1, 128)
    phi_p = _pad_axis(_pad_axis(phi_pack.astype(f32), 1, 128), 0, 8,
                      value=0.0)
    if phi_p.shape[0] < P + 1:                    # guard row must exist
        phi_p = jnp.pad(phi_p, ((0, 8), (0, 0)))

    c_p = _pad_axis(counts_t.astype(f32), 0, 8)
    mu_p = _pad_axis(mu_p, 0, 8)
    th_p = _pad_axis(th_p, 0, 8, value=-alpha)
    pt_p = _pad_axis(pt_p, 0, 8)
    p_tok_p = _pad_axis(p_tok.astype(jnp.int32), 0, 8, value=P)

    mu_new, d_pack, r_pack = power_sweep_tokens(
        p_tok_p, c_p, mu_p, th_p, pt_p, phi_p,
        alpha=alpha, beta=beta, wbeta=wbeta, n_pow=P)
    return (mu_new[:T0, :Pk].astype(mu_sel.dtype),
            d_pack[:P, :Pk].astype(mu_sel.dtype),
            r_pack[:P, :Pk].astype(mu_sel.dtype))


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "wbeta",
                                             "update_phi", "kblocked",
                                             "kb", "vmem_budget_bytes"))
def power_sweep_carry(p_tok: jnp.ndarray, doc_ids: jnp.ndarray,
                      counts_t: jnp.ndarray, mu_t: jnp.ndarray,
                      theta: jnp.ndarray, phi_tot: jnp.ndarray,
                      phi_rows: jnp.ndarray, mask_rows: jnp.ndarray, *,
                      alpha: float, beta: float, wbeta: float,
                      update_phi: bool = True, kblocked: bool = False,
                      kb=None, vmem_budget_bytes=None):
    """Carry-resident megakernel over the full [T, K] mu carry.

    ``kblocked=True`` dispatches the K-blocked two-pass variant
    (DESIGN.md §13) under the identical padding contract — the lane
    padding already makes K a multiple of 128, which every candidate
    topic-block width divides; ``kb``/``vmem_budget_bytes`` tune the
    block width and the tile chooser's budget (default: env/global).

    p_tok [T] int32 in [0, P] (P = the guard row: non-power / frozen /
    padding tokens — mask zero, token untouched); doc_ids [T] int32;
    counts_t [T, 1]; mu_t [T, K]; theta [D, K] (the doc-topic statistic of
    mu_t); phi_tot [K] (the Eq. 1 denominator row); phi_rows/mask_rows
    [P+1, K] — packed phi rows densified over K with their 0/1 topic
    selection, guard row all zeros.  On the serving path
    ``update_phi=False`` the selection is implicit (every row but the
    guard selects all topics — the kernel compares p_tok against the
    guard id instead of gathering a mask, ``mask_rows`` is replaced by a
    dummy, and the phi rows reach the kernel pre-gathered per token);
    ``beta`` must be 0 there so the K lane padding keeps u == 0 exactly.

    Padding contract (keeps the fused math exact — see kernel.py):
      - K -> lane multiple (128): mask pads 0, so padded columns carry
        u == 0 and mu stays bit-identical (phi_tot pads 0, denominator
        wbeta > 0 keeps the division finite);
      - rows -> sublane multiple (8): zero phi/mask rows;
      - D -> sublane multiple (8): no doc_id points there, rows accumulate
        exact zeros;
      - T -> tile multiple: padded tokens carry p_tok == P (guard) and
        c == 0, so they update nothing and accumulate exact zeros.

    Returns (mu_new [T, K], theta_delta [D, K], d_rows [P, K],
    r_rows [P, K], rdoc [D]).  The mode-dead outputs come back as zeros
    of truncated shape (the kernel never allocates them at full size):
    d_rows/r_rows are [0, K] on the serving path ``update_phi=False``,
    rdoc (the per-doc |c*delta| mass) is all-zero [D] on the training
    path.
    """
    from repro.kernels.power_sweep.kernel import (
        power_sweep_carry_kblocked_tokens, power_sweep_carry_tokens)

    T0, K0 = mu_t.shape
    P = phi_rows.shape[0] - 1
    D0 = theta.shape[0]
    f32 = jnp.float32

    if not update_phi and beta != 0.0:
        raise ValueError("power_sweep_carry(update_phi=False) requires "
                         "beta == 0 (serving phi is pre-normalized; a "
                         "nonzero beta would leak into the lane padding)")

    mu_p = _pad_axis(_pad_axis(mu_t.astype(f32), 1, 128), 0, 8)
    th_p = _pad_axis(_pad_axis(theta.astype(f32), 1, 128), 0, 8)
    pt_p = _pad_axis(phi_tot.astype(f32).reshape(1, -1), 1, 128)
    if update_phi:
        phi_p = _pad_axis(_pad_axis(phi_rows.astype(f32), 1, 128), 0, 8)
        msk_p = _pad_axis(_pad_axis(mask_rows.astype(f32), 1, 128), 0, 8)
    else:
        # serving: the kernel streams each token's phi row ([T, K]; a
        # whole-vocabulary table never fits VMEM) and derives the implicit
        # all-topics mask from the guard compare — a sublane dummy mask
        phi_tok = jnp.take(phi_rows.astype(f32), p_tok, axis=0)
        phi_p = _pad_axis(_pad_axis(phi_tok, 1, 128), 0, 8)
        msk_p = jnp.zeros((8, phi_p.shape[1]), f32)
    c_p = _pad_axis(counts_t.astype(f32), 0, 8)
    p_tok_p = _pad_axis(p_tok.astype(jnp.int32), 0, 8, value=P)
    doc_p = _pad_axis(doc_ids.astype(jnp.int32), 0, 8)

    if kblocked:
        sweep_fn = functools.partial(power_sweep_carry_kblocked_tokens,
                                     kb=kb,
                                     vmem_budget_bytes=vmem_budget_bytes)
    else:
        sweep_fn = functools.partial(power_sweep_carry_tokens,
                                     vmem_budget_bytes=vmem_budget_bytes)
    mu_new, th_delta, d_rows, r_rows, rd_rows = sweep_fn(
        p_tok_p, doc_p, c_p, mu_p, th_p, pt_p, phi_p, msk_p,
        alpha=alpha, beta=beta, wbeta=wbeta, update_phi=update_phi,
        n_guard=P)
    dt = mu_t.dtype
    n_keep = P if update_phi else 0
    return (mu_new[:T0, :K0].astype(dt),
            th_delta[:D0, :K0].astype(dt),
            d_rows[:n_keep, :K0].astype(dt),
            r_rows[:n_keep, :K0].astype(dt),
            (jnp.sum(rd_rows[:D0, :K0], axis=1) if not update_phi
             else jnp.zeros((D0,), jnp.float32)).astype(dt))


def power_sweep_carry_kblocked(*args, **kwargs):
    """`power_sweep_carry` pinned to the K-blocked two-pass kernel."""
    return power_sweep_carry(*args, kblocked=True, **kwargs)
