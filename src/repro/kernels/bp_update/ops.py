"""jit'd wrapper: batch-level dense BP sweep backed by the Pallas kernel.

Handles layout (padded-CSR [D, L] -> token-major [T, K]), padding to tile
multiples, the per-token theta/phi gathers, and the residual scatter back to
[W, K].  Drop-in replacement for `repro.core.pobp.dense_sweep` when the
topic axis is not model-sharded (the normalization is fused in-kernel; the
sharded path keeps the jnp implementation — see DESIGN.md §4).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.residuals import token_scatter_wk
from repro.core.types import HIGHEST, LDAConfig, MiniBatch, TokenLayout
from repro.kernels.bp_update.kernel import bp_update_tokens


def _pad_to(x: jnp.ndarray, axis: int, multiple: int):
    from repro.kernels import pad_axis
    return pad_axis(x, axis, multiple), x.shape[axis]


def dense_sweep_pallas(batch: MiniBatch, mu: jnp.ndarray,
                       phi_eff_wk: jnp.ndarray, phi_tot: jnp.ndarray,
                       cfg: LDAConfig, layout: TokenLayout = None,
                       wbeta=None):
    """Fused-kernel version of core.pobp.dense_sweep (K unsharded).

    Accepts an optional precomputed TokenLayout so callers that already
    run token-major (core.pobp's persistent inner loop) don't rebuild it.
    Returns (mu_new [D, L, K], r_wk [W, K]) — bitwise-compatible contract.
    A traced `wbeta` (the live_w*beta smoothing of a capacity-laddered
    run, DESIGN.md §12) folds into the phi_tot argument with the kernel's
    static wbeta pinned at 1.0 (the unit offset keeps padded lanes'
    denominator nonzero); the kernel itself needs no new code.
    """
    D, L = batch.word_ids.shape
    K = mu.shape[-1]
    layout = layout or batch.token_layout()
    theta = jnp.einsum("dl,dlk->dk", batch.counts, mu, precision=HIGHEST)
    if wbeta is None:
        wb_static = cfg.vocab_size * cfg.beta
    else:
        phi_tot, wb_static = phi_tot + (wbeta - 1.0), 1.0

    counts_t = layout.counts                                       # [T, 1]
    mu_t = mu.reshape(-1, K)
    theta_t = jnp.take(theta, layout.doc_ids, axis=0)              # token-major
    phi_t = jnp.take(phi_eff_wk, layout.word_ids, axis=0)

    # pad K to lane multiple; padded topics get phi_tot=+inf-ish guard via
    # zero phi & theta: u=alpha*beta/(wbeta) > 0 -> contributes to the norm!
    # So pad with theta=-alpha, phi=-beta => u = 0 exactly.
    kpad = (-K) % 128
    if kpad:
        mu_t = jnp.pad(mu_t, ((0, 0), (0, kpad)))
        theta_t = jnp.pad(theta_t, ((0, 0), (0, kpad)), constant_values=-cfg.alpha)
        phi_t = jnp.pad(phi_t, ((0, 0), (0, kpad)), constant_values=-cfg.beta)
        phi_tot_p = jnp.pad(phi_tot.reshape(1, -1), ((0, 0), (0, kpad)),
                            constant_values=1.0)
    else:
        phi_tot_p = phi_tot.reshape(1, -1)

    counts_t, T0 = _pad_to(counts_t, 0, 8)
    mu_t, _ = _pad_to(mu_t, 0, 8)
    theta_t, _ = _pad_to(theta_t, 0, 8)
    phi_t, _ = _pad_to(phi_t, 0, 8)

    mu_new_t, r_t = bp_update_tokens(
        counts_t, mu_t, theta_t, phi_t, phi_tot_p,
        alpha=cfg.alpha, beta=cfg.beta, wbeta=wb_static)

    mu_new = mu_new_t[:T0, :K].reshape(D, L, K)
    r_tok = r_t[:T0, :K].reshape(D, L, K)
    r_wk = token_scatter_wk(batch.word_ids, r_tok, cfg.vocab_size)
    return mu_new, r_wk
