"""Fused BP message update kernel (Eq. 1 + Eq. 7), token-major layout.

Tokens (the non-zero doc-word entries) are flattened to a [T, K] layout.
Each grid program owns a TT-token tile with the full (local) topic width K
resident in VMEM, computes

    u      = (theta - c*mu + alpha) * (phi - c*mu + beta) / (phi_tot - c*mu + W*beta)
    mu'    = u / sum_k u
    r      = c * |mu' - mu|

in one pass — five HBM streams (mu, theta, phi in; mu', r out) instead of the
~12 an unfused XLA graph issues, and zero [T, K] temporaries in HBM.

Tiling: TT is the largest power of two whose double-buffered blocks and
in-kernel temporaries fit the shared VMEM budget (`repro.kernels`), which
is also the kernel's Mosaic VMEM limit; K is padded to a multiple of 128
(lane width) and TT to a multiple of 8 (sublane width) by ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels as K_


def _kernel(counts_ref, mu_ref, theta_ref, phi_ref, phi_tot_ref,
            mu_out_ref, r_out_ref, *, alpha: float, beta: float, wbeta: float):
    c = counts_ref[...]                       # [TT, 1]
    mu = mu_ref[...]                          # [TT, K]
    self_c = c * mu
    th = theta_ref[...] - self_c + alpha
    ph = phi_ref[...] - self_c + beta
    pt = phi_tot_ref[...] - self_c + wbeta    # [1, K] broadcasts over TT
    u = th * ph / pt
    denom = jnp.sum(u, axis=-1, keepdims=True)
    mu_new = u / jnp.maximum(denom, 1e-30)
    mu_out_ref[...] = mu_new
    r_out_ref[...] = c * jnp.abs(mu_new - mu)


def vmem_bytes(tt: int, k_width: int) -> int:
    """VMEM the kernel needs at tile TT: five [TT, K] blocks (mu, theta,
    phi in; mu', r out) and the lane-padded [TT, 1] counts block, each
    double-buffered by the pipeline, plus ~6 [TT, K] f32 temporaries and
    the double-buffered [1, K] (sublane-padded to 8) phi_tot block."""
    blocks = 2 * tt * (5 * k_width * 4 + K_.LANE_ROW_BYTES)
    return blocks + 6 * tt * k_width * 4 + 2 * 8 * k_width * 4


def token_tile(k_width: int, vmem_budget_bytes=None) -> int:
    """Largest power-of-two TT in [8, 512] whose `vmem_bytes` fit the budget.

    Power of two so the divisibility fallback (halving until TT | T, T a
    multiple of 8) never collapses to a degenerate non-aligned tile.
    """
    budget = K_.vmem_budget(vmem_budget_bytes)
    tt = 512
    while tt > 8 and vmem_bytes(tt, k_width) > budget:
        tt //= 2
    if vmem_bytes(tt, k_width) > budget:
        raise ValueError(f"bp_update at K={k_width} needs "
                         f"{vmem_bytes(tt, k_width):,} B of VMEM at the "
                         f"minimum tile, over the {budget:,} B budget")
    return tt


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "wbeta"))
def bp_update_tokens(counts_t: jnp.ndarray, mu_t: jnp.ndarray,
                     theta_t: jnp.ndarray, phi_t: jnp.ndarray,
                     phi_tot: jnp.ndarray, *, alpha: float, beta: float,
                     wbeta: float):
    """Token-major fused update.

    counts_t [T, 1], mu_t/theta_t/phi_t [T, K], phi_tot [1, K];
    T % TT == 0 and K % 128 == 0 are the caller's (ops.py) responsibility.
    Returns (mu_new [T, K], r_tok [T, K]).
    """
    T, K = mu_t.shape
    budget = K_.vmem_budget()
    TT = token_tile(K, budget)
    while T % TT:
        TT //= 2
    grid = (T // TT,)
    spec_tk = pl.BlockSpec((TT, K), lambda i: (i, 0))
    spec_c = pl.BlockSpec((TT, 1), lambda i: (i, 0))
    spec_pt = pl.BlockSpec((1, K), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta, wbeta=wbeta),
        grid=grid,
        in_specs=[spec_c, spec_tk, spec_tk, spec_tk, spec_pt],
        out_specs=[spec_tk, spec_tk],
        out_shape=[jax.ShapeDtypeStruct((T, K), mu_t.dtype),
                   jax.ShapeDtypeStruct((T, K), mu_t.dtype)],
        compiler_params=K_.compiler_params(budget),
        interpret=K_.INTERPRET,
    )(counts_t, mu_t, theta_t, phi_t, phi_tot)
