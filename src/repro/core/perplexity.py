"""Predictive perplexity (paper Eq. 20, §4 protocol).

Protocol: per document, tokens are split 80/20.  With phi fixed, theta is
estimated on the 80% split by BP fold-in from a fixed random init; perplexity
is evaluated on the held-out 20% split.  Lower is better.

Fold-in routes through the shared token-major inference body
(`core.infer.fold_in_tokens`) — eval, the training driver's held-out hook
and the serving engine all compile the exact same program (DESIGN.md §11).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import infer
from repro.core.types import HIGHEST, LDAConfig, MiniBatch


def normalize_phi(phi_acc_wk: jnp.ndarray, beta: float,
                  live_w=None) -> jnp.ndarray:
    """phi[w, k] = (phi_hat + beta) / sum_w (phi_hat + beta)  — per-topic normalize.

    `live_w` switches to capacity-ladder semantics (DESIGN.md §12): rows in
    [live_w, W_cap) are guard rows, EXCLUDED from the per-topic denominator
    (their statistic is structurally zero, and W_cap*beta smoothing mass
    would otherwise jump every time the rung grows) and assigned the
    beta-prior value beta/denom — the posterior mass of one unseen word,
    which is exactly what serving's OOV admission folds in.  With
    ``live_w == W_cap`` (or None) this reduces to the fixed-W formula.
    """
    sm = phi_acc_wk + beta
    if live_w is None:
        return sm / jnp.sum(sm, axis=0, keepdims=True)
    live = jnp.arange(phi_acc_wk.shape[0])[:, None] < live_w
    denom = jnp.sum(jnp.where(live, sm, 0.0), axis=0, keepdims=True)
    return jnp.where(live, sm, beta) / jnp.maximum(denom, 1e-30)


def fold_in_theta(key: jax.Array, batch: MiniBatch, phi_norm_wk: jnp.ndarray,
                  cfg: LDAConfig, iters: int = 30,
                  residual_tol: float = 0.0) -> jnp.ndarray:
    """Estimate theta[D, K] on the training split with phi fixed (BP fold-in).

    Thin wrapper over `core.infer.fold_in_tokens` (the one fold-in body);
    ``residual_tol > 0`` enables the serving engine's per-document early
    exit, 0 keeps the paper's fixed-sweep eval protocol.
    """
    return infer.fold_in_tokens(key, batch, phi_norm_wk, cfg, iters=iters,
                                residual_tol=residual_tol).theta


def predictive_perplexity(theta: jnp.ndarray, phi_norm_wk: jnp.ndarray,
                          test: MiniBatch) -> jnp.ndarray:
    """Eq. (20) on the held-out split."""
    phi_tok = jnp.take(phi_norm_wk, test.word_ids, axis=0)       # [D, L, K]
    p = jnp.einsum("dk,dlk->dl", theta, phi_tok, precision=HIGHEST)
    logp = jnp.where(test.counts > 0, jnp.log(jnp.maximum(p, 1e-30)), 0.0)
    n = jnp.maximum(jnp.sum(test.counts), 1.0)
    return jnp.exp(-jnp.sum(test.counts * logp) / n)


def evaluate(key: jax.Array, phi_acc_wk: jnp.ndarray, train: MiniBatch,
             test: MiniBatch, cfg: LDAConfig, fold_iters: int = 30,
             live_w=None) -> float:
    """End-to-end: normalize phi, fold in theta, score the 20% split.

    `live_w` evaluates a capacity-laddered phi at its live vocabulary:
    guard rows get the beta-prior mass, so held-out documents whose words
    were mapped to a guard/OOV row still score finitely (DESIGN.md §12).
    """
    phi_norm = normalize_phi(phi_acc_wk, cfg.beta, live_w=live_w)
    theta = fold_in_theta(key, train, phi_norm, cfg, iters=fold_iters)
    return float(predictive_perplexity(theta, phi_norm, test))
