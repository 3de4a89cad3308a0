"""Pure-jnp reference implementation of belief propagation for LDA.

This is the *oracle* for everything else in the repo:
  - the batch BP algorithm of Zeng et al. (paper ref [5]), synchronous
    (Jacobi) schedule,
  - the message update Eq. (1) with exact self-exclusion terms,
  - sufficient statistics Eqs. (2)-(3),
  - residuals Eq. (7).

No sharding, no selection, no streaming — deliberately simple and slow.
OBP (M>1), POBP (N>1) and the Pallas kernel are all tested against this.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import HIGHEST, LDAConfig, MiniBatch


def init_messages(key: jax.Array, batch: MiniBatch, K: int) -> jnp.ndarray:
    """Random normalized messages mu[D, L, K] (Fig. 4 line 3); document d
    draws its [L, K] field from ``fold_in(key, d)``."""
    D, L = batch.word_ids.shape
    u = jax.vmap(lambda d: jax.random.uniform(
        jax.random.fold_in(key, d), (L, K), minval=0.01, maxval=1.0))(
            jnp.arange(D, dtype=jnp.int32))
    return u / jnp.sum(u, axis=-1, keepdims=True)


def theta_hat_from(batch: MiniBatch, mu: jnp.ndarray) -> jnp.ndarray:
    """Eq. (2) inclusive form: theta_hat[d, k] = sum_l c[d,l] mu[d,l,k]."""
    return jnp.einsum("dl,dlk->dk", batch.counts, mu, precision=HIGHEST)


def phi_delta_from(batch: MiniBatch, mu: jnp.ndarray, W: int) -> jnp.ndarray:
    """Mini-batch contribution to Eq. (3): Delta phi_hat[k, w] (scatter-add over tokens)."""
    weighted = batch.counts[..., None] * mu                     # [D, L, K]
    flat_w = batch.word_ids.reshape(-1)                         # [D*L]
    flat = weighted.reshape(-1, mu.shape[-1])                   # [D*L, K]
    out = jnp.zeros((W, mu.shape[-1]), flat.dtype).at[flat_w].add(flat)
    return out.T                                                # [K, W]


def bp_sweep(
    batch: MiniBatch,
    mu: jnp.ndarray,
    phi_prior: jnp.ndarray,
    cfg: LDAConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One synchronous BP sweep over all tokens.

    phi_prior[K, W] is the accumulated statistic from *previous* mini-batches
    (zero for pure batch BP).  Returns (mu_new, residual_wk[W, K], theta_hat).
    """
    K, W = cfg.num_topics, cfg.vocab_size
    theta = theta_hat_from(batch, mu)                           # [D, K]
    phi = phi_prior + phi_delta_from(batch, mu, W)              # [K, W]
    phi_tot = jnp.sum(phi, axis=1)                              # [K]

    c = batch.counts[..., None]                                 # [D, L, 1]
    self_contrib = c * mu                                       # [D, L, K]
    th = theta[:, None, :] - self_contrib + cfg.alpha           # Eq.(1) numerator, theta part
    ph = jnp.take(phi.T, batch.word_ids, axis=0) - self_contrib + cfg.beta
    pt = phi_tot[None, None, :] - self_contrib + W * cfg.beta
    unnorm = th * ph / pt
    mu_new = unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)

    # Eq. (7): r[w, k] accumulated over tokens of word w.
    r_tok = batch.counts[..., None] * jnp.abs(mu_new - mu)      # [D, L, K]
    flat_w = batch.word_ids.reshape(-1)
    r_wk = jnp.zeros((W, K), r_tok.dtype).at[flat_w].add(r_tok.reshape(-1, K))
    return mu_new, r_wk, theta


def batch_bp(
    key: jax.Array,
    batch: MiniBatch,
    cfg: LDAConfig,
    iters: int,
    phi_prior: jnp.ndarray | None = None,
):
    """Full batch BP: `iters` synchronous sweeps.  Returns (mu, phi_hat, theta_hat, residual_trace)."""
    K, W = cfg.num_topics, cfg.vocab_size
    if phi_prior is None:
        phi_prior = jnp.zeros((K, W), jnp.float32)
    mu = init_messages(key, batch, K)
    tokens = jnp.maximum(batch.num_tokens(), 1.0)

    def body(mu, _):
        mu_new, r_wk, _ = bp_sweep(batch, mu, phi_prior, cfg)
        return mu_new, jnp.sum(r_wk) / tokens

    mu, res_trace = jax.lax.scan(body, mu, None, length=iters)
    theta = theta_hat_from(batch, mu)
    phi = phi_prior + phi_delta_from(batch, mu, W)
    return mu, phi, theta, res_trace


def log_likelihood(batch: MiniBatch, theta: jnp.ndarray, phi: jnp.ndarray,
                   cfg: LDAConfig) -> jnp.ndarray:
    """Token log-likelihood sum_{w,d} x log(sum_k theta_d(k) phi_w(k)) with
    normalized (smoothed) multinomials."""
    theta_n = (theta + cfg.alpha)
    theta_n = theta_n / jnp.sum(theta_n, axis=-1, keepdims=True)        # [D, K]
    phi_n = (phi + cfg.beta)
    phi_n = phi_n / jnp.sum(phi_n, axis=1, keepdims=True)               # [K, W]
    p_tok = jnp.einsum("dk,kdl->dl", theta_n,
                       jnp.take(phi_n, batch.word_ids, axis=1),
                       precision=HIGHEST)                                # [D, L]
    logp = jnp.where(batch.counts > 0, jnp.log(jnp.maximum(p_tok, 1e-30)), 0.0)
    return jnp.sum(batch.counts * logp)
