"""Plain references the benchmark holds the program to.

Written from the paper (arXiv:1311.4150, Fig. 4) and the fold-in
equations in straightforward ``jax.numpy``, batch-major [D, L, K], with
no kernels, no token layouts and no code of the program: nothing here
imports ``repro``.  Inputs are the benchmark's own (the generator's
statistic, its minibatches, the seed); random message fields are drawn
from the seed with the same derivation the method specifies (one
uniform [L, K] field per document from ``fold_in(key, document)``).

``dtype`` and ``precision`` select the arithmetic.  The configurations
state float32 with contractions at HIGHEST; the controls run the same
code one step lower (``precision=HIGH``, or bfloat16 arithmetic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _scatter_rows(word_ids, vals, n_words):
    """[D, L, K] per-token values summed into [W, K] rows by word id."""
    K = vals.shape[-1]
    return jnp.zeros((n_words, K), vals.dtype).at[word_ids.reshape(-1)].add(
        vals.reshape(-1, K))


def _dense_sweep(word_ids, counts, mu, phi_eff, phi_tot, hp, precision):
    """Eq. 1 over every token (the t = 1 sweep): new messages and the
    residual matrix r[w, k] (Eq. 7)."""
    W = hp["W"]
    c = counts[..., None]
    theta = jnp.einsum("dl,dlk->dk", counts, mu, precision=precision)
    self_c = c * mu
    th = theta[:, None, :] - self_c + hp["alpha"]
    ph = phi_eff[word_ids] - self_c + hp["beta"]
    pt = phi_tot[None, None, :] - self_c + W * hp["beta"]
    unnorm = th * ph / pt
    mu1 = unnorm / jnp.sum(unnorm, axis=-1, keepdims=True)
    return mu1, _scatter_rows(word_ids, c * jnp.abs(mu1 - mu), W)


def _selective_sweep(word_ids, counts, mu, theta, phi_eff, phi_tot, sel_w,
                     sel_k, hp, precision):
    """Fig. 4 lines 15-21: update messages at (power word, power topic)
    only, renormalized within the selected topics so every message keeps
    its mass.  Returns (mu, theta, packed delta phi, packed residual)."""
    W, K = hp["W"], hp["K"]
    P, Pk = sel_k.shape
    # each token's row of the power submatrix, or the empty row P
    row_of = jnp.full((W,), P, jnp.int32).at[sel_w].set(
        jnp.arange(P, dtype=jnp.int32))
    p_tok = row_of[word_ids]                                  # [D, L]
    chosen = jnp.zeros((P + 1, K), bool).at[
        jnp.arange(P)[:, None], sel_k].set(True)
    sel = chosen[p_tok]                                       # [D, L, K]
    c = counts[..., None]
    mass = jnp.sum(jnp.where(sel, mu, 0.0), axis=-1, keepdims=True)
    self_c = c * mu
    th = theta[:, None, :] - self_c + hp["alpha"]
    ph = phi_eff[word_ids] - self_c + hp["beta"]
    pt = phi_tot[None, None, :] - self_c + W * hp["beta"]
    u = jnp.where(sel, th * ph / pt, 0.0)
    denom = jnp.maximum(jnp.sum(u, axis=-1, keepdims=True), 1e-30)
    new = jnp.where(sel, u * mass / denom, mu)
    theta = jnp.einsum("dl,dlk->dk", counts, new, precision=precision)
    cd = (c * (new - mu)).reshape(-1, K)
    rows = p_tok.reshape(-1)
    d_rows = jnp.zeros((P + 1, K), mu.dtype).at[rows].add(cd)
    r_rows = jnp.zeros((P + 1, K), mu.dtype).at[rows].add(jnp.abs(cd))
    return (new, theta, jnp.take_along_axis(d_rows[:P], sel_k, axis=1),
            jnp.take_along_axis(r_rows[:P], sel_k, axis=1))


@functools.partial(jax.jit, static_argnames=("hp", "num_shards",
                                             "dtype", "precision"))
def pobp_step(phi_acc, rng, word_ids, counts, *, hp, num_shards=1,
              dtype=jnp.float32, precision=HIGHEST):
    """One POBP minibatch (Fig. 4, one m) of the plain reference.

    ``phi_acc`` [W, K] is the accumulated statistic, ``rng`` the stream
    key (split once per minibatch), ``word_ids``/``counts`` [D, L] the
    whole minibatch; with ``num_shards`` N the documents are N equal
    contiguous blocks whose first sweep starts from block-local
    statistics (line 5), and every later quantity is the all-reduced
    one.  ``hp`` is a tuple of (name, value) pairs: W, K, alpha, beta,
    P, Pk, iters, tol.

    Returns (phi_acc_new, rng_new, mean_residual, iterations).
    """
    hp = dict(hp)
    W, K, P, Pk = hp["W"], hp["K"], hp["P"], hp["Pk"]
    D, L = word_ids.shape
    rng_new, sub = jax.random.split(rng)
    phi_acc = phi_acc.astype(dtype)
    counts = counts.astype(dtype)
    c = counts[..., None]
    u = jax.vmap(lambda d: jax.random.uniform(
        jax.random.fold_in(sub, d), (L, K), minval=0.01, maxval=1.0))(
            jnp.arange(D, dtype=jnp.int32)).astype(dtype)
    mu0 = u / jnp.sum(u, axis=-1, keepdims=True)
    total = jnp.maximum(jnp.sum(counts), 1.0)

    ds = D // num_shards
    mus, r_glob = [], jnp.zeros((W, K), dtype)
    for s in range(num_shards):
        blk = slice(s * ds, (s + 1) * ds)
        w_s, n_s, m_s = word_ids[blk], counts[blk], mu0[blk]
        phi_s = phi_acc + _scatter_rows(w_s, n_s[..., None] * m_s, W)
        mu1_s, r_s = _dense_sweep(w_s, n_s, m_s, phi_s,
                                  jnp.sum(phi_s, axis=0), hp, precision)
        mus.append(mu1_s)
        r_glob = r_glob + r_s
    mu = jnp.concatenate(mus, axis=0)
    phi_eff = phi_acc + _scatter_rows(word_ids, c * mu, W)
    phi_tot = jnp.sum(phi_eff, axis=0)
    theta = jnp.einsum("dl,dlk->dk", counts, mu, precision=precision)
    r_w = jnp.sum(r_glob, axis=1)

    def cond(carry):
        *_, r_w, t = carry
        return (t < hp["iters"]) & (jnp.sum(r_w) / total > hp["tol"])

    def body(carry):
        mu, theta, phi_eff, phi_tot, r_glob, r_w, t = carry
        _, sel_w = jax.lax.top_k(r_w, P)
        _, sel_k = jax.lax.top_k(r_glob[sel_w], Pk)
        mu, theta, d_pack, r_pack = _selective_sweep(
            word_ids, counts, mu, theta, phi_eff, phi_tot, sel_w, sel_k, hp,
            precision)
        old = jnp.take_along_axis(r_glob[sel_w], sel_k, axis=1)
        rw_delta = jnp.sum(r_pack - old, axis=1)
        phi_eff = phi_eff.at[sel_w[:, None], sel_k].add(d_pack)
        phi_tot = phi_tot + jnp.zeros_like(phi_tot).at[sel_k].add(d_pack)
        r_glob = r_glob.at[sel_w[:, None], sel_k].set(r_pack)
        r_w = r_w.at[sel_w].add(rw_delta)
        return mu, theta, phi_eff, phi_tot, r_glob, r_w, t + 1

    carry = (mu, theta, phi_eff, phi_tot, r_glob, r_w, jnp.int32(1))
    *_, phi_eff, _, _, r_w, t = jax.lax.while_loop(cond, body, carry)
    # Eq. 11 with the paper's weight 1 (plain accumulation)
    return (phi_eff.astype(jnp.float32), rng_new,
            (jnp.sum(r_w) / total).astype(jnp.float32), t)


def normalize_phi(phi_acc, beta):
    """phi[w, k] = (phi_acc + beta) / sum_w (phi_acc + beta)."""
    sm = phi_acc + beta
    return sm / jnp.sum(sm, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("alpha", "dtype", "precision"))
def fold_in(phi_rows, counts, u0, *, alpha, iters=None, fold_iters=30,
            tol=0.0, dtype=jnp.float32, precision=HIGHEST):
    """BP fold-in of a block of documents with phi fixed.

    ``phi_rows`` [N, L, K]: each token's normalized phi row; ``counts``
    [N, L] (0 on padding); ``u0`` [N, L, K] the random field each
    document started from.  Every sweep updates all tokens of a document
    at once from the theta of the sweep before (Eq. 1 with phi held).

    With ``iters`` [N], document n runs exactly ``iters[n]`` sweeps.
    Without it, each runs until its own stopping rule holds: the
    remaining theta movement, estimated as a geometric tail of the
    residual, ``r * rho / (1 - rho)`` with ``rho`` the sweep-over-sweep
    decay clipped to [0.8, 0.95], is at most ``tol`` per token, or it
    has run ``fold_iters`` sweeps.  The residual ``r`` is the count-
    weighted L1 change of the messages in a sweep.

    Returns the smoothed, normalized theta [N, K] and the sweeps each
    document ran [N].
    """
    del precision                     # the fold-in has no contraction
    phi_rows, counts = phi_rows.astype(dtype), counts.astype(dtype)
    c = counts[..., None]
    tokens = jnp.sum(counts, axis=1)
    mu = u0.astype(dtype)
    mu = mu / jnp.maximum(jnp.sum(mu, -1, keepdims=True), 1e-30)
    theta = jnp.sum(c * mu, axis=1)
    n = counts.shape[0]
    r = jnp.full((n,), jnp.inf, dtype)
    r_prev = jnp.ones((n,), dtype)
    ran = jnp.zeros((n,), jnp.int32)

    def active(r, r_prev, ran):
        if iters is not None:
            return ran < iters
        rho = jnp.clip(r / jnp.maximum(r_prev, 1e-30), 0.8, 0.95)
        return (ran < fold_iters) & (r * rho / (1 - rho) > tol * tokens)

    def body(_, carry):
        mu, theta, r, r_prev, ran = carry
        live = active(r, r_prev, ran)
        th = theta[:, None, :] - c * mu + alpha
        un = th * phi_rows
        new = un / jnp.maximum(jnp.sum(un, -1, keepdims=True), 1e-30)
        new = jnp.where(live[:, None, None], new, mu)
        delta = new - mu
        r_new = jnp.sum(c * jnp.abs(delta), axis=(1, 2))
        return (new, theta + jnp.sum(c * delta, axis=1),
                jnp.where(live, r_new, r), jnp.where(live, r, r_prev),
                ran + live.astype(jnp.int32))

    length = fold_iters if iters is None else jnp.max(iters)
    mu, theta, r, r_prev, ran = jax.lax.fori_loop(
        0, length, body, (mu, theta, r, r_prev, ran))
    out = (theta + alpha).astype(jnp.float32)
    return out / jnp.sum(out, axis=-1, keepdims=True), ran
