"""The plain reference of `bench/reference.pobp_step` with its topic axis
shared out over the chips of a mesh.

The same equations (arXiv:1311.4150, Fig. 4) in the same order, written
again in plain ``jax.numpy`` with every [W, K] and [D, L, K] array held
as column blocks over the mesh axis ``model``
(``with_sharding_constraint``), so that a K no chip can hold alone runs
on several.  XLA partitions the plain program; nothing of the program
under test is used, and nothing here imports ``repro``.  Two statements
are written so that they partition by rows: the power-submatrix updates
of phi and r go through the [P, K] rows of the power words (a column
scatter into a [P, K] block, then a row update), which sets and adds the
same entries as the reference's point updates.  Every chip sees every
document; one minibatch is one data shard (the reference's
``num_shards=1``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.reference import HIGHEST


def _cols(mesh, x):
    """``x`` with its last (topic) axis over ``model``."""
    spec = P(*([None] * (x.ndim - 1)), "model")
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _scatter_rows(mesh, word_ids, vals, n_words):
    """[D, L, K] per-token values summed into [W, K] rows by word id."""
    K = vals.shape[-1]
    out = _cols(mesh, jnp.zeros((n_words, K), vals.dtype))
    return _cols(mesh, out.at[word_ids.reshape(-1)].add(vals.reshape(-1, K)))


def _dense_sweep(mesh, word_ids, counts, mu, phi_eff, phi_tot, hp,
                 precision):
    """Eq. 1 over every token (the t = 1 sweep): new messages and the
    residual matrix r[w, k] (Eq. 7)."""
    W = hp["W"]
    c = counts[..., None]
    theta = jnp.einsum("dl,dlk->dk", counts, mu, precision=precision)
    self_c = c * mu
    th = theta[:, None, :] - self_c + hp["alpha"]
    ph = _cols(mesh, phi_eff[word_ids]) - self_c + hp["beta"]
    pt = phi_tot[None, None, :] - self_c + W * hp["beta"]
    unnorm = _cols(mesh, th * ph / pt)
    mu1 = _cols(mesh, unnorm / jnp.sum(unnorm, axis=-1, keepdims=True))
    return mu1, _scatter_rows(mesh, word_ids, c * jnp.abs(mu1 - mu), W)


def _selective_sweep(mesh, word_ids, counts, mu, theta, phi_eff, phi_tot,
                     sel_w, chosen, hp, precision):
    """Fig. 4 lines 15-21: update messages at (power word, power topic)
    only, renormalized within the selected topics so every message keeps
    its mass.  ``chosen`` [P + 1, K] marks each power word's selected
    topics (row P: none).  Returns (mu, theta, delta phi rows, residual
    rows), the rows [P, K] over each power word's topics."""
    W = hp["W"]
    P_ = sel_w.shape[0]
    row_of = jnp.full((W,), P_, jnp.int32).at[sel_w].set(
        jnp.arange(P_, dtype=jnp.int32))
    p_tok = row_of[word_ids]                                  # [D, L]
    sel = _cols(mesh, chosen[p_tok])                          # [D, L, K]
    c = counts[..., None]
    mass = jnp.sum(jnp.where(sel, mu, 0.0), axis=-1, keepdims=True)
    self_c = c * mu
    th = theta[:, None, :] - self_c + hp["alpha"]
    ph = _cols(mesh, phi_eff[word_ids]) - self_c + hp["beta"]
    pt = phi_tot[None, None, :] - self_c + W * hp["beta"]
    u = _cols(mesh, jnp.where(sel, th * ph / pt, 0.0))
    denom = jnp.maximum(jnp.sum(u, axis=-1, keepdims=True), 1e-30)
    new = _cols(mesh, jnp.where(sel, u * mass / denom, mu))
    theta = jnp.einsum("dl,dlk->dk", counts, new, precision=precision)
    K = mu.shape[-1]
    cd = (c * (new - mu)).reshape(-1, K)
    rows = p_tok.reshape(-1)
    d_rows = _cols(mesh, jnp.zeros((P_ + 1, K), mu.dtype).at[rows].add(cd))
    r_rows = _cols(mesh, jnp.zeros((P_ + 1, K), mu.dtype).at[rows].add(
        jnp.abs(cd)))
    return new, theta, d_rows[:P_], r_rows[:P_]


def _block(x, b, n):
    """Rows [b * n, (b + 1) * n) of ``x``."""
    return jax.lax.dynamic_slice_in_dim(x, b * n, n, axis=0)


@functools.partial(jax.jit, static_argnames=("hp", "mesh", "blocks",
                                             "dtype", "precision"),
                   donate_argnums=(0,))
def pobp_step(phi_acc, rng, word_ids, counts, *, hp, mesh, blocks=1,
              dtype=jnp.float32, precision=HIGHEST):
    """One POBP minibatch (Fig. 4, one m) of the plain reference, topics
    over ``mesh``'s ``model`` axis.

    ``phi_acc`` [W, K] is the accumulated statistic (donated), ``rng``
    the stream key (split once per minibatch), ``word_ids``/``counts``
    [D, L] the whole minibatch, ``hp`` a tuple of (name, value) pairs:
    W, K, alpha, beta, P, Pk, iters, tol.  Each document's random field
    is its uniform [L, K] draw from ``fold_in(key, document)``.  The
    documents are taken ``D / blocks`` at a time wherever each document's
    update depends only on the shared statistics: the first statistic
    (Fig. 4 line 5), the dense sweep and every selective sweep; the
    [W, K] and [P, K] sums over documents add block by block.

    Returns (phi_acc_new, rng_new, mean_residual, iterations), the
    statistic over ``model`` as it came.
    """
    hp = dict(hp)
    W, K, P_, Pk = hp["W"], hp["K"], hp["P"], hp["Pk"]
    D, L = word_ids.shape
    n = D // blocks
    rng_new, sub = jax.random.split(rng)
    phi_acc = _cols(mesh, phi_acc.astype(dtype))
    counts = counts.astype(dtype)
    total = jnp.maximum(jnp.sum(counts), 1.0)

    def field(b):
        u = _cols(mesh, jax.vmap(lambda d: jax.random.uniform(
            jax.random.fold_in(sub, d), (L, K), minval=0.01, maxval=1.0))(
                b * n + jnp.arange(n, dtype=jnp.int32)).astype(dtype))
        return _cols(mesh, u / jnp.sum(u, axis=-1, keepdims=True))

    def add_stat(b, phi):
        w, c = _block(word_ids, b, n), _block(counts, b, n)
        return _cols(mesh, phi + _scatter_rows(mesh, w, c[..., None]
                                               * field(b), W))

    phi_0 = jax.lax.fori_loop(0, blocks, add_stat, phi_acc)
    phi_tot0 = jnp.sum(phi_0, axis=0)

    def dense(b, carry):
        mu, r_glob, phi_eff = carry
        w, c = _block(word_ids, b, n), _block(counts, b, n)
        mu1, r_b = _dense_sweep(mesh, w, c, field(b), phi_0, phi_tot0, hp,
                                precision)
        return (_cols(mesh, jax.lax.dynamic_update_slice_in_dim(
                    mu, mu1, b * n, axis=0)),
                _cols(mesh, r_glob + r_b),
                _cols(mesh, phi_eff + _scatter_rows(mesh, w, c[..., None]
                                                    * mu1, W)))

    mu, r_glob, phi_eff = jax.lax.fori_loop(
        0, blocks, dense, (_cols(mesh, jnp.zeros((D, L, K), dtype)),
                           _cols(mesh, jnp.zeros((W, K), dtype)), phi_acc))
    phi_tot = jnp.sum(phi_eff, axis=0)
    theta = jnp.einsum("dl,dlk->dk", counts, mu, precision=precision)
    r_w = jnp.sum(r_glob, axis=1)

    def cond(carry):
        *_, r_w, t = carry
        return (t < hp["iters"]) & (jnp.sum(r_w) / total > hp["tol"])

    def body(carry):
        mu, theta, phi_eff, phi_tot, r_glob, r_w, t = carry
        _, sel_w = jax.lax.top_k(r_w, P_)
        r_sel = _cols(mesh, r_glob[sel_w])                    # [P, K]
        _, sel_k = jax.lax.top_k(r_sel, Pk)
        chosen = _cols(mesh, jnp.zeros((P_ + 1, K), bool).at[
            jnp.arange(P_)[:, None], sel_k].set(True))

        def sweep(b, acc):
            mu, theta, d_rows, r_rows = acc
            mu_b, theta_b, d_b, r_b = _selective_sweep(
                mesh, _block(word_ids, b, n), _block(counts, b, n),
                _block(mu, b, n), _block(theta, b, n), phi_eff, phi_tot,
                sel_w, chosen, hp, precision)
            return (_cols(mesh, jax.lax.dynamic_update_slice_in_dim(
                        mu, mu_b, b * n, axis=0)),
                    jax.lax.dynamic_update_slice_in_dim(theta, theta_b,
                                                        b * n, axis=0),
                    _cols(mesh, d_rows + d_b), _cols(mesh, r_rows + r_b))

        zeros_pk = _cols(mesh, jnp.zeros((P_, K), dtype))
        mu, theta, d_rows, r_rows = jax.lax.fori_loop(
            0, blocks, sweep, (mu, theta, zeros_pk, zeros_pk))
        hit = chosen[:P_]
        # the residual's change at the power submatrix, the new residual
        # there, and phi's packed delta added (entries off it add 0)
        rw_delta = jnp.sum(jnp.where(hit, r_rows - r_sel, 0.0), axis=1)
        d_rows = jnp.where(hit, d_rows, 0.0)
        phi_eff = _cols(mesh, phi_eff.at[sel_w].add(d_rows))
        phi_tot = phi_tot + jnp.sum(d_rows, axis=0)
        r_glob = _cols(mesh, r_glob.at[sel_w].set(
            jnp.where(hit, r_rows, r_sel)))
        r_w = r_w.at[sel_w].add(rw_delta)
        return mu, theta, phi_eff, phi_tot, r_glob, r_w, t + 1

    carry = (mu, theta, phi_eff, phi_tot, r_glob, r_w, jnp.int32(1))
    *_, phi_eff, _, _, r_w, t = jax.lax.while_loop(cond, body, carry)
    # Eq. 11 with the paper's weight 1 (plain accumulation)
    return (phi_eff.astype(jnp.float32), rng_new,
            (jnp.sum(r_w) / total).astype(jnp.float32), t)
