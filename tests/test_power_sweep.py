"""Token-major power sweep: kernel-vs-ref parity, seed-semantics parity,
algorithm invariants, and the layout round-trip.  No hypothesis dependency —
this file keeps kernel coverage where property tests are skipped."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LDAConfig, MiniBatch, make_sim_minibatch_fn
from repro.core.pobp import (dense_sweep, selective_sweep,
                             selective_sweep_tokens,
                             selective_sweep_tokens_pallas)
from repro.core.residuals import token_scatter_wk
from repro.core.sync import LocalReducer
from repro.core import power as pw
from repro.kernels.power_sweep.ops import power_sweep
from repro.kernels.power_sweep.ref import power_sweep_tokens_ref


def _state(key, cfg, D=8, L=14):
    ks = jax.random.split(key, 4)
    wid = jax.random.randint(ks[0], (D, L), 0, cfg.vocab_size).astype(jnp.int32)
    cnt = jax.random.randint(ks[1], (D, L), 0, 3).astype(jnp.float32)
    batch = MiniBatch(wid, cnt)
    mu = jax.nn.softmax(jax.random.normal(ks[2], (D, L, cfg.num_topics)), -1)
    theta = jnp.einsum("dl,dlk->dk", cnt, mu)
    phi = jax.random.uniform(ks[3], (cfg.vocab_size, cfg.num_topics)) * 5
    return batch, mu, theta, phi, jnp.sum(phi, 0)


def _selection(key, cfg, P, Pk):
    r = jax.random.uniform(key, (cfg.vocab_size, cfg.num_topics))
    sel_w = pw.select_power_words(jnp.sum(r, 1), P)
    sel_k = pw.select_power_topics(r, sel_w, Pk)
    return sel_w, sel_k


# ------------------------------------------------------- kernel vs oracle

@pytest.mark.parametrize("T,P,Pk", [(50, 8, 3), (256, 40, 50), (40, 16, 130),
                                    (8, 1, 1), (512, 64, 8)])
def test_power_sweep_kernel_matches_ref(T, P, Pk):
    rng = np.random.default_rng(T * P + Pk)
    p_tok = jnp.asarray(rng.integers(0, P + 1, T).astype(np.int32))
    c = jnp.asarray(rng.integers(0, 4, (T, 1)).astype(np.float32))
    mu_sel = jnp.asarray(rng.uniform(0.01, 1, (T, Pk)).astype(np.float32))
    # theta and phi_tot include each token's own count mass (c * mu), as
    # every real statistic does; without it the self-count subtraction
    # drives the denominator through zero and the outputs blow up
    self_c = np.asarray(c) * np.asarray(mu_sel)
    th = jnp.asarray(rng.uniform(0, 5, (T, Pk)).astype(np.float32) + self_c)
    pt = jnp.asarray(rng.uniform(1, 9, (T, Pk)).astype(np.float32) + self_c)
    phip = jnp.asarray(rng.uniform(0, 5, (P, Pk)).astype(np.float32))
    kw = dict(alpha=0.1, beta=0.01, wbeta=0.4)
    mu1, d1, r1 = power_sweep(p_tok, c, mu_sel, th, pt, phip, **kw)
    phip1 = jnp.concatenate([phip, jnp.zeros((1, Pk))], 0)
    mu2, d2, r2 = power_sweep_tokens_ref(p_tok, c, mu_sel, th, pt, phip1,
                                         n_pow=P, **kw)
    np.testing.assert_allclose(np.asarray(mu1), np.asarray(mu2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2[:P]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2[:P]),
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(r1) >= 0)


# ------------------------------------------- token-major vs seed semantics

CFG = LDAConfig(vocab_size=40, num_topics=10, lambda_w=0.2, lambda_k_abs=3)


def test_token_sweep_matches_seed_selective_sweep():
    batch, mu, theta, phi, phi_tot = _state(jax.random.PRNGKey(0), CFG)
    sel_w, sel_k = _selection(jax.random.PRNGKey(1), CFG, 8, 3)
    m1, t1, d1, r1 = selective_sweep(batch, mu, theta, phi, phi_tot,
                                     sel_w, sel_k, CFG)
    lay = batch.token_layout()
    m2, t2, d2, r2 = selective_sweep_tokens(
        lay, mu.reshape(-1, CFG.num_topics), theta, phi, phi_tot,
        sel_w, sel_k, CFG)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(lay.to_batch_major(m2)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                               rtol=1e-5, atol=1e-6)


def test_pallas_sweep_matches_jnp_token_sweep():
    batch, mu, theta, phi, phi_tot = _state(jax.random.PRNGKey(2), CFG)
    sel_w, sel_k = _selection(jax.random.PRNGKey(3), CFG, 8, 3)
    lay = batch.token_layout()
    mu_t = mu.reshape(-1, CFG.num_topics)
    outs1 = selective_sweep_tokens(lay, mu_t, theta, phi, phi_tot,
                                   sel_w, sel_k, CFG)
    outs2 = selective_sweep_tokens_pallas(lay, mu_t, theta, phi, phi_tot,
                                          sel_w, sel_k, CFG)
    for a, b in zip(outs1, outs2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_token_sweep_invariants():
    """Mass conservation, untouched non-power entries, packed-delta
    consistency with the [W, K] token scatter restriction."""
    batch, mu, theta, phi, phi_tot = _state(jax.random.PRNGKey(4), CFG)
    sel_w, sel_k = _selection(jax.random.PRNGKey(5), CFG, 8, 3)
    lay = batch.token_layout()
    mu_t = mu.reshape(-1, CFG.num_topics)
    m2, t2, d2, r2 = selective_sweep_tokens(lay, mu_t, theta, phi, phi_tot,
                                            sel_w, sel_k, CFG)
    # sum_k mu == 1 stays invariant (mass-conserving renormalization)
    np.testing.assert_allclose(np.asarray(jnp.sum(m2, -1)), 1.0, atol=1e-5)
    # non-power tokens bit-identical
    in_power = np.isin(np.asarray(lay.word_ids), np.asarray(sel_w))
    np.testing.assert_array_equal(np.asarray(m2)[~in_power],
                                  np.asarray(mu_t)[~in_power])
    # unselected topic coords untouched even for power tokens
    unsel = np.setdiff1d(np.arange(CFG.num_topics), np.asarray(sel_k))
    np.testing.assert_array_equal(np.asarray(m2)[:, unsel],
                                  np.asarray(mu_t)[:, unsel])
    # theta consistent with the updated messages
    np.testing.assert_allclose(
        np.asarray(t2),
        np.asarray(jnp.einsum("dl,dlk->dk", batch.counts,
                              lay.to_batch_major(m2))), rtol=1e-5, atol=1e-5)
    # packed deltas == the [W, K] token scatter restricted to (sel_w, sel_k)
    d_tok = lay.to_batch_major(m2 - mu_t) * batch.counts[..., None]
    d_wk = token_scatter_wk(batch.word_ids, d_tok, CFG.vocab_size)
    np.testing.assert_allclose(np.asarray(pw.pack_rows(d_wk, sel_w, sel_k)),
                               np.asarray(d2), rtol=1e-4, atol=1e-5)
    # residual pack dominates the signed delta pack
    assert float(jnp.sum(r2)) >= abs(float(jnp.sum(d2))) - 1e-6


def test_token_layout_round_trip():
    batch, mu, *_ = _state(jax.random.PRNGKey(6), CFG, D=5, L=9)
    lay = batch.token_layout()
    assert lay.num_slots == 5 * 9
    np.testing.assert_array_equal(
        np.asarray(lay.word_ids.reshape(5, 9)), np.asarray(batch.word_ids))
    np.testing.assert_array_equal(
        np.asarray(lay.counts.reshape(5, 9)), np.asarray(batch.counts))
    np.testing.assert_array_equal(np.asarray(lay.doc_ids.reshape(5, 9)),
                                  np.tile(np.arange(5)[:, None], (1, 9)))
    mu_t = mu.reshape(-1, CFG.num_topics)
    np.testing.assert_array_equal(np.asarray(lay.to_batch_major(mu_t)),
                                  np.asarray(mu))


# ------------------------------------------------------------- end to end

def test_pobp_minibatch_pallas_matches_jnp():
    W, K = 60, 16
    cfgj = LDAConfig(vocab_size=W, num_topics=K, lambda_w=0.2, lambda_k_abs=4,
                     inner_iters=6, residual_tol=1e-9)
    cfgp = dataclasses.replace(cfgj, impl="pallas")
    wid = jax.random.randint(jax.random.PRNGKey(5), (10, 14), 0, W)
    cnt = jax.random.randint(jax.random.PRNGKey(6), (10, 14), 0, 3)
    outs = {}
    for name, c_ in (("jnp", cfgj), ("pallas", cfgp)):
        fn, _ = make_sim_minibatch_fn(c_, 1, "power")
        outs[name] = fn(wid.astype(jnp.int32), cnt.astype(jnp.float32),
                        jnp.zeros((W, K)), jax.random.PRNGKey(1),
                        jnp.float32(1.0))
    assert int(outs["jnp"][1]) == int(outs["pallas"][1])  # same iter count
    for a, b in zip(outs["jnp"], outs["pallas"]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=1e-5)


def test_dense_sweep_pallas_matches_jnp_sweep():
    """bp_update coverage without hypothesis (cf. tests/test_kernels.py)."""
    key = jax.random.PRNGKey(3)
    cfg = LDAConfig(vocab_size=90, num_topics=16)
    from repro.kernels.bp_update.ops import dense_sweep_pallas
    batch, mu, theta, phi, phi_tot = _state(key, cfg, D=12, L=20)
    m1, r1 = dense_sweep_pallas(batch, mu, phi, phi_tot, cfg,
                                batch.token_layout())
    m2, r2 = dense_sweep(batch, mu, phi, phi_tot, cfg, LocalReducer())
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-4,
                               atol=1e-4)


def test_token_loop_trajectory_matches_seed_loop():
    """mean_r trajectories of the production token-major loop and a
    faithfully reconstructed seed [D, L, K] loop agree to <= 1e-5.

    sweep_policy is pinned to 'packed': this test over-iterates a tiny
    random batch far past convergence (tol=1e-9), a regime where the
    selective update eventually blows up numerically (the seed does too,
    identically) — the chain formulation tracks the seed bit-closely
    through it, while dense_layout only tracks to float associativity
    (its sane-regime parity is pinned in tests/test_sweep_policy.py)."""
    cfg = LDAConfig(vocab_size=80, num_topics=12, lambda_w=0.15,
                    lambda_k_abs=4, inner_iters=6, residual_tol=1e-9,
                    sweep_policy="packed")
    W, K = cfg.vocab_size, cfg.num_topics
    P, Pk = cfg.num_power_words, cfg.num_power_topics
    key = jax.random.PRNGKey(9)
    wid = jax.random.randint(key, (16, 18), 0, W).astype(jnp.int32)
    cnt = jax.random.randint(jax.random.PRNGKey(10), (16, 18), 0, 3
                             ).astype(jnp.float32)
    batch = MiniBatch(wid, cnt)
    total = jnp.sum(cnt)

    # shared dense phase (lines 3-10)
    u0 = jax.random.uniform(jax.random.PRNGKey(1), (16, 18, K),
                            minval=0.01, maxval=1.0)
    mu0 = u0 / jnp.sum(u0, -1, keepdims=True)
    phi_eff = token_scatter_wk(wid, cnt[..., None] * mu0, W)
    phi_tot = jnp.sum(phi_eff, 0)
    mu1, r_glob = dense_sweep(batch, mu0, phi_eff, phi_tot, cfg,
                              LocalReducer())
    theta = jnp.einsum("dl,dlk->dk", cnt, mu1)
    r_w = jnp.sum(r_glob, 1)

    def seed_iter(mu, theta, phi_eff, phi_tot, r_glob, r_w):
        sel_w = pw.select_power_words(r_w, P)
        sel_k = pw.select_power_topics(r_glob, sel_w, Pk)
        mu, theta, d, r = selective_sweep(batch, mu, theta, phi_eff,
                                          phi_tot, sel_w, sel_k, cfg)
        phi_eff = pw.scatter_add_rows(phi_eff, sel_w, sel_k, d)
        phi_tot = phi_tot + jnp.zeros_like(phi_tot).at[sel_k].add(d)
        r_glob = pw.scatter_set_rows(r_glob, sel_w, sel_k, r)
        return mu, theta, phi_eff, phi_tot, r_glob, jnp.sum(r_glob, 1)

    from repro.core.residuals import mean_residual, packed_rw_delta
    lay = batch.token_layout()

    def token_iter(mu_t, theta, phi_eff, phi_tot, r_glob, r_w):
        sel_w = pw.select_power_words(r_w, P)
        sel_k = pw.select_power_topics(r_glob, sel_w, Pk)
        mu_t, theta, d, r = selective_sweep_tokens(
            lay, mu_t, theta, phi_eff, phi_tot, sel_w, sel_k, cfg)
        rw_d = packed_rw_delta(r_glob, sel_w, sel_k, r)
        phi_eff = pw.scatter_add_rows(phi_eff, sel_w, sel_k, d)
        phi_tot = phi_tot + jnp.zeros_like(phi_tot).at[sel_k].add(d)
        r_glob = pw.scatter_set_rows(r_glob, sel_w, sel_k, r)
        return mu_t, theta, phi_eff, phi_tot, r_glob, r_w.at[sel_w].add(rw_d)

    s_seed = (mu1, theta, phi_eff, phi_tot, r_glob, r_w)
    s_tok = (mu1.reshape(-1, K), theta, phi_eff, phi_tot, r_glob, r_w)
    for _ in range(5):
        s_seed = seed_iter(*s_seed)
        s_tok = token_iter(*s_tok)
        a = float(mean_residual(s_seed[-1], total))
        b = float(mean_residual(s_tok[-1], total))
        assert abs(a - b) <= 1e-5, (a, b)
