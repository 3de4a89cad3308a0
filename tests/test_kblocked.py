"""K-blocked carry megakernel (DESIGN.md §13): parity vs the oracle and
the full-K one-pass kernel, the shared VMEM tile chooser's edge shapes,
and the kblock-aware dispatch/serving contracts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LDAConfig, MiniBatch
from repro.core.sweep_dispatch import (_resolve_cached, carry_vmem_fit,
                                       resolve_sweep_policy)
from repro.kernels.power_sweep import kernel as K_
from repro.kernels.power_sweep.ops import (power_sweep_carry,
                                           power_sweep_carry_kblocked)
from repro.kernels.power_sweep.ref import power_sweep_carry_kblocked_ref


def _carry_inputs(seed, T, D, K, P, update_phi=True):
    rng = np.random.default_rng(seed)
    p_tok = jnp.asarray(rng.integers(0, P + 1, T).astype(np.int32))
    doc_ids = jnp.asarray(rng.integers(0, D, T).astype(np.int32))
    c = jnp.asarray(rng.integers(0, 4, (T, 1)).astype(np.float32))
    mu = rng.uniform(0.01, 1, (T, K)).astype(np.float32)
    mu = jnp.asarray(mu / mu.sum(-1, keepdims=True))
    theta = jnp.asarray(rng.uniform(0, 5, (D, K)).astype(np.float32))
    pt = jnp.asarray(rng.uniform(1, 9, (K,)).astype(np.float32))
    phi = rng.uniform(0, 5, (P + 1, K)).astype(np.float32)
    phi[-1] = 0.0
    if update_phi:
        mask = (rng.uniform(0, 1, (P + 1, K)) < 0.5).astype(np.float32)
        mask[-1] = 0.0
        kw = dict(alpha=0.1, beta=0.01, wbeta=0.4, update_phi=True)
    else:
        # serving contract: pre-normalized phi, beta == 0, implicit mask
        mask = np.ones((P + 1, K), np.float32)
        mask[-1] = 0.0
        phi = phi / np.maximum(phi.sum(0, keepdims=True), 1e-30)
        pt = jnp.ones((K,), jnp.float32)
        kw = dict(alpha=0.1, beta=0.0, wbeta=1.0, update_phi=False)
    phi = phi * mask
    return (p_tok, doc_ids, c, mu, theta, pt, jnp.asarray(phi),
            jnp.asarray(mask)), kw


# --------------------------------------------- kblocked vs oracle / full-K

@pytest.mark.parametrize("T,D,K,P,update_phi", [
    (48, 8, 256, 12, True),      # 2 K-blocks (kb=128)
    (48, 8, 256, 12, False),     # serving mode, 2 K-blocks
    (24, 4, 384, 6, True),       # 3 K-blocks, TT hits the floor of 8
    (64, 16, 200, 10, True),     # K not lane-aligned: ops pads 200 -> 256
    (40, 8, 130, 5, False),      # serving, padded 130 -> 256? no: 130->256
])
def test_kblocked_matches_ref_and_fullk(T, D, K, P, update_phi):
    args, kw = _carry_inputs(T * K + P, T, D, K, P, update_phi)
    outs_kb = power_sweep_carry(*args, kblocked=True, kb=128, **kw)
    outs_fk = power_sweep_carry(*args, **kw)
    outs_rf = power_sweep_carry_kblocked_ref(*args, **kw)
    n_keep = P if update_phi else 0
    ref = (outs_rf[0], outs_rf[1], outs_rf[2][:n_keep], outs_rf[3][:n_keep],
           outs_rf[4] if not update_phi else jnp.zeros((D,)))
    for a, b, c in zip(outs_kb, outs_fk, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-5)


def test_kblocked_auto_block_width_under_tiny_budget():
    """kb=None picks the width from the budget; a tiny budget forces the
    narrowest block and the outputs stay exact."""
    T, D, K, P = 32, 8, 512, 8
    args, kw = _carry_inputs(7, T, D, K, P, update_phi=True)
    outs_kb = power_sweep_carry(*args, kblocked=True,
                                vmem_budget_bytes=600_000, **kw)
    outs_fk = power_sweep_carry(*args, **kw)
    for a, b in zip(outs_kb, outs_fk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-5)


def test_kblocked_single_block_routes_to_fullk():
    """KB >= K degenerates to the one-pass kernel (same compiled program),
    so outputs are bit-identical to the full-K call."""
    T, D, K, P = 24, 4, 128, 6
    args, kw = _carry_inputs(11, T, D, K, P, update_phi=True)
    outs_kb = power_sweep_carry_kblocked(*args, kb=128, **kw)
    outs_fk = power_sweep_carry(*args, **kw)
    for a, b in zip(outs_kb, outs_fk):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- tile chooser contract

def test_pow2_tile_bounds_and_monotone():
    budget = K_.vmem_budget()
    assert K_._pow2_tile(0, 4, budget) == 512          # capped at 512
    assert K_._pow2_tile(budget + 1, 4, budget) == 8   # floored at 8
    prev = 512
    for per_tok in (64, 1024, 65536, 2**22):
        tt = K_._pow2_tile(0, per_tok, budget)
        assert 8 <= tt <= prev and tt & (tt - 1) == 0  # power of two
        prev = tt


def test_fit_token_tile_clamps_and_raises():
    assert K_.fit_token_tile(24, 512) == 8     # 24 % 16 != 0 -> floor 8
    assert K_.fit_token_tile(64, 512) == 64
    assert K_.fit_token_tile(96, 64) == 32
    with pytest.raises(ValueError):
        K_.fit_token_tile(12, 512)             # T not a multiple of 8


def test_vmem_budget_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_VMEM_BUDGET_BYTES", raising=False)
    assert K_.vmem_budget() == K_.DEFAULT_VMEM_BUDGET
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "777")
    assert K_.vmem_budget() == 777
    assert K_.vmem_budget(1234) == 1234        # override beats env


def test_kblock_width_ladder():
    # huge budget: widest candidate dividing K
    assert K_.kblock_width(1024, 48, 224, 10**9) == 512
    # tiny budget: falls through to the narrowest divisor
    assert K_.kblock_width(1024, 48, 224, 100_000) == 128
    # K=256 cannot take 512
    assert K_.kblock_width(256, 48, 224, 10**9) == 256
    with pytest.raises(ValueError):
        K_.kblock_width(200, 48, 224)          # K must be lane-padded


def test_both_tile_choosers_share_the_budget(monkeypatch):
    """Satellite: one budget source for the packed chooser and the carry
    chooser — shrinking it via env shrinks both tiles."""
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "200000")
    small_pk = K_.token_tile(128, 48)
    small_ca = K_.carry_token_tile(128, 48, 224)
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(K_.DEFAULT_VMEM_BUDGET))
    assert K_.token_tile(128, 48) > small_pk
    assert K_.carry_token_tile(128, 48, 224) > small_ca


# -------------------------------------------------------- dispatch policy

def test_kblocked_policy_on_jnp_is_dense_layout():
    cfg = LDAConfig(vocab_size=100, num_topics=16, sweep_policy="kblocked")
    assert resolve_sweep_policy(cfg, 1000, 16, 8, 5) == "dense_layout"
    cfgp = dataclasses.replace(cfg, impl="pallas")
    assert resolve_sweep_policy(cfgp, 1000, 16, 8, 5) == "kblocked"


def test_pallas_auto_flips_on_vmem_fit():
    """auto -> dense_layout while the full-K carry fits, kblocked beyond;
    the flip is driven purely by the budget (``small`` fits the K-blocked
    kernel's 128-wide topic blocks, not the full-K tables)."""
    big, small = 10**9, 4_000_000
    assert carry_vmem_fit(1024, 48, 224, big)
    assert not carry_vmem_fit(1024, 48, 224, small)
    kw = dict(T=4096, K=1024, Pk=16, P=48, crossover=8_000_000,
              impl="pallas", n_docs=224)
    assert _resolve_cached("auto", budget=big, **kw) == "dense_layout"
    assert _resolve_cached("auto", budget=small, **kw) == "kblocked"


def test_cfg_budget_reaches_dispatch():
    cfg = LDAConfig(vocab_size=100, num_topics=1024, impl="pallas",
                    sweep_policy="auto", vmem_budget_bytes=4_000_000)
    assert resolve_sweep_policy(cfg, 4096, 1024, 16, 48,
                                n_docs=224) == "kblocked"
    cfg2 = dataclasses.replace(cfg, vmem_budget_bytes=None)
    assert resolve_sweep_policy(cfg2, 4096, 1024, 16, 48,
                                n_docs=224) == "dense_layout"


# ------------------------------------------------- end-to-end parity paths

def test_selective_sweep_kblocked_matches_dense_layout():
    """Training inner loop: the kblocked policy computes the dense-layout
    answer (same math, different tiling)."""
    from repro.core.pobp import (selective_sweep_tokens,
                                 selective_sweep_tokens_pallas)
    from repro.core import power as pw

    cfg = LDAConfig(vocab_size=40, num_topics=10, lambda_w=0.2,
                    lambda_k_abs=3, impl="pallas", sweep_policy="kblocked")
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    D, L = 8, 14
    wid = jax.random.randint(ks[0], (D, L), 0, cfg.vocab_size).astype(jnp.int32)
    cnt = jax.random.randint(ks[1], (D, L), 0, 3).astype(jnp.float32)
    batch = MiniBatch(wid, cnt)
    mu = jax.nn.softmax(jax.random.normal(ks[2], (D, L, cfg.num_topics)), -1)
    theta = jnp.einsum("dl,dlk->dk", cnt, mu)
    phi = jax.random.uniform(ks[3], (cfg.vocab_size, cfg.num_topics)) * 5
    r = jax.random.uniform(ks[4], (cfg.vocab_size, cfg.num_topics))
    sel_w = pw.select_power_words(jnp.sum(r, 1), 8)
    sel_k = pw.select_power_topics(r, sel_w, 3)
    lay = batch.token_layout()
    mu_t = mu.reshape(-1, cfg.num_topics)
    outs_ref = selective_sweep_tokens(lay, mu_t, theta, phi, jnp.sum(phi, 0),
                                      sel_w, sel_k, cfg)
    outs_kb = selective_sweep_tokens_pallas(lay, mu_t, theta, phi,
                                            jnp.sum(phi, 0), sel_w, sel_k,
                                            cfg)
    for a, b in zip(outs_ref, outs_kb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_serving_foldin_kblocked_matches_auto():
    """Fixed-phi fold-in: pinning kblocked serves the same theta as the
    default policy (the whole-vocabulary guard row is the n_guard)."""
    from repro.core.infer import fold_in_tokens

    W, K, D, L = 60, 16, 6, 12
    key = jax.random.PRNGKey(3)
    phi = jax.random.uniform(jax.random.PRNGKey(4), (W, K)) + 0.1
    phi = phi / jnp.sum(phi, 0, keepdims=True)
    wid = jax.random.randint(key, (D, L), 0, W).astype(jnp.int32)
    cnt = jax.random.randint(jax.random.PRNGKey(5), (D, L), 0, 3
                             ).astype(jnp.float32)
    batch = MiniBatch(wid, cnt)
    cfg_a = LDAConfig(vocab_size=W, num_topics=K, impl="pallas",
                      sweep_policy="auto")
    cfg_k = dataclasses.replace(cfg_a, sweep_policy="kblocked")
    ra = fold_in_tokens(jax.random.PRNGKey(7), batch, phi, cfg_a, iters=8)
    rk = fold_in_tokens(jax.random.PRNGKey(7), batch, phi, cfg_k, iters=8)
    np.testing.assert_allclose(np.asarray(ra.theta), np.asarray(rk.theta),
                               rtol=1e-5, atol=1e-6)
