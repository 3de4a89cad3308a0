"""Core correctness: POBP vs oracles, algorithm invariants, paper claims."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LDAConfig, MiniBatch, make_sim_minibatch_fn, run_stream
from repro.core import perplexity, power, ref
from repro.core.pobp import selective_sweep
from repro.core.sync import dense_sync_bytes, power_sync_bytes
from repro.data import (docs_to_padded, lda_corpus, minibatch_stream,
                        sharded_minibatch_stream, train_test_split_counts)

CFG = LDAConfig(vocab_size=120, num_topics=8, lambda_w=0.3, lambda_k_abs=4,
                inner_iters=8, residual_tol=1e-6)


def small_corpus(seed=0, docs=64, W=120, K=8):
    d, stats, true_phi = lda_corpus(seed, docs, W, K, doc_len_mean=50)
    return d, true_phi


@pytest.fixture(scope="module")
def corpus():
    return small_corpus()


# ------------------------------------------------------------------ oracles

def test_pobp_n1_dense_equals_batch_bp_oracle(corpus):
    """N=1, M=1, dense mode must match the pure-jnp batch BP oracle exactly
    (paper §3.2: 'If N=1, POBP reduces to OBP'; 'If M=1 ... batch BP')."""
    docs, _ = corpus
    batch = docs_to_padded(docs)
    cfg = CFG
    key = jax.random.PRNGKey(7)

    fn, _ = make_sim_minibatch_fn(cfg, num_shards=1, sync_mode="dense")
    phi_new, iters, mean_r, mu, theta = fn(
        batch.word_ids, batch.counts,
        jnp.zeros((cfg.vocab_size, cfg.num_topics)), key, jnp.float32(1.0))

    mu_ref, phi_ref, theta_ref, _ = ref.batch_bp(key, batch, cfg,
                                                 iters=int(iters))
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_ref),
                               rtol=2e-5, atol=2e-6)
    # oracle stores phi as [K, W]; POBP uses [W, K]
    np.testing.assert_allclose(np.asarray(phi_new), np.asarray(phi_ref).T,
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(np.asarray(theta), np.asarray(theta_ref),
                               rtol=2e-5, atol=2e-4)


def test_pobp_shards_agree_on_global_state(corpus):
    """Every data shard must end a mini-batch with an identical phi_acc —
    the synchronized-global-matrix invariant of Eq. (4)."""
    docs, _ = corpus
    stream = sharded_minibatch_stream(docs, 32, num_shards=4)
    fn, _ = make_sim_minibatch_fn(CFG, num_shards=4, sync_mode="power")
    batch = next(iter(stream))
    phi_new, *_ = fn(batch.word_ids, batch.counts,
                     jnp.zeros((CFG.vocab_size, CFG.num_topics)),
                     jax.random.PRNGKey(0), jnp.float32(1.0))
    assert phi_new.shape[0] == 4
    for n in range(1, 4):
        np.testing.assert_allclose(np.asarray(phi_new[0]),
                                   np.asarray(phi_new[n]), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_data,n_model", [(2, 1), (4, 1), (2, 2)])
def test_init_field_is_layout_invariant(n_data, n_model):
    """Each shard's random message field is its slice of the one global
    field — documents by global index, topics by global column — so a
    run's start does not depend on how documents and topics are laid
    over shards or chips."""
    from repro.core.pobp import init_field
    D, L, K = 8, 5, CFG.num_topics
    key = jax.random.PRNGKey(11)
    full = np.asarray(init_field(key, D, L, CFG, K, 0, 0))
    dl, kl = D // n_data, K // n_model
    for i in range(n_data):
        for j in range(n_model):
            part = init_field(key, dl, L, CFG, kl, i * dl, j * kl)
            np.testing.assert_array_equal(
                np.asarray(part), full[i * dl:(i + 1) * dl, :, j * kl:(j + 1) * kl])


def test_dense_vs_power_converge_to_similar_perplexity(corpus):
    """The paper's core accuracy claim: sparse power sync (Eq. 6) must not
    cost much accuracy vs dense sync (Eq. 4) at lambda_w ~ 0.3."""
    docs, _ = corpus
    train, test = train_test_split_counts(docs, 0)
    cfg = LDAConfig(vocab_size=120, num_topics=8, lambda_w=0.3, lambda_k_abs=6,
                    inner_iters=15, residual_tol=0.01)
    out = {}
    for mode in ("dense", "power"):
        phi, _, _ = run_stream(sharded_minibatch_stream(train, 32, 4), cfg,
                               num_shards=4, sync_mode=mode, seed=3)
        out[mode] = perplexity.evaluate(jax.random.PRNGKey(5), phi,
                                        docs_to_padded(train),
                                        docs_to_padded(test), cfg)
    assert out["power"] < 1.30 * out["dense"], out


# ------------------------------------------------------------- invariants

def test_selective_sweep_preserves_normalization_and_untouched_entries():
    key = jax.random.PRNGKey(0)
    cfg = LDAConfig(vocab_size=40, num_topics=10, lambda_w=0.2, lambda_k_abs=3)
    D, L = 6, 12
    wid = jax.random.randint(key, (D, L), 0, cfg.vocab_size).astype(jnp.int32)
    cnt = jnp.ones((D, L), jnp.float32)
    batch = MiniBatch(wid, cnt)
    mu = jax.nn.softmax(jax.random.normal(key, (D, L, cfg.num_topics)), -1)
    theta = jnp.einsum("dl,dlk->dk", cnt, mu)
    phi = jax.random.uniform(key, (cfg.vocab_size, cfg.num_topics)) * 5
    phi_tot = jnp.sum(phi, 0)
    sel_w = jnp.asarray([3, 17, 29, 5, 11, 22, 8, 0], jnp.int32)
    sel_k = jnp.tile(jnp.asarray([[1, 4, 7]], jnp.int32), (8, 1))

    mu2, theta2, dpack, rpack = selective_sweep(batch, mu, theta, phi, phi_tot,
                                                sel_w, sel_k, cfg)
    # normalization is conserved
    np.testing.assert_allclose(np.asarray(jnp.sum(mu2, -1)), 1.0, atol=1e-5)
    # non-power tokens untouched
    in_power = np.isin(np.asarray(wid), np.asarray(sel_w))
    np.testing.assert_array_equal(np.asarray(mu2)[~in_power],
                                  np.asarray(mu)[~in_power])
    # unselected topic coords untouched even for power tokens
    unsel = np.setdiff1d(np.arange(cfg.num_topics), np.asarray(sel_k[0]))
    np.testing.assert_array_equal(np.asarray(mu2)[..., unsel],
                                  np.asarray(mu)[..., unsel])
    # theta consistent with messages
    np.testing.assert_allclose(np.asarray(theta2),
                               np.asarray(jnp.einsum("dl,dlk->dk", cnt, mu2)),
                               rtol=1e-5, atol=1e-5)
    # residual pack is the |delta| scatter
    assert float(jnp.sum(rpack)) >= float(jnp.abs(jnp.sum(dpack)))


def test_two_step_selection_matches_numpy():
    key = jax.random.PRNGKey(1)
    r = jax.random.uniform(key, (50, 16))
    r_w = jnp.sum(r, 1)
    sel_w = power.select_power_words(r_w, 10)
    np_top = np.argsort(-np.asarray(r_w))[:10]
    assert set(np.asarray(sel_w).tolist()) == set(np_top.tolist())
    sel_k = power.select_power_topics(r, sel_w, 4)
    for i, w in enumerate(np.asarray(sel_w)):
        expect = set(np.argsort(-np.asarray(r)[w])[:4].tolist())
        assert set(np.asarray(sel_k)[i].tolist()) == expect


def test_pack_scatter_roundtrip():
    key = jax.random.PRNGKey(2)
    mat = jax.random.normal(key, (30, 12))
    sel_w = jnp.asarray([4, 9, 0, 22], jnp.int32)
    sel_k = jnp.asarray([[0, 3], [1, 2], [5, 7], [10, 11]], jnp.int32)
    packed = power.pack_rows(mat, sel_w, sel_k)
    again = power.pack_rows(power.scatter_set_rows(jnp.zeros_like(mat), sel_w,
                                                   sel_k, packed), sel_w, sel_k)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(again))
    added = power.scatter_add_rows(mat, sel_w, sel_k, packed)
    np.testing.assert_allclose(np.asarray(power.pack_rows(added, sel_w, sel_k)),
                               np.asarray(packed) * 2, rtol=1e-6)


def test_tokens_from_batch_matches_loop_reference(corpus):
    """The np.repeat vectorization of gibbs.tokens_from_batch must emit
    token arrays identical (order included) to the per-token double loop
    it replaced — the setup bottleneck of the accuracy benchmark."""
    from repro.core.gibbs import tokens_from_batch

    def reference(batch):
        wid = np.asarray(batch.word_ids)
        cnt = np.asarray(batch.counts).astype(np.int64)
        docs, words = [], []
        for d in range(wid.shape[0]):
            for l in range(wid.shape[1]):
                c = int(cnt[d, l])
                if c > 0:
                    docs.extend([d] * c)
                    words.extend([int(wid[d, l])] * c)
        return np.asarray(docs, np.int32), np.asarray(words, np.int32)

    docs, _ = corpus
    for batch in (docs_to_padded(docs),
                  docs_to_padded(docs[:3], max_len=8),
                  MiniBatch(jnp.zeros((2, 4), jnp.int32),
                            jnp.zeros((2, 4), jnp.float32))):
        got_d, got_w = tokens_from_batch(batch)
        ref_d, ref_w = reference(batch)
        np.testing.assert_array_equal(got_d, ref_d)
        np.testing.assert_array_equal(got_w, ref_w)
        assert got_d.dtype == np.int32 and got_w.dtype == np.int32


# ----------------------------------------------------- communication claims

def test_comm_bytes_follow_eq5_and_eq6(corpus):
    """The byte meter must reproduce the paper's complexity expressions."""
    docs, _ = corpus
    cfg = LDAConfig(vocab_size=120, num_topics=8, lambda_w=0.25, lambda_k_abs=4,
                    inner_iters=6, residual_tol=1e-9)
    stream = sharded_minibatch_stream(docs, 32, 4)
    fn, meter = make_sim_minibatch_fn(cfg, 4, "power")
    b = next(iter(stream))
    fn(b.word_ids, b.counts, jnp.zeros((120, 8)), jax.random.PRNGKey(0),
       jnp.float32(1.0))
    P, Pk = cfg.num_power_words, cfg.num_power_topics
    # per power-loop iteration: packed phi + packed r  (r_w sync is model-axis)
    assert meter.phase_bytes("power") == 2 * P * Pk * 4
    # dense phase: full phi + full r once (Fig. 4 lines 9-10)
    assert meter.phase_bytes("dense") == 2 * 120 * 8 * 4
    assert power_sync_bytes(P, Pk, 120) < dense_sync_bytes(120, 8)


def test_bf16_sync_halves_bytes(corpus):
    docs, _ = corpus
    cfg = CFG
    stream = sharded_minibatch_stream(docs, 32, 4)
    fn, meter = make_sim_minibatch_fn(cfg, 4, "power", sync_dtype=jnp.bfloat16)
    b = next(iter(stream))
    fn(b.word_ids, b.counts, jnp.zeros((cfg.vocab_size, cfg.num_topics)),
       jax.random.PRNGKey(0), jnp.float32(1.0))
    P, Pk = cfg.num_power_words, cfg.num_power_topics
    assert meter.phase_bytes("power") == 2 * P * Pk * 2  # half of fp32


# ------------------------------------------------------------ end-to-end

def test_learning_recovers_topics_beats_random(corpus):
    docs, true_phi = corpus
    train, test = train_test_split_counts(docs, 0)
    cfg = LDAConfig(vocab_size=120, num_topics=8, lambda_w=0.3, lambda_k_abs=6,
                    inner_iters=15, residual_tol=0.01)
    phi, hist, _ = run_stream(sharded_minibatch_stream(train, 32, 4), cfg,
                              num_shards=4, sync_mode="power", seed=11)
    key = jax.random.PRNGKey(5)
    ppl = perplexity.evaluate(key, phi, docs_to_padded(train),
                              docs_to_padded(test), cfg)
    ppl_rand = perplexity.evaluate(key, jnp.zeros_like(phi),
                                   docs_to_padded(train), docs_to_padded(test),
                                   cfg)
    assert ppl < 0.6 * ppl_rand, (ppl, ppl_rand)
    assert not np.isnan(ppl)


def test_residual_decreases_within_minibatch(corpus):
    """Fig. 5: the residual is a convergence signal — it must decrease."""
    docs, _ = corpus
    batch = docs_to_padded(docs)
    cfg = LDAConfig(vocab_size=120, num_topics=8, inner_iters=10,
                    residual_tol=1e-9)
    _, _, _, trace = ref.batch_bp(jax.random.PRNGKey(0), batch, cfg, iters=60)
    tr = np.asarray(trace)
    # early iterations may oscillate while topics differentiate; by iter 60
    # the residual must be far below its early level (Fig. 5 shape).
    assert tr[-1] < tr[1] * 0.1, tr[::5]
