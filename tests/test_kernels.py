"""Per-kernel validation: shape/dtype sweeps + hypothesis properties,
always against the pure-jnp ref.py oracle (interpret mode on CPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import LDAConfig, MiniBatch
from repro.core import power as pw
from repro.core.pobp import dense_sweep
from repro.core.sync import LocalReducer
from repro.kernels.bp_update.kernel import bp_update_tokens, token_tile
from repro.kernels.bp_update.ops import dense_sweep_pallas
from repro.kernels.bp_update.ref import bp_update_tokens_ref
from repro.kernels.power_pack import ops as pp_ops
from repro.kernels.power_pack.ref import pack_rows_ref


def _rand_inputs(key, T, K, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    c = jax.random.randint(k1, (T, 1), 0, 4).astype(dtype)
    mu = jax.nn.softmax(jax.random.normal(k2, (T, K)), -1).astype(dtype)
    th = (jax.random.uniform(k3, (T, K)) * 5).astype(dtype)
    ph = (jax.random.uniform(k4, (T, K)) * 5).astype(dtype)
    pt = jnp.sum(ph, 0, keepdims=True) + 1.0
    return c, mu, th, ph, pt


# ------------------------------------------------------------ bp_update

@pytest.mark.parametrize("T,K", [(8, 128), (64, 128), (256, 256), (40, 384),
                                 (512, 1024), (16, 2048)])
def test_bp_update_shape_sweep(T, K):
    c, mu, th, ph, pt = _rand_inputs(jax.random.PRNGKey(T * K), T, K)
    kw = dict(alpha=0.1, beta=0.01, wbeta=1.2)
    m1, r1 = bp_update_tokens(c, mu, th, ph, pt, **kw)
    m2, r2 = bp_update_tokens_ref(c, mu, th, ph, pt, **kw)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-5, atol=1e-6)
    # normalized output
    np.testing.assert_allclose(np.asarray(jnp.sum(m1, -1)), 1.0, atol=1e-5)


def test_bp_update_dtype_bf16():
    c, mu, th, ph, pt = _rand_inputs(jax.random.PRNGKey(0), 32, 128,
                                     dtype=jnp.bfloat16)
    kw = dict(alpha=0.1, beta=0.01, wbeta=1.2)
    m1, r1 = bp_update_tokens(c, mu, th, ph, pt, **kw)
    m2, r2 = bp_update_tokens_ref(c, mu, th, ph, pt, **kw)
    np.testing.assert_allclose(np.asarray(m1, dtype=np.float32),
                               np.asarray(m2, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


def test_token_tile_fits_vmem():
    """The tile fits the shared VMEM budget — which is also the kernel's
    Mosaic VMEM limit — with its blocks double-buffered, and is the
    largest power of two that does."""
    from repro.kernels import vmem_budget
    from repro.kernels.bp_update.kernel import vmem_bytes
    for K in (128, 512, 2048, 4096, 10240):
        tt = token_tile(K)
        assert tt % 8 == 0 and tt >= 8
        assert vmem_bytes(tt, K) <= vmem_budget()
        assert tt == 512 or vmem_bytes(2 * tt, K) > vmem_budget()


def test_dense_sweep_pallas_matches_jnp_sweep():
    """ops.py wrapper (gathers + kernel + scatter) vs core.pobp.dense_sweep."""
    key = jax.random.PRNGKey(3)
    cfg = LDAConfig(vocab_size=90, num_topics=16)
    D, L = 12, 20
    wid = jax.random.randint(key, (D, L), 0, cfg.vocab_size).astype(jnp.int32)
    cnt = jax.random.randint(key, (D, L), 0, 3).astype(jnp.float32)
    batch = MiniBatch(wid, cnt)
    mu = jax.nn.softmax(jax.random.normal(key, (D, L, cfg.num_topics)), -1)
    phi = jax.random.uniform(key, (cfg.vocab_size, cfg.num_topics)) * 3
    phi_tot = jnp.sum(phi, 0)
    m1, r1 = dense_sweep_pallas(batch, mu, phi, phi_tot, cfg)
    m2, r2 = dense_sweep(batch, mu, phi, phi_tot, cfg, LocalReducer())
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ power_pack

def _pp(W, K, P, Pk, rows=None, guard=None, id=None):
    """One power_pack case: random distinct rows (rows=None) or the given
    ones; entries on the ``guard`` row carry zeros."""
    return pytest.param(W, K, P if rows is None else len(rows), Pk, rows,
                        guard, id=id or f"{W}-{K}-{P}-{Pk}")


@pytest.mark.parametrize("W,K,P,Pk,rows,guard", [
    _pp(64, 32, 8, 4), _pp(128, 256, 16, 50), _pp(500, 96, 50, 10),
    _pp(32, 130, 4, 130),
    # every row of a tile, and of the tiles beside it (given unsorted)
    _pp(64, 32, 0, 4, list(range(31, 7, -1)), id="full-adjacent-tiles"),
    _pp(64, 32, 0, 4, [9 * t for t in range(8)], id="one-row-per-tile"),
    # W % 8 = 3 and 4, with rows of the partial last tile and without
    _pp(67, 40, 0, 6, [66, 64, 1, 30, 65, 12], id="w8r3-tail"),
    _pp(67, 40, 0, 6, [0, 7, 8, 40, 63], id="w8r3-no-tail"),
    _pp(100, 128, 0, 16, [97, 5, 99, 96, 40, 98, 95, 8, 9], id="w8r4-tail"),
    _pp(100, 128, 0, 16, [95, 5, 88, 40, 8, 9, 0], id="w8r4-no-tail"),
    _pp(67, 16, 0, 16, [66, 3, 64, 20], id="pk-eq-k-tail"),
    _pp(131, 24, 0, 8, list(range(130, 0, -13)), id="descending"),
    # repeated rows (body and tail) whose values must all add up
    _pp(67, 32, 0, 5, [3, 17, 3, 66, 40, 17, 3, 66, 64, 65, 66],
        id="repeated"),
    # the capacity ladder: dead slots all on the first guard row, zeros
    _pp(72, 32, 0, 4, [7, 49, 12, 30, 0] + [50] * 7, guard=50,
        id="guard-row"),
    _pp(67, 32, 0, 4, [7, 61, 12, 30, 64, 2] + [65] * 14, guard=65,
        id="guard-row-in-tail"),
])
def test_power_pack_shape_sweep(W, K, P, Pk, rows, guard):
    rng = np.random.default_rng(W + K)
    mat = jnp.asarray(rng.normal(size=(W, K)).astype(np.float32))
    sel_w = (rng.choice(W, P, replace=False) if rows is None
             else np.asarray(rows))
    sel_k = jnp.asarray(np.stack([rng.choice(K, Pk, replace=False)
                                  for _ in range(P)]).astype(np.int32))
    vals = rng.normal(size=(P, Pk)).astype(np.float32)
    vals[sel_w == guard] = 0.0
    sel_w, vals = jnp.asarray(sel_w.astype(np.int32)), jnp.asarray(vals)
    np.testing.assert_allclose(np.asarray(pp_ops.pack_rows(mat, sel_w, sel_k)),
                               np.asarray(pack_rows_ref(mat, sel_w, sel_k)),
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(pp_ops.scatter_add_rows(mat, sel_w, sel_k, vals)),
        np.asarray(jax.jit(pw.scatter_add_rows)(mat, sel_w, sel_k, vals)))


@pytest.mark.parametrize("W,K,P,Pk", [(64, 32, 12, 4), (67, 40, 20, 6),
                                      (131, 16, 30, 16)])
def test_power_pack_scatter_skips_sentinel_ids(W, K, P, Pk):
    """A topic shard's selection holds the sentinel id K in the slots
    another shard owns, and rows that own no selected topic at all (all
    sentinel, in the body and in the partial last tile): the scatter adds
    nothing there, as XLA's out-of-bounds drop does."""
    rng = np.random.default_rng(W * K + P)
    mat = jnp.asarray(rng.normal(size=(W, K)).astype(np.float32))
    rest = np.setdiff1d(np.arange(W), [W - 1, 3])
    # a tail row and a body row first
    sel_w = np.r_[W - 1, 3, rng.choice(rest, P - 2, replace=False)]
    sel_k = np.stack([rng.choice(K, Pk, replace=False) for _ in range(P)])
    sel_k[rng.random((P, Pk)) < 0.6] = K
    sel_k[:2] = K                           # own no selected topic
    vals = rng.normal(size=(P, Pk)).astype(np.float32)
    args = (jnp.asarray(sel_w.astype(np.int32)),
            jnp.asarray(sel_k.astype(np.int32)), jnp.asarray(vals))
    got = np.asarray(pp_ops.scatter_add_rows(mat, *args))
    np.testing.assert_array_equal(
        got, np.asarray(jax.jit(pw.scatter_add_rows)(mat, *args)))
    np.testing.assert_array_equal(got[[W - 1, 3]], np.asarray(mat)[[W - 1, 3]])


def test_power_pack_scatter_moves_tail_entries_alone():
    """At W % 8 != 0 no XLA scatter into the [W, K] matrix takes more than
    7 * Pk updates: the partial last tile's rows get only their entries."""
    W, K, P, Pk = 67, 16, 12, 5
    text = jax.jit(pp_ops.scatter_add_rows).lower(
        jax.ShapeDtypeStruct((W, K), jnp.float32),
        jax.ShapeDtypeStruct((P,), jnp.int32),
        jax.ShapeDtypeStruct((P, Pk), jnp.int32),
        jax.ShapeDtypeStruct((P, Pk), jnp.float32)).as_text()
    sigs = re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<([^>]*)>, '
                      r'tensor<[^>]*>, tensor<([^>]*)>\)', text, re.S)
    assert sigs, "no scatter found in the module"
    updates = [int(np.prod([int(d) for d in u.split("x")[:-1]]))
               for op, u in sigs if op == f"{W}x{K}xf32"]
    assert all(n <= 7 * Pk for n in updates), updates


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 30), st.integers(2, 20), st.data())
def test_power_pack_property_roundtrip(W, K, data):
    """hypothesis: pack(scatter(zeros, idx, vals)) == vals for any valid idx."""
    P = data.draw(st.integers(1, W))
    Pk = data.draw(st.integers(1, K))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    sel_w = jnp.asarray(rng.choice(W, P, replace=False).astype(np.int32))
    sel_k = jnp.asarray(np.stack([rng.choice(K, Pk, replace=False)
                                  for _ in range(P)]).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(P, Pk)).astype(np.float32))
    zero = jnp.zeros((W, K), jnp.float32)
    scattered = pp_ops.scatter_add_rows(zero, sel_w, sel_k, vals)
    back = pp_ops.pack_rows(scattered, sel_w, sel_k)
    np.testing.assert_allclose(np.asarray(back), np.asarray(vals), rtol=1e-6,
                               atol=1e-6)
    # total mass conserved
    np.testing.assert_allclose(float(jnp.sum(scattered)), float(jnp.sum(vals)),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.sampled_from([128, 256, 384]), st.data())
def test_bp_update_property_normalized_and_positive(T, K, data):
    """hypothesis: output is a prob. dist. and residual >= 0, any T/K/counts."""
    seed = data.draw(st.integers(0, 2**31))
    c, mu, th, ph, pt = _rand_inputs(jax.random.PRNGKey(seed), T, K)
    m1, r1 = bp_update_tokens(c, mu, th, ph, pt, alpha=0.05, beta=0.02, wbeta=2.0)
    assert not np.any(np.isnan(np.asarray(m1)))
    np.testing.assert_allclose(np.asarray(jnp.sum(m1, -1)), 1.0, atol=1e-4)
    assert np.all(np.asarray(r1) >= 0)
