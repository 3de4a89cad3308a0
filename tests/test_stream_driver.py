"""Production streaming driver regressions (ISSUE 2): comm-meter retrace
idempotence + analytic Eq. 5/6 match, shape-bucketed streaming parity with
a bounded compile count, crash-resume trajectory, prefetch thread
lifecycle, and the power_sync_bytes itemsize fix."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LDAConfig, MiniBatch, init_train_state,
                        make_train_step, run_stream)
from repro.core.sync import dense_sync_bytes, power_sync_bytes
from repro.data import (bucketed_minibatch_stream, docs_to_padded, lda_corpus,
                        minibatch_stream, sharded_minibatch_stream)

W, K = 120, 8
CFG = LDAConfig(vocab_size=W, num_topics=K, lambda_w=0.25, lambda_k_abs=4,
                inner_iters=6, residual_tol=1e-9)


@pytest.fixture(scope="module")
def docs():
    d, _, _ = lda_corpus(0, 64, W, K, doc_len_mean=40)
    return d


# ------------------------------------------------------- comm meter (Eq. 5/6)

def _stream_with_lengths(docs, lengths, num_shards=2):
    chunk = docs[:32]
    for L in lengths:
        b = docs_to_padded(chunk, max_len=L)
        D, Lp = b.word_ids.shape
        yield MiniBatch(
            word_ids=b.word_ids.reshape(num_shards, D // num_shards, Lp),
            counts=b.counts.reshape(num_shards, D // num_shards, Lp))


@pytest.mark.parametrize("mode", ["power", "dense"])
def test_meter_bytes_invariant_under_retrace(docs, mode):
    """A variable-L stream retraces the step; the byte meter must report the
    same per-mini-batch payload as an identical fixed-L stream (the seed
    meter double-counted every psum on retrace: 7680 vs 3840)."""
    _, _, m_fixed = run_stream(_stream_with_lengths(docs, [8, 8, 8]), CFG,
                               num_shards=2, sync_mode=mode)
    _, _, m_var = run_stream(_stream_with_lengths(docs, [8, 16, 8]), CFG,
                             num_shards=2, sync_mode=mode)
    assert m_fixed.bytes_by_phase == m_var.bytes_by_phase
    # dense phase (Fig. 4 lines 9-10): full phi + full r, Eq. 5 payloads
    assert m_var.phase_bytes("dense") == 2 * dense_sync_bytes(W, K)
    if mode == "power":
        P, Pk = CFG.num_power_words, CFG.num_power_topics
        # per power-loop iteration: packed phi + packed r (Eq. 6; the r_w
        # term of power_sync_bytes travels on the model axis, which the
        # simulation's LocalReducer never records)
        assert m_var.phase_bytes("power") == (
            power_sync_bytes(P, Pk, W) - W * 4)
    else:
        assert m_var.phase_bytes("dense_loop") == 2 * dense_sync_bytes(W, K)


def test_per_minibatch_bytes_formula(docs):
    """dense + (iters-1) * sparse (the documented mini-batch total)."""
    _, hist, meter = run_stream(_stream_with_lengths(docs, [8]), CFG,
                                num_shards=2, sync_mode="power")
    iters = hist[0]["iters"]
    by = meter.bytes_by_phase
    once = by["dense"] + by["tokens"]
    assert meter.per_minibatch_bytes(iters) == once + (iters - 1) * by["power"]


def test_per_minibatch_bytes_bills_model_loop_phases_per_iteration():
    """Loop-body model-axis psums carry distinct '*_loop' phase names so
    the dense + (iters-1)*sparse split stays correct on topic-sharded
    meshes (the outer 'model_rw' is once-per-batch, the in-body
    'model_rw_loop' is per-iteration)."""
    from repro.core.sync import CommMeter, MeshReducer

    meter = CommMeter()
    red = MeshReducer("s", meter=meter)

    def shard(x):
        r = red.psum(x, "model_rw", compress=False)        # once per batch
        def body(c):
            # 0.25: the 2-shard psum doubles c, so the carry must shrink
            # by more than 2x per iteration for the loop to terminate
            return red.psum(c, "model_rw_loop", compress=False) * 0.25
        return jax.lax.while_loop(lambda c: jnp.sum(c) > 1e-3, body, r)

    jax.jit(lambda x: jax.vmap(shard, axis_name="s")(x))(jnp.ones((2, 8)))
    assert meter.per_minibatch_bytes(5) == 8 * 4 + 4 * (8 * 4)


def test_meter_max_merges_shape_variant_retraces():
    """Shape-DEPENDENT payloads (e.g. the L-dependent model_norm psum on a
    topic-sharded mesh) across bucket retraces must report what the worst
    single mini-batch pays — not the sum over every bucket variant."""
    from repro.core.sync import CommMeter, MeshReducer

    meter = CommMeter()
    red = MeshReducer("s", meter=meter)

    def fn(x):
        return jax.vmap(lambda y: red.psum(y, "model_norm", compress=False),
                        axis_name="s")(x)

    jit_fn = jax.jit(fn)
    jit_fn(jnp.ones((2, 8)))
    jit_fn(jnp.ones((2, 8)))      # cache hit: no new trace
    jit_fn(jnp.ones((2, 16)))     # bucket retrace: bigger payload
    assert meter.phase_bytes("model_norm") == 16 * 4  # max, not 8*4 + 16*4


def test_make_len_buckets_rejects_non_growing_ladder():
    from repro.data import make_len_buckets

    assert make_len_buckets(50) == (8, 16, 32, 64)
    with pytest.raises(ValueError):
        make_len_buckets(64, growth=1.0)


def test_power_sync_bytes_threads_itemsize():
    """Eq. 6 payloads for sync_dtype=bfloat16: the packed terms honor
    itemsize while the r_w term defaults to float32 width (the repo's
    residual psums are compress=False), overridable via rw_itemsize."""
    P, Pk, Wv = 10, 4, 100
    assert power_sync_bytes(P, Pk, Wv) == 2 * P * Pk * 4 + Wv * 4
    assert power_sync_bytes(P, Pk, Wv, itemsize=2) == 2 * P * Pk * 2 + Wv * 4
    assert power_sync_bytes(P, Pk, Wv, itemsize=2, rw_itemsize=2) == (
        2 * P * Pk * 2 + Wv * 2)


# ------------------------------------------------- shape-bucketed streaming

def _variable_length_corpus():
    """Sequential chunks with very different document lengths, so a
    16-doc mini-batch stream crosses several natural padded shapes."""
    out = []
    for seed, mean in ((1, 10), (2, 30), (3, 55), (4, 12)):
        d, _, _ = lda_corpus(seed, 16, W, K, doc_len_mean=mean)
        out.extend(d)
    return out


def test_bucketed_stream_matches_unbucketed_with_bounded_compiles():
    """Bucketing pads L up to a fixed ladder: phi_acc must agree with the
    natural-shape stream (cfg.init_pad_len makes the random init
    L-invariant; padding slots carry zero counts) while the step compiles
    at most once per bucket instead of once per shape."""
    docs = _variable_length_corpus()
    buckets = (16, 32, 64)
    cfg = LDAConfig(vocab_size=W, num_topics=K, lambda_w=0.25, lambda_k_abs=4,
                    inner_iters=4, residual_tol=0.0, init_pad_len=buckets[-1])

    phi_ref, hist_ref, _ = run_stream(
        sharded_minibatch_stream(docs, 16, num_shards=2), cfg,
        num_shards=2, seed=7)

    step, _ = make_train_step(cfg, num_shards=2)
    state = init_train_state(cfg, seed=7)
    traj = []
    for batch in bucketed_minibatch_stream(docs, 16, num_shards=2,
                                           len_buckets=buckets):
        state, diag = step(state, batch.word_ids, batch.counts)
        traj.append(float(diag["mean_r"]))

    assert step._cache_size() <= len(buckets)
    np.testing.assert_allclose(np.asarray(state.phi_acc),
                               np.asarray(phi_ref), rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(traj, [h["mean_r"] for h in hist_ref],
                               rtol=1e-4, atol=1e-7)


# --------------------------------------------------------- crash-resume

def _driver_args(ckpt_dir=None, **over):
    from repro.launch.lda_train import default_args
    base = dict(minibatches=8, docs_per_batch=16, shards=2, vocab=W, topics=K,
                lambda_k=4, inner_iters=4, tol=1e-9, log_every=0,
                eval_every=0, doc_len_means="10,20,30", len_buckets="16,32",
                ckpt_every=3, seed=3, ckpt_dir=ckpt_dir)
    base.update(over)
    return default_args(**base)


def test_crash_resume_reproduces_trajectory(tmp_path):
    """--crash-at N + rerun must continue from the latest checkpoint and
    reproduce the uninterrupted mean_r trajectory (full state — phi_acc,
    m, RNG, stream cursor — round-trips through repro.dist.checkpoint)."""
    from repro.launch.lda_train import train_loop

    full = train_loop(_driver_args())

    ckdir = str(tmp_path / "ck")
    with pytest.raises(SystemExit):
        train_loop(_driver_args(ckpt_dir=ckdir, crash_at=5))
    # rerun the SAME command: the simulated failure must not re-fire on a
    # resumed run, so this completes
    resumed = train_loop(_driver_args(ckpt_dir=ckdir, crash_at=5))

    assert resumed["first_m"] == 3          # resumed at the m=3 checkpoint
    np.testing.assert_allclose(resumed["mean_r"],
                               full["mean_r"][resumed["first_m"]:],
                               rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(resumed["phi_acc"], full["phi_acc"],
                               rtol=1e-6, atol=1e-7)


def test_resume_rejects_mismatched_flags(tmp_path):
    """A checkpoint written under one (seed, sync) must not be silently
    spliced into a run with different flags."""
    from repro.launch.lda_train import train_loop

    ckdir = str(tmp_path / "ck")
    train_loop(_driver_args(ckpt_dir=ckdir, minibatches=3, ckpt_every=3))
    with pytest.raises(ValueError, match="seed"):
        train_loop(_driver_args(ckpt_dir=ckdir, minibatches=6, seed=99))


# ------------------------------------------------------ prefetch lifecycle

def _alive_prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "repro-prefetch" and t.is_alive()]


def test_prefetch_thread_exits_when_stream_abandoned(docs):
    """A consumer that abandons the generator early (crashed driver,
    cancelled request) must not leak the worker: the seed blocked forever
    on q.put with an unreachable t.join."""
    gen = minibatch_stream(docs, 4, prefetch=1)
    next(gen)
    assert _alive_prefetch_threads(), "worker should be running mid-stream"
    gen.close()                      # delivers GeneratorExit
    deadline = time.time() + 5.0
    while _alive_prefetch_threads() and time.time() < deadline:
        time.sleep(0.02)
    assert not _alive_prefetch_threads(), "prefetch worker leaked"


def test_prefetch_stream_still_yields_everything(docs):
    n_direct = sum(1 for _ in minibatch_stream(docs, 8, prefetch=0))
    n_prefetch = sum(1 for _ in minibatch_stream(docs, 8, prefetch=3))
    assert n_direct == n_prefetch == -(-len(docs) // 8)


def test_prefetch_worker_exception_propagates():
    bad = [(None, None)]  # len(None) inside docs_to_padded -> TypeError
    with pytest.raises(TypeError):
        list(minibatch_stream(bad, 1, prefetch=2))


# ------------------------------------------------- shard_map production path

def test_driver_shard_map_backend_smoke():
    """The driver's --backend shard_map executes the SAME per-shard body the
    dryrun cell compiles (make_mesh_shard_fn) on an 8-device CPU mesh.
    Subprocess: the host device count is fixed before jax starts."""
    import os
    import subprocess
    import sys

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.lda_train",
         "--backend", "shard_map", "--mesh-shape", "4,2",
         "--minibatches", "2", "--docs-per-batch", "16", "--vocab", "64",
         "--topics", "8", "--lambda-k", "4", "--inner-iters", "3",
         "--log-every", "1", "--no-warmup-buckets"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[done] 2 minibatches" in out.stdout
    # the carry is placed as the step returns it: one program, no recompile
    assert "compiles=2" not in out.stdout
    # topic-sharded phases must appear (model-axis psums are real here),
    # including the per-iteration loop phase billed by per_minibatch_bytes
    assert "model_norm" in out.stdout and "model_rw" in out.stdout
    assert "model_rw_loop" in out.stdout


# ------------------------------------------ PS billing model (DESIGN.md §15)

def test_ps_reducer_splits_w_rows_payloads_into_push_pull_legs():
    """Under the parameter server every W-proportional payload crosses the
    wire twice — delta push + slice pull — and both legs stay w_rows-marked
    so touched-granularity billing (`bytes_by_phase_at`) scales them."""
    from repro.core.sync import CommMeter, LocalReducer, PSReducer

    meter = CommMeter()
    red = PSReducer(LocalReducer(meter=meter))
    assert red.meter is meter                       # inherited from inner
    x = jnp.ones((W, K), jnp.float32)
    out = red.psum(x, "power", compress=False, w_rows=W)
    np.testing.assert_array_equal(out, x)           # single worker: identity
    by = meter.bytes_by_phase
    assert by == {"power.push": W * K * 4, "power.pull": W * K * 4}
    # touched-row billing: pass the measured touched count as live_w
    touched = meter.bytes_by_phase_at(30)
    assert touched["power.push"] == touched["power.pull"] == 30 * K * 4
    # bf16 wire override halves both legs and round-trips the dtype
    out16 = red.psum(x, "dense_loop", dtype=jnp.bfloat16, w_rows=W)
    assert out16.dtype == jnp.float32
    assert meter.phase_bytes("dense_loop.push") == W * K * 2
    assert meter.phase_bytes("dense_loop.pull") == W * K * 2
    # per-topic payloads never live on row-sharded servers; with a single
    # worker (LocalReducer inner) they need no communication at all
    red.psum(jnp.ones((K,)), "model_norm", compress=False)
    assert "model_norm" not in meter.bytes_by_phase


def test_ps_reducer_bills_worker_allreduce_and_dedups_retraces():
    """With several workers (Mesh inner) non-row payloads still need a
    worker all-reduce and bill unchanged; push/pull legs dedup across
    plain retraces and max-merge across shape-bucket variants exactly
    like the allreduce phases they replace."""
    from repro.core.sync import CommMeter, MeshReducer, PSReducer

    red = PSReducer(MeshReducer("s"))
    meter = red.meter

    def run(L):
        def shard(x, y):
            a = red.psum(x, "power", compress=False, w_rows=W)
            b = red.psum(y, "model_norm", compress=False)
            return a, b
        return jax.jit(lambda x, y: jax.vmap(shard, axis_name="s")(x, y))(
            jnp.ones((2, W, K)), jnp.ones((2, L)))

    a, b = run(8)
    np.testing.assert_array_equal(np.asarray(a)[0], np.full((W, K), 2.0))
    np.testing.assert_array_equal(np.asarray(b)[0], np.full((8,), 2.0))
    run(8)                                          # plain retrace: no-op
    run(16)                                         # shape bucket: max-merge
    by = meter.bytes_by_phase
    assert by["power.push"] == by["power.pull"] == W * K * 4
    assert by["model_norm"] == 16 * 4


def test_per_minibatch_bytes_counts_push_pull_legs_as_loop_phases():
    """The power loop's push/pull legs are per-inner-iteration payloads:
    dense + (iters-1) * sparse must bill them (iters-1) times while the
    once-per-batch dense legs bill once."""
    from repro.core.sync import CommMeter, PSReducer, SimReducer

    meter = CommMeter()
    red = PSReducer(SimReducer(meter=meter))
    x = jnp.ones((2, 10, K), jnp.float32)           # leading N=2 shard axis
    out = red.psum(x, "power", compress=False, w_rows=10)
    np.testing.assert_array_equal(np.asarray(out)[0], np.asarray(out)[1])
    red.psum(jnp.ones((2, W, K)), "dense", compress=False, w_rows=W)
    leg_loop, leg_once = 2 * 10 * K * 4, 2 * W * K * 4
    assert meter.per_minibatch_bytes(4) == 2 * leg_once + 3 * 2 * leg_loop


def test_touched_power_sync_bytes_caps_rows_and_threads_itemsize():
    """Touched-W Eq. 6: the packed exchange covers at most min(P, touched)
    rows and the residual leg shrinks to the touched rows."""
    from repro.core.sync import power_sync_bytes, touched_power_sync_bytes

    P, Pk = 50, 8
    assert touched_power_sync_bytes(P, Pk, 20) == 2 * 20 * Pk * 4 + 20 * 4
    # more touched rows than power slots: packed legs cap at P
    assert touched_power_sync_bytes(P, Pk, 90) == 2 * P * Pk * 4 + 90 * 4
    # touching the whole vocabulary degenerates to the dense-W Eq. 6 model
    assert touched_power_sync_bytes(P, Pk, W) == power_sync_bytes(P, Pk, W)
    # compressed payload width threads through the packed legs only
    assert (touched_power_sync_bytes(P, Pk, 20, itemsize=2)
            == 2 * 20 * Pk * 2 + 20 * 4)
