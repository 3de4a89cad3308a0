"""The program's tracing (`repro.obs`): span and stamp rings and their
bounds, the ring's clock against the profiler's, the join of a compiled
POBP step's instructions to its phase scopes, and the slab engine's
spans, per-request stamps and bounded latency window."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.pobp import make_train_step
from repro.core.types import LDAConfig, LDATrainState
from repro.data.synthetic import lda_corpus
from repro.serve import SlabEngine
from repro.serve import engine as engine_mod

STEP_SCOPES = ("pobp.init", "pobp.dense_sweep", "pobp.dense_sync",
               "pobp.select", "pobp.selective_sweep", "pobp.power_sync",
               "pobp.scatter", "pobp.accumulate")


@pytest.fixture(autouse=True)
def empty_rings():
    obs.clear()
    yield
    obs.clear()


# ------------------------------------------------------------ the rings


def test_span_records_name_and_host_interval():
    t0 = time.perf_counter_ns()
    with obs.span("outer") as sp:
        with obs.span("inner"):
            time.sleep(0.002)
    t1 = time.perf_counter_ns()
    (inner, s_i, e_i), (outer, s_o, e_o) = obs.records()
    assert (inner, outer) == ("inner", "outer")      # in the order ended
    assert t0 <= s_o <= s_i < e_i <= e_o <= t1
    assert e_i - s_i >= 2e6
    assert sp.t0_ns == s_o
    assert obs.records(s_i, e_i) == [("inner", s_i, e_i)]
    assert obs.records(e_o + 1) == []


def test_span_is_recorded_when_its_block_raises():
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError
    assert [r[0] for r in obs.records()] == ["failing"]


def test_span_ring_keeps_the_newest():
    for i in range(obs.SPAN_RING + 5):
        with obs.span(f"s{i}"):
            pass
    recs = obs.records()
    assert len(recs) == obs.SPAN_RING
    assert recs[0][0] == "s5" and recs[-1][0] == f"s{obs.SPAN_RING + 4}"


def test_stamp_ring_keeps_the_newest():
    for i in range(obs.STAMP_RING + 3):
        obs.stamp("submit", i, t_ns=i)
    reqs = obs.requests()
    assert len(reqs) == obs.STAMP_RING
    assert min(reqs) == 3


def test_requests_gather_every_stamp_of_a_request_in_range():
    obs.stamp("submit", "a", t_ns=100, tenant="t1")
    obs.stamp("submit", "b", t_ns=150)
    obs.stamp("done", "a", t_ns=900)
    obs.stamp("done", "b", t_ns=2000)
    obs.stamp("submit", "c", t_ns=3000)
    now = time.perf_counter_ns()
    obs.stamp("refill", "c")                       # stamped now
    got = obs.requests(800, 1000)
    # "a" has a stamp in range: all of its stamps come back
    assert got == {"a": {"submit": 100, "done": 900, "tenant": "t1"}}
    assert set(obs.requests(1000, 2500)) == {"b"}
    assert obs.requests()["c"]["refill"] >= now
    obs.stamp("submit", "a", t_ns=950)             # a later stamp replaces
    assert obs.requests(800, 1000)["a"]["submit"] == 950


# ---------------------------------------------------- the trace's clock


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out[e.name] = int(e.start_ns)
    return out


def test_ring_and_profiler_trace_differ_by_one_offset(tmp_path):
    """Each span's ring start and its TraceAnnotation's start in the
    written trace differ by the same offset, within 20 us: what a
    reader's alignment of the ring to a trace rests on."""
    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(12):
            with obs.span(f"obs_clock.{i}"):
                jax.block_until_ready(x @ x)
                time.sleep(0.001 * (i % 4))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    offsets = [events[n] - s for n, s, _ in obs.records()
               if n.startswith("obs_clock.")]
    assert len(offsets) == 12
    assert max(offsets) - min(offsets) <= 20_000


# ------------------------------------------------- compiled step scopes


@pytest.fixture(scope="module")
def step_text():
    """A tiny two-shard POBP step compiled on the CPU (two shards, so
    the power sync's psums are in the program)."""
    cfg = LDAConfig(vocab_size=256, num_topics=16, lambda_w=0.1,
                    lambda_k_abs=4, inner_iters=3, residual_tol=0.0,
                    impl="xla")
    step, _ = make_train_step(cfg, num_shards=2)
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = LDATrainState(phi_acc=sds((256, 16), jnp.float32),
                          m=sds((), jnp.int32),
                          rng=sds(key.shape, key.dtype))
    return step.lower(state, sds((2, 8, 16), jnp.int32),
                      sds((2, 8, 16), jnp.float32)).compile().as_text()


def test_every_pobp_phase_owns_compiled_instructions(step_text):
    scopes = obs.op_scopes(step_text, STEP_SCOPES)
    owned = {s for s in scopes.values() if s is not None}
    assert owned == set(STEP_SCOPES)
    # the map covers every instruction of the module
    assert set(scopes) == set(obs.hlo_ops(step_text))


def test_power_selection_owns_the_top_k(step_text):
    ops = obs.hlo_ops(step_text)
    scopes = obs.op_scopes(step_text, STEP_SCOPES)
    top_k = [n for n, (op, name) in ops.items()
             if (op == "custom-call" and name.endswith("top_k"))
             or op == "sort"]
    assert top_k
    assert {scopes[n] for n in top_k} == {"pobp.select"}


@pytest.fixture(scope="module")
def topic_sharded_text():
    """The per-shard POBP body with its topics over two shards of a
    named ``model`` axis (vmap), compiled on the CPU."""
    from repro.core.pobp import pobp_shard_body
    from repro.core.sync import LocalReducer, MeshReducer
    cfg = LDAConfig(vocab_size=256, num_topics=16, lambda_w=0.1,
                    lambda_k_abs=4, inner_iters=3, residual_tol=0.0,
                    impl="xla")

    def shard(wid, cnt, phi_l):
        return pobp_shard_body(wid, cnt, phi_l, jax.random.PRNGKey(0),
                               jnp.float32(1.0), cfg, LocalReducer(),
                               MeshReducer("model"))[:3]

    sds = jax.ShapeDtypeStruct
    return jax.jit(jax.vmap(shard, in_axes=(None, None, 0),
                            axis_name="model")).lower(
        sds((8, 16), jnp.int32), sds((8, 16), jnp.float32),
        sds((2, 256, 8), jnp.float32)).compile().as_text()


def test_topic_merge_and_model_norm_own_compiled_instructions(
        topic_sharded_text):
    """Topics over the model axis: the merge of power-topic candidates
    and the selective sweep's renormalization reach the compiled
    instructions' op names, each under its own scope."""
    scopes = obs.op_scopes(topic_sharded_text, STEP_SCOPES
                           + ("pobp.topic_merge", "pobp.model_norm"))
    owned = {s for s in scopes.values() if s is not None}
    assert {"pobp.topic_merge", "pobp.model_norm"} <= owned
    ops = obs.hlo_ops(topic_sharded_text)
    sorts = {scopes[n] for n, (op, name) in ops.items()
             if op == "sort" or (op == "custom-call"
                                 and name.endswith("top_k"))}
    # the shard's own candidates, then the merge of every shard's
    assert {"pobp.select", "pobp.topic_merge"} <= sorts


@pytest.mark.parametrize("line, name, opcode", [
    ('  %fusion.202 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] %p), '
     'kind=kLoop, calls=%fc, metadata={op_name="jit(step)/while/body/'
     'pobp.scatter/add"}', "fusion.202", "fusion"),
    ('  ROOT %tuple.1 = (f32[4,3]{1,0}, (s32[], f32[2]{0})) '
     'tuple(%a, %b)', "tuple.1", "tuple"),
    ('  %while.28 = (s32[], f32[2]{0}) while(%t), condition=%c, body=%b',
     "while.28", "while"),
    ('  %all-reduce.3 = f32[16]{0} all-reduce(f32[16]{0} %x), '
     'replica_groups={}, to_apply=%add', "all-reduce.3", "all-reduce"),
])
def test_hlo_ops_reads_name_and_opcode(line, name, opcode):
    assert obs.hlo_ops(line) == {
        name: (opcode, "jit(step)/while/body/pobp.scatter/add"
               if "op_name" in line else "")}


FUSED = """HloModule jit_step

%fused_computation.3 (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  %gather.1 = f32[8]{0} gather(f32[8]{0} %p.0), metadata={op_name="jit(step)/while/body/pobp.select/gather"}
  %mul.2 = f32[8]{0} multiply(f32[8]{0} %gather.1, f32[8]{0} %p.0), metadata={op_name="jit(step)/while/body/pobp.select/mul"}
  ROOT %copy.4 = f32[8]{0} copy(f32[8]{0} %mul.2)
}

ENTRY %main.5 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %fusion.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation.3
}
"""


def test_a_fusion_without_metadata_takes_its_fused_ops_scope():
    scopes = obs.op_scopes(FUSED, STEP_SCOPES)
    assert scopes["fusion.3"] == "pobp.select"
    assert scopes["copy.4"] is None and scopes["x"] is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/while/body/pobp.select/top_k", "pobp.select"),
    ("jit(step)/vmap(pobp.init)/mul", "pobp.init"),
    ("jit(step)/pobp.scatter/jit(scatter_add_rows)/pobp.select/x",
     "pobp.select"),                               # the innermost
    ("jit(step)/while/cond/lt", None),
    ("", None),
])
def test_innermost_scope(op_name, scope):
    assert obs.innermost_scope(op_name, STEP_SCOPES) == scope


# ------------------------------------------------------ the slab engine

W, K = 200, 16
CFG = LDAConfig(vocab_size=W, num_topics=K, alpha=0.1, beta=0.01)
STATS_KEYS = {
    "served", "steps", "docs_per_s", "latency_p50_s", "latency_p99_s",
    "mean_fold_iters", "cold_fold_iters", "warm_fold_iters", "compiles",
    "slots", "slot_len", "sweeps_per_step", "slot_occupancy", "warmup_s",
    "bytes_by_phase", "per_request_bytes", "live_words", "w_cap",
    "occupancy", "phi_version", "oov_rate", "cache_served", "warm_starts",
    "retrain_batches", "shed", "shed_frac", "quarantined",
    "admission_slo_s", "step_ema_s"}


@pytest.fixture(scope="module")
def phi_and_docs():
    docs, _, phi_true = lda_corpus(0, 48, W, K, doc_len_mean=30)
    return jnp.asarray(phi_true.T) * 200.0, docs


def test_slab_stamps_come_in_request_order(phi_and_docs):
    phi_acc, docs = phi_and_docs
    eng = SlabEngine(phi_acc, CFG, slots=8, slot_len=64, seed=1)
    ids = [eng.submit(d) for d in docs]
    res = eng.drain()
    assert sorted(r.req_id for r in res) == sorted(ids)
    reqs = obs.requests()
    for i in ids:
        r = reqs[i]
        assert (r["submit"] <= r["refill"] <= r["retire_dispatch"]
                <= r["done"]), r
    # refill lanes hold slots // 4 documents a step: later ones queue
    assert max(reqs[i]["refill"] - reqs[i]["submit"] for i in ids) > 0
    assert set(eng.stats()) >= STATS_KEYS


def test_slab_spans_cover_every_step_and_harvest(phi_and_docs):
    phi_acc, docs = phi_and_docs
    eng = SlabEngine(phi_acc, CFG, slots=8, slot_len=64, seed=2)
    for d in docs[:20]:
        eng.submit(d)
    eng.drain()
    names = [n for n, _, _ in obs.records()]
    steps = eng.stats()["steps"]
    assert names.count("slab.submit") == 20
    assert names.count("slab.dispatch") == steps
    assert names.count("slab.refill") == steps
    assert names.count("slab.harvest.block") == steps
    assert 0 < names.count("slab.harvest.fetch") == names.count(
        "slab.harvest.retire")


def test_slab_latency_window_is_bounded(phi_and_docs, monkeypatch):
    monkeypatch.setattr(engine_mod, "LATENCY_WINDOW", 5)
    phi_acc, docs = phi_and_docs
    eng = SlabEngine(phi_acc, CFG, slots=8, slot_len=64, seed=3)
    for d in docs[:12]:
        eng.submit(d)
    res = eng.drain()
    assert len(res) == 12 and len(eng._latencies) == 5
    s = eng.stats()
    assert s["served"] == 12
    assert np.isfinite(s["latency_p50_s"])
    assert s["latency_p99_s"] >= s["latency_p50_s"]
    newest = sorted(r.latency_s for r in res[-5:])
    assert sorted(eng._latencies) == pytest.approx(newest)
