"""Training cells whose topics are shared out over the chips: the POBP
step on a mesh ``(1, chips)`` of axes ``("data", "model")``, so every
chip holds all of a minibatch's documents and K / chips topic columns of
every [W, K] statistic, and the plain reference with the same columns
(`bench/reference_tp.py`).

The run is `bench/train_cell.run_train`'s: set-up (the pool, the step,
the first three minibatches through the window's call and feed), the
window, then the comparison with the reference over those minibatches,
with the same checks and counters.  What differs is where the data
lives.  No chip holds a whole [K, W] or [W, K] array: the generator's
topics and their CDF are drawn by topic rows on their own chips, the
words of each token are drawn on the chip that holds its topic, and the
mid-stream statistic is laid out by topic columns.  The traffic names
the mesh (``"mesh": [1, chips]``).

`phase_ms` charges the traced step's ops to the POBP phases, the
topic-merge and model-axis renormalization scopes among them, through
this cell's own step compiled again on its mesh.
"""

from __future__ import annotations

import functools
import gc
import sys
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import harness
from bench.generator import (Corpus, _search, jax_key, keys_to_docs,
                             zipf_base)
from bench.train_cell import (CHECK_STEPS, Pool, _norm_diff, gaps,
                              lda_config, prior_tokens, ref_params)

# documents the reference takes at a time (its [D, L, K] temporaries)
REF_DOC_BLOCKS = 4
# the scopes the phase split charges: the step's, and the two that only
# a topic-sharded step has
TP_SCOPES = ("pobp.init", "pobp.dense_sweep", "pobp.dense_sync",
             "pobp.select", "pobp.topic_merge", "pobp.selective_sweep",
             "pobp.model_norm", "pobp.power_sync", "pobp.scatter",
             "pobp.accumulate")


def make_mesh(traffic: dict, devices):
    shape = tuple(int(x) for x in traffic["mesh"])
    return Mesh(np.asarray(devices[:shape[0] * shape[1]]).reshape(shape),
                ("data", "model"))


# ---------------------------------------------------------------------------
# the generator, by topic rows
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("K", "W", "mesh"))
def _topics(key, base_by_rank, *, K: int, W: int, mesh):
    """`bench.generator._topics` with each chip drawing its own topic
    rows: row k is the Dirichlet draw of the k-th split key, the same on
    any layout.  [K, W] over ``model`` by rows."""
    k_perm, k_gam = jax.random.split(key)
    perm = jax.random.permutation(k_perm, W)

    def rows(keys, base, perm):
        g = jax.lax.map(lambda k: jax.random.gamma(k, base), keys,
                        batch_size=16)
        g = g / jnp.sum(g, axis=1, keepdims=True)
        return jnp.zeros((keys.shape[0], W), jnp.float32).at[:, perm].set(g)

    return jax.shard_map(rows, mesh=mesh, in_specs=(P("model"), P(), P()),
                         out_specs=P("model", None))(
        jax.random.split(k_gam, K), base_by_rank, perm)


@functools.partial(jax.jit, static_argnames=("n_max", "mesh"))
def _doc_keys(key, topic_cdf, doc_alpha, lengths, *, n_max: int, mesh):
    """`bench.generator._doc_keys` with the topics' CDF [K, W] by rows
    over ``model``: every chip draws the same topics and uniforms, and
    each token's word is searched on the chip holding its topic."""
    K, W = topic_cdf.shape
    D = lengths.shape[0]
    k_th, k_z, k_w = jax.random.split(key, 3)
    theta = jax.random.dirichlet(k_th, jnp.full((K,), doc_alpha), (D,))
    th_cdf = jnp.cumsum(theta, axis=1)
    doc = jnp.broadcast_to(jnp.arange(D, dtype=jnp.int32)[:, None],
                           (D, n_max))
    u = jax.random.uniform(k_z, (D, n_max)) * th_cdf[:, -1:]
    z = _search(th_cdf.reshape(-1), doc, K, u)
    r = jax.random.uniform(k_w, (D, n_max))

    def words(cdf, z, r):
        kl = cdf.shape[0]
        zl = z - jax.lax.axis_index("model") * kl
        mine = (zl >= 0) & (zl < kl)
        zl = jnp.where(mine, zl, 0)
        w = _search(cdf.reshape(-1), zl, W, r * cdf[zl, W - 1])
        return jax.lax.psum(jnp.where(mine, w, 0), "model")

    w = jax.shard_map(words, mesh=mesh,
                      in_specs=(P("model", None), P(), P()),
                      out_specs=P())(topic_cdf, z, r)
    real = jnp.arange(n_max)[None, :] < lengths[:, None]
    keys = jnp.where(real, doc * W + w, D * W)
    return jnp.sort(keys.reshape(-1))


class TopicShardedCorpus(Corpus):
    """`bench.generator.Corpus` with its topics and their CDF held by
    topic rows over the mesh's ``model`` axis."""

    def __init__(self, seed: int, config: dict, mesh):
        self.seed = int(seed)
        self.config = config
        self.mesh = mesh
        self.K = int(config["num_topics"])
        self.W = int(config["vocab_size"])
        self.corpus = config["corpus"]
        base = zipf_base(self.W, self.corpus["zipf_exponent"]) * self.corpus[
            "topic_concentration"]
        self.topics = _topics(jax_key(seed, "topics"),
                              jnp.asarray(base, jnp.float32), K=self.K,
                              W=self.W, mesh=mesh)
        self._cdf = jax.jit(functools.partial(jnp.cumsum, axis=1))(
            self.topics)
        self._alpha = float(self.corpus["doc_topic_concentration"]) / self.K

    def docs(self, lengths: np.ndarray, stream: str, block: int,
             n_max: int):
        out = []
        for b, i in enumerate(range(0, len(lengths), block)):
            lens = np.zeros(block, np.int32)
            part = lengths[i:i + block]
            lens[:len(part)] = part
            keys = _doc_keys(jax.random.fold_in(jax_key(self.seed, stream),
                                                b),
                             self._cdf, self._alpha, jnp.asarray(lens),
                             n_max=n_max, mesh=self.mesh)
            out.extend(keys_to_docs(np.asarray(keys), block,
                                    self.W)[:len(part)])
        return out

    def phi_acc(self, tokens: float):
        """`bench.generator.mid_stream_phi` laid out by topic columns."""
        scale = float(tokens) / self.K
        return jax.jit(lambda t: t.T * scale, out_shardings=NamedSharding(
            self.mesh, P(None, "model")))(self.topics)


class TopicShardedPool(Pool):
    """`bench.train_cell.Pool` drawn from a `TopicShardedCorpus`; its
    ``phi0`` is the mid-stream statistic by topic columns."""

    def __init__(self, run, n_docs: int, mesh):
        from repro.data.batching import docs_to_padded
        config, traffic = run.cell.config, run.cell.traffic
        L = int(config["len_bucket"])
        n_mb = int(traffic["pool_minibatches"])
        corpus = TopicShardedCorpus(run.seed, config, mesh)
        lens = corpus.lengths(n_mb * n_docs, "train", groups=n_mb)
        docs = corpus.docs(lens, "train", block=n_docs,
                           n_max=int(lens.max()))
        self.phi0 = corpus.phi_acc(prior_tokens(config))
        del corpus
        self.word_ids: List[np.ndarray] = []
        self.counts: List[np.ndarray] = []
        self.tokens: List[float] = []
        self.nnz: List[int] = []
        self.words: List[int] = []
        drawn = 0.0
        for i in range(n_mb):
            part = docs[i * n_docs:(i + 1) * n_docs]
            drawn += sum(float(c.sum()) for _, c in part)
            mb = docs_to_padded(part, max_len=L)
            w, c = np.asarray(mb.word_ids), np.asarray(mb.counts)
            self.word_ids.append(w)
            self.counts.append(c)
            self.tokens.append(float(c.sum()))
            self.nnz.append(int(np.count_nonzero(c)))
            self.words.append(int(np.unique(w[c > 0]).size))
        self.slots = int(self.word_ids[0].size)
        self.distinct_per_doc = float(np.mean([len(d[0]) for d in docs]))
        self.truncated_share = 1.0 - sum(self.tokens) / max(drawn, 1.0)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _step_and_shardings(cfg, mesh):
    from repro.core.types import LDATrainState
    from repro.launch.lda_train import make_shardmap_train_step
    step, _ = make_shardmap_train_step(cfg, mesh)
    rows = NamedSharding(mesh, P("data", None))
    rep = NamedSharding(mesh, P())
    state = LDATrainState(phi_acc=NamedSharding(mesh, P(None, "model")),
                          m=rep, rng=rep)
    return step, rows, state


def run_train_tp(run, traced: bool, trace_dir: str, control: bool = False):
    # the exact merge of power topics over topic shards: a program
    # without it selects per shard and cannot run this cell
    from repro.core.power import select_power_topics_merged  # noqa: F401

    from repro.core.types import LDATrainState

    config, traffic = run.cell.config, run.cell.traffic
    chips = run.cell.chips
    n_docs = int(config["docs_per_batch"])
    t_setup = time.perf_counter()
    devices = jax.devices()[:chips]
    mesh = make_mesh(traffic, devices)
    pool = TopicShardedPool(run, n_docs, mesh)
    cfg = lda_config(config)
    step, rows, state_sharding = _step_and_shardings(cfg, mesh)

    def feed(i):
        j = i % len(pool)
        with run.spans("feed"):
            return (jax.device_put(pool.word_ids[j], rows),
                    jax.device_put(pool.counts[j], rows))

    # the program's first statistic is a copy: phi0 stays for the norms
    # and the reference
    phi0 = pool.phi0
    pool.phi0 = None
    state = jax.device_put(LDATrainState(
        phi_acc=jnp.copy(phi0), m=jnp.int32(config["prior_minibatches"]),
        rng=jax_key(run.seed, "stream")), state_sharding)
    norm = jax.jit(_norm_diff)

    prog_loss, prog_norm = [], {}
    nxt = feed(0)
    for i in range(CHECK_STEPS):
        with run.spans("dispatch"):
            state, diag = step(state, *nxt)
        nxt = feed(i + 1)
        prog_loss.append(float(diag["mean_r"]))
        if i == 0:
            prog_norm["grad"] = float(norm(state.phi_acc, phi0))
    prog_norm["change"] = float(norm(state.phi_acc, phi0))
    jax.block_until_ready(state.phi_acc)
    run.e2e["setup_s"] = time.perf_counter() - t_setup

    gc_pauses = harness.GcPauses()
    if traced:
        jax.profiler.start_trace(trace_dir)
    compiles0 = run.compiles.events
    iters: list = []
    losses: list = []
    tokens = 0.0
    steps = 0
    nnz = 0
    pending = None
    idx = CHECK_STEPS
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            with run.spans("dispatch"):
                state, diag = step(state, *nxt)
            j = idx % len(pool)
            tokens += pool.tokens[j]
            nnz += pool.nnz[j]
            steps += 1
            idx += 1
            nxt = feed(idx)
            iters.append(diag["iters"])
            losses.append(diag["mean_r"])
            if pending is not None:
                with run.spans("wait"):
                    pending.block_until_ready()
            pending = diag["iters"]
            if time.perf_counter() - t0 >= run.seconds:
                break
        with run.spans("wait"):
            jax.block_until_ready(state.phi_acc)
        t1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    run.counters.update(gc_pauses.close())
    window_compiles = run.compiles.events - compiles0

    from bench.device import memory_peak_bytes
    run.device.update(memory_peak_bytes(devices))
    iters = [int(x) for x in jax.device_get(iters)]
    losses = np.asarray(jax.device_get(losses), np.float64)
    run.attempted = steps
    run.failed = int(np.sum(~np.isfinite(losses)))
    run.e2e["train_tokens_per_s_chip"] = tokens / (t1 - t0) / chips
    fed = [(i % len(pool)) for i in range(CHECK_STEPS, idx)]
    hp = dict(ref_params(config))
    run.counters.update(
        steps=steps, window_s=t1 - t0, nnz=nnz,
        slots=pool.slots * steps,
        pad_slots=sum(pool.slots - pool.nnz[j] for j in fed),
        iters=iters,
        words=[pool.words[j] for j in fed],
        nnz_per_step=[pool.nnz[j] for j in fed],
        distinct_per_doc=pool.distinct_per_doc,
        truncated_share=pool.truncated_share,
        power_words=hp["P"], power_topics=hp["Pk"],
        num_topics=cfg.num_topics, chips=chips, docs=n_docs,
        mesh=list(mesh.devices.shape))
    del state, diag, nxt, pending
    gc.collect()

    t_ref = time.perf_counter()
    check_against_reference(run, pool, mesh, phi0, prog_loss, prog_norm,
                            window_compiles, control)
    print(f"[bench] setup {run.e2e['setup_s']:.1f} s, window {t1 - t0:.1f} s "
          f"({steps} steps), reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr, flush=True)


def reference_readings(run, pool, mesh, phi0, *, dtype=None,
                       precision=None):
    """Losses and norms of `bench.reference_tp` over the first
    minibatches, from ``phi0`` (kept; float32 at HIGHEST unless told
    otherwise)."""
    from bench import reference_tp as ref
    kw = {}
    if dtype is not None:
        kw["dtype"] = dtype
    if precision is not None:
        kw["precision"] = precision
    rep = NamedSharding(mesh, P())
    hp = ref_params(run.cell.config)
    docs = len(pool.word_ids[0])
    blocks = REF_DOC_BLOCKS if docs % REF_DOC_BLOCKS == 0 else 1
    norm = jax.jit(_norm_diff)
    phi = jnp.copy(phi0)
    rng = jax.device_put(jax_key(run.seed, "stream"), rep)
    losses, norms = [], {}
    for i in range(CHECK_STEPS):
        phi, rng, loss, _ = ref.pobp_step(
            phi, rng, jax.device_put(pool.word_ids[i], rep),
            jax.device_put(pool.counts[i], rep), hp=hp, mesh=mesh,
            blocks=blocks, **kw)
        losses.append(float(loss))
        if i == 0:
            norms["grad"] = float(norm(phi, phi0))
    norms["change"] = float(norm(phi, phi0))
    return losses, norms


def check_against_reference(run, pool, mesh, phi0, prog_loss, prog_norm,
                            window_compiles, control):
    ref_loss, ref_norms = reference_readings(run, pool, mesh, phi0)
    lim = run.cell.limits
    for name, value in gaps(prog_loss, prog_norm, ref_loss,
                            ref_norms).items():
        run.check(name, value, lim[name])
    run.check("window_compiles", window_compiles, 0)
    run.check("nonfinite_steps", run.failed, 0)
    run.counters["readings"] = {"program": {"loss": prog_loss,
                                            **prog_norm},
                                "reference": {"loss": ref_loss,
                                              **ref_norms}}
    if control:
        for name, kw in (("bfloat16", {"dtype": jnp.bfloat16}),
                         ("high", {"precision": jax.lax.Precision.HIGH})):
            c_loss, c_norms = reference_readings(run, pool, mesh, phi0, **kw)
            run.counters.setdefault("control", {})[name] = gaps(
                c_loss, c_norms, ref_loss, ref_norms)


# ---------------------------------------------------------------------------
# the step's phases, for the metric readers
# ---------------------------------------------------------------------------

def lower_step(run):
    """``lower()`` of the cell's step at the cell's shapes on its mesh,
    with `run_train_tp`'s donation and shardings."""
    from repro.core.types import LDATrainState
    config = run.cell.config
    n_docs = int(config["docs_per_batch"])
    L = int(config["len_bucket"])
    W, K = int(config["vocab_size"]), int(config["num_topics"])
    mesh = make_mesh(run.cell.traffic, jax.devices())
    step, rows, st = _step_and_shardings(lda_config(config), mesh)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    state = LDATrainState(
        phi_acc=sds((W, K), jnp.float32, sharding=st.phi_acc),
        m=sds((), jnp.int32, sharding=st.m),
        rng=sds(key.shape, key.dtype, sharding=st.rng))
    return lambda: step.lower(state, sds((n_docs, L), jnp.int32,
                                         sharding=rows),
                              sds((n_docs, L), jnp.float32, sharding=rows))


def phase_ms(run, scopes) -> Optional[float]:
    """Device ms per step under ``scopes`` (leaf ops only, averaged over
    the chips), from the trace joined to this cell's compiled step; None
    where the join finds nothing."""
    from bench.program_trace import _compiled_text, _obs, split_ops
    c = run.counters
    if "tp_step_split" not in c:
        c["tp_step_split"] = None
        if run.trace is not None and _obs() is not None:
            try:
                text = _compiled_text(lower_step(run), TP_SCOPES)
            except Exception as e:        # a reader never fails the run
                print(f"[bench] the step did not compile again: {e!r}",
                      file=sys.stderr, flush=True)
                return None
            c["tp_step_split"] = split_ops(run.trace, text, TP_SCOPES)
    split = c["tp_step_split"]
    if split is None or not any(s in split for s in scopes):
        return None
    return sum(split.get(s, 0.0) for s in scopes)
