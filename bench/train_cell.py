"""Training cells: back-to-back POBP minibatch steps on a pool of
generated minibatches, then the comparison with the plain reference.

Set-up builds the step and its state (a mid-stream statistic from the
generator), drives the first three minibatches through the same call and
feed the window uses, and records what the comparison needs.  The window
then runs steps until ``--seconds`` have passed, feeding the next
minibatch while the current step runs.  After the window the program's
state is freed and the reference repeats the first three minibatches.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import List

import numpy as np

from bench import harness
from bench.generator import Corpus, jax_key, make_topics, mid_stream_phi

CHECK_STEPS = 3


def lda_config(config: dict):
    from repro.core.types import LDAConfig
    return LDAConfig(vocab_size=int(config["vocab_size"]),
                     num_topics=int(config["num_topics"]),
                     alpha=float(config["alpha"]), beta=float(config["beta"]),
                     lambda_w=float(config["lambda_w"]),
                     lambda_k_abs=int(config["lambda_k_abs"]),
                     inner_iters=int(config["inner_iters"]),
                     residual_tol=float(config["residual_tol"]),
                     impl=config["impl"])


def ref_params(config: dict) -> tuple:
    W, K = int(config["vocab_size"]), int(config["num_topics"])
    return (("W", W), ("K", K), ("alpha", float(config["alpha"])),
            ("beta", float(config["beta"])),
            ("P", max(1, int(round(float(config["lambda_w"]) * W)))),
            ("Pk", max(1, min(int(config["lambda_k_abs"]), K))),
            ("iters", int(config["inner_iters"])),
            ("tol", float(config["residual_tol"])))


def prior_tokens(config: dict) -> float:
    """Tokens of the minibatches the stream has already seen (a fixed
    number: the same for every seed)."""
    return (float(config["prior_minibatches"]) * config["docs_per_batch"]
            * config["corpus"]["tokens_per_doc"])


class Pool:
    """The cell's minibatches on the host, packed by the program's
    batching layer (``data/batching.docs_to_padded``)."""

    def __init__(self, run, n_docs: int):
        from repro.data.batching import docs_to_padded
        config, traffic = run.cell.config, run.cell.traffic
        L = int(config["len_bucket"])
        n_mb = int(traffic["pool_minibatches"])
        corpus = Corpus(run.seed, config)
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

    def __len__(self) -> int:
        return len(self.word_ids)


def _norm_diff(a, b):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


def run_train(run, traced: bool, trace_dir: str, control: bool = False):
    import jax
    import jax.numpy as jnp

    from repro.core.types import LDATrainState

    config, traffic = run.cell.config, run.cell.traffic
    chips = run.cell.chips
    n_docs = int(traffic.get("docs_per_chip") or config["docs_per_batch"]
                 ) * chips
    t_setup = time.perf_counter()
    pool = Pool(run, n_docs)
    cfg = lda_config(config)
    rng0 = jax_key(run.seed, "stream")
    devices = jax.devices()[:chips]
    if chips == 1:
        from repro.core.pobp import make_train_step
        step, _ = make_train_step(cfg)
        place_batch = jax.device_put
        place_state = lambda s: s
    else:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.launch.lda_train import make_shardmap_train_step
        mesh = Mesh(np.asarray(devices).reshape(chips, 1), ("data", "model"))
        step, _ = make_shardmap_train_step(cfg, mesh)
        rows = NamedSharding(mesh, P("data", None))
        rep = NamedSharding(mesh, P())

        def place_batch(x):
            return jax.device_put(x, rows)

        def place_state(s):
            return jax.device_put(s, LDATrainState(
                phi_acc=NamedSharding(mesh, P(None, "model")), m=rep,
                rng=rep))

    def feed(i):
        j = i % len(pool)
        with run.spans("feed"):
            return place_batch(pool.word_ids[j]), place_batch(pool.counts[j])

    state = place_state(LDATrainState(
        phi_acc=pool.phi0, m=jnp.int32(config["prior_minibatches"]),
        rng=rng0))
    pool.phi0 = None
    phi0 = jnp.copy(state.phi_acc)
    norm = jax.jit(_norm_diff)

    # the first minibatches: the same call and feed as the window
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
    del phi0
    jax.block_until_ready(state.phi_acc)
    run.e2e["setup_s"] = time.perf_counter() - t_setup

    # the window
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
    fed = [(idx0 % len(pool)) for idx0 in range(CHECK_STEPS, idx)]
    run.counters.update(
        steps=steps, window_s=t1 - t0, nnz=nnz,
        slots=pool.slots * steps,
        pad_slots=sum(pool.slots - pool.nnz[j] for j in fed),
        iters=iters,
        words=[pool.words[j] for j in fed],
        nnz_per_step=[pool.nnz[j] for j in fed],
        distinct_per_doc=pool.distinct_per_doc,
        truncated_share=pool.truncated_share,
        power_words=dict(ref_params(config))["P"],
        power_topics=dict(ref_params(config))["Pk"],
        num_topics=cfg.num_topics, chips=chips, docs=n_docs)
    del state, diag, nxt, pending
    gc.collect()

    t_ref = time.perf_counter()
    check_against_reference(run, pool, prog_loss, prog_norm,
                            window_compiles, control)
    print(f"[bench] setup {run.e2e['setup_s']:.1f} s, window {t1 - t0:.1f} s "
          f"({steps} steps), reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr, flush=True)


def reference_readings(run, pool, *, dtype=None, precision=None):
    """Losses and norms of the plain reference over the first minibatches
    (float32 at HIGHEST unless told otherwise)."""
    import jax
    import jax.numpy as jnp

    from bench import reference as ref
    config = run.cell.config
    kw = {}
    if dtype is not None:
        kw["dtype"] = dtype
    if precision is not None:
        kw["precision"] = precision
    chips = run.cell.chips
    hp = ref_params(config)
    rng0 = jax_key(run.seed, "stream")
    phi0 = mid_stream_phi(make_topics(run.seed, config["corpus"],
                                      int(config["num_topics"]),
                                      int(config["vocab_size"])),
                          prior_tokens(config))
    place = jnp.asarray
    if chips > 1:
        # documents over the chips, the rest replicated: XLA partitions
        # the plain program; nothing of the program's sharding is used
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:chips]), ("data",))
        rows = NamedSharding(mesh, P("data", None))
        rep = NamedSharding(mesh, P())
        place = lambda x: jax.device_put(x, rows)
        phi0 = jax.device_put(phi0, rep)
        rng0 = jax.device_put(rng0, rep)
    norm = jax.jit(_norm_diff)
    phi, rng = phi0, rng0
    losses, norms = [], {}
    for i in range(CHECK_STEPS):
        phi, rng, loss, _ = ref.pobp_step(
            phi, rng, place(pool.word_ids[i]), place(pool.counts[i]), hp=hp,
            num_shards=chips, **kw)
        losses.append(float(loss))
        if i == 0:
            norms["grad"] = float(norm(phi, phi0))
    norms["change"] = float(norm(phi, phi0))
    return losses, norms


def gaps(loss, norms, ref_loss, ref_norms) -> dict:
    """The compared numbers: each step's loss (the mean residual after
    the minibatch), the first gradient's norm (the minibatch statistic
    Eq. 11 adds to phi_acc), the parameters' change over the three
    minibatches — each as the gap to the reference over the
    reference's value."""
    return {
        "loss_gap": max(abs(a - b) / max(abs(b), 1e-30)
                        for a, b in zip(loss, ref_loss)),
        "grad_gap": abs(norms["grad"] - ref_norms["grad"])
        / max(ref_norms["grad"], 1e-30),
        "change_gap": abs(norms["change"] - ref_norms["change"])
        / max(ref_norms["change"], 1e-30),
    }


def check_against_reference(run, pool, prog_loss, prog_norm,
                            window_compiles, control):
    import jax.numpy as jnp
    ref_loss, ref_norms = reference_readings(run, pool)
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
        import jax
        for name, kw in (("bfloat16", {"dtype": jnp.bfloat16}),
                         ("high", {"precision": jax.lax.Precision.HIGH})):
            c_loss, c_norms = reference_readings(run, pool, **kw)
            run.counters.setdefault("control", {})[name] = gaps(
                c_loss, c_norms, ref_loss, ref_norms)
