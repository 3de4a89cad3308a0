"""Serving cells: open-loop arrivals of generated documents into the
continuous-batching fold-in engine (``serve/engine.SlabEngine``), then
the comparison of the served theta with the plain fold-in reference.

Arrivals are due at fixed times drawn by the traffic mix's arrival
process (``bench/arrivals/<name>.py``, named by the mix's ``arrivals``
key), so every seed offers the same number of documents over the same
span.  One thread submits every document whose due time has passed, advances
the slab while work is in flight, and sleeps to the next due time when
it is idle.  A document's latency runs from its due time to the moment
its theta is on the host; how late the loop submitted it is recorded
apart.  After the window the loop keeps stepping until every document
due in the window is answered, for at most a minute.
"""

from __future__ import annotations

import collections
import functools
import gc
import math
import sys
import time

import numpy as np

from bench import harness
from bench.generator import Corpus, make_topics, mid_stream_phi, seed_words
from bench.train_cell import lda_config, prior_tokens

WAIT_AFTER_CLOSE_S = 60.0


def truncate(ids, counts, slot_len: int):
    """A document as a slot holds it: its ``slot_len`` highest-count
    words where it has more (the serving contract)."""
    if len(ids) > slot_len:
        keep = np.argsort(-counts)[:slot_len]
        return ids[keep], counts[keep]
    return ids, counts


class _Tracked:
    """Wraps ``SlabEngine.step`` to record, per document, the step and
    refill lane that admitted it (the queue is first in, first out) —
    what the reference needs to start each document from the same
    random field — and times the engine's harvest in a span of its own,
    so that a device gap under it is told apart from refill and
    dispatch."""

    def __init__(self, engine, spans):
        self.engine = engine
        self.refill_lanes = int(engine._refill_cap)
        self.fifo: "collections.deque[int]" = collections.deque()
        self.lane = {}
        self.steps = 0
        self._step = engine.step
        engine.step = self.step
        harvest = engine._harvest

        def timed_harvest(block=False):
            with spans("harvest"):
                return harvest(block)
        engine._harvest = timed_harvest

    def release(self) -> None:
        """Drops the engine, so that its device state can be freed."""
        self.engine = self._step = None

    def queued(self) -> int:
        e = self.engine
        return e.in_flight() - e.live_slots()

    def step(self):
        before = self.queued()
        n = self._step()
        for lane in range(before - self.queued()):
            self.lane[self.fifo.popleft()] = (self.steps, lane)
        self.steps += 1
        return n


def run_serve(run, traced: bool, trace_dir: str, control: bool = False):
    import jax

    from repro.serve.engine import SlabEngine

    config, tr = run.cell.config, run.cell.traffic
    t_setup = time.perf_counter()
    corpus = Corpus(run.seed, config)
    lens = corpus.lengths(int(tr["pool_docs"]), "serve")
    docs = corpus.docs(lens, "serve", block=int(tr["gen_block"]),
                       n_max=int(lens.max()))
    phi_acc = corpus.phi_acc(prior_tokens(config))
    del corpus
    cfg = lda_config(config)
    slot_len = int(tr["slot_len"])
    engine_seed = seed_words(run.seed, "engine")[0]
    engine = SlabEngine(phi_acc, cfg, slots=int(tr["slots"]),
                        slot_len=slot_len,
                        sweeps_per_step=int(tr["sweeps_per_step"]),
                        fold_iters=int(tr["fold_iters"]),
                        residual_tol=float(tr["residual_tol"]),
                        impl=config["impl"], seed=engine_seed)
    del phi_acc
    tracked = _Tracked(engine, run.spans)
    # warm the host path and the step on a burst from the pool
    n_warm = int(tr["warmup_docs"])
    for i in range(n_warm):
        tracked.fifo.append(-1 - i)
        engine.submit(docs[i % len(docs)], req_id=-1 - i)
    engine.drain()
    run.e2e["setup_s"] = time.perf_counter() - t_setup

    rate = float(tr["rate_docs_per_s"])
    n = int(round(rate * run.seconds))
    due_off = harness.load_piece("arrivals", tr["arrivals"]).offsets(
        n, rate, run.seed, tr)
    order = np.random.default_rng(seed_words(run.seed, "docs")).permutation(n)
    doc_of = order % len(docs)
    submit_t = np.full(n, np.nan)
    done_t = np.full(n, np.nan)
    served = {}
    stats0 = engine.stats()
    gc_pauses = harness.GcPauses()

    if traced:
        jax.profiler.start_trace(trace_dir)
    compiles0 = run.compiles.events
    spans = run.spans
    i = 0
    t0_ns = time.perf_counter_ns()
    t0 = t0_ns * 1e-9
    due = t0 + due_off
    close = t0 + run.seconds
    deadline = close + WAIT_AFTER_CLOSE_S
    in_window_done = 0
    stats1 = None
    window = jax.profiler.TraceAnnotation("window")
    window.__enter__()
    while True:
        now = time.perf_counter()
        if i < n and due[i] <= now:
            with spans("submit"):
                while i < n and due[i] <= now:
                    tracked.fifo.append(i)
                    engine.submit(docs[doc_of[i]], req_id=i)
                    submit_t[i] = time.perf_counter()
                    i += 1
        if engine.in_flight():
            with spans("slab_step"):
                engine.step()
        elif i < n:
            with spans("generator_wait"):
                time.sleep(max(0.0, due[i] - time.perf_counter()))
        with spans("poll"):
            got = engine.poll()
            t = time.perf_counter()
            for r in got:
                if r.req_id >= 0:
                    done_t[r.req_id] = t
                    served[r.req_id] = r
                    in_window_done += t <= close
        if stats1 is None and t > close:
            window.__exit__(None, None, None)
            stats1 = engine.stats()
        if (i >= n and not engine.in_flight()) or t > deadline:
            break
    t_end_ns = time.perf_counter_ns()
    t_end = t_end_ns * 1e-9
    if stats1 is None:
        window.__exit__(None, None, None)
        stats1 = engine.stats()
    if traced:
        jax.profiler.stop_trace()
    run.counters.update(gc_pauses.close())
    window_compiles = run.compiles.events - compiles0

    from bench.device import memory_peak_bytes
    run.device.update(memory_peak_bytes(jax.devices()[:1]))
    ok = np.array([k in served and served[k].error is None
                   for k in range(n)])
    lat = np.where(ok, done_t - due, (t_end - due))
    run.attempted = n
    run.failed = int(n - ok.sum())
    run.e2e["serve_latency_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
    run.counters["latency_p95_s"] = float(np.percentile(lat, 95))
    run.e2e["serve_docs_per_s"] = in_window_done / run.seconds
    d_steps = stats1["steps"] - stats0["steps"]
    occ = ((stats1["slot_occupancy"] * stats1["steps"]
            - stats0["slot_occupancy"] * stats0["steps"])
           / max(d_steps, 1))
    window_docs = [k for k in served if done_t[k] <= close]
    run.counters.update(
        late_s=(submit_t - due)[np.isfinite(submit_t)],
        steps=d_steps, occupied_share=occ,
        doc_tokens=np.asarray([min(len(docs[doc_of[k]][0]), slot_len)
                               for k in window_docs], np.float64),
        doc_iters=np.asarray([served[k].iters for k in window_docs],
                             np.float64),
        sweeps_per_step=int(tr["sweeps_per_step"]),
        num_topics=cfg.num_topics,
        span_window=(t0_ns, t0_ns + int(run.seconds * 1e9)),
        mean_fold_iters=float(np.mean([r.iters for r in served.values()]))
        if served else 0.0)
    tracked.release()
    del engine
    gc.collect()

    t_ref = time.perf_counter()
    check_against_reference(run, docs, doc_of, served, tracked, engine_seed,
                            window_compiles, control)
    print(f"[bench] setup {run.e2e['setup_s']:.1f} s, window "
          f"{t_end - t0:.1f} s ({n} offered, {len(served)} answered), "
          f"reference {time.perf_counter() - t_ref:.1f} s; "
          f"{run.counters['gc_collections']} collections in the window, "
          f"longest {run.counters['gc_max_pause_s'] * 1e3:.1f} ms; "
          f"latest submit {np.max(run.counters['late_s']) * 1e3:.1f} ms "
          f"late; longest slab step "
          f"{run.spans.longest_ns('slab_step', t0_ns, t_end_ns) * 1e-6:.1f} "
          f"ms, longest harvest "
          f"{run.spans.longest_ns('harvest', t0_ns, t_end_ns) * 1e-6:.1f} "
          f"ms; p95 {run.counters['latency_p95_s'] * 1e3:.1f} ms",
          file=sys.stderr, flush=True)


def _field_keys(engine_seed: int, n_steps: int):
    """The per-step subkeys of the engine's key chain (split once per
    step from ``PRNGKey(seed)``)."""
    import jax

    def body(key, _):
        key, sub = jax.random.split(key)
        return key, sub

    _, subs = jax.lax.scan(body, jax.random.PRNGKey(engine_seed), None,
                           length=n_steps)
    return subs


def reference_thetas(run, docs, doc_of, served, tracked, engine_seed,
                     sample, **kw):
    """The plain fold-in of each sampled document from the same random
    field: its theta after as many sweeps as it was served, and the
    sweeps that the reference's own stopping rule (the configuration's
    ``residual_tol`` and ``fold_iters``) runs."""
    import jax
    import jax.numpy as jnp

    from bench import reference as ref
    config, tr = run.cell.config, run.cell.traffic
    slot_len, R = int(tr["slot_len"]), tracked.refill_lanes
    K = int(config["num_topics"])
    phi = ref.normalize_phi(
        mid_stream_phi(make_topics(run.seed, config["corpus"], K,
                                   int(config["vocab_size"])),
                       prior_tokens(config)), float(config["beta"]))
    subs = _field_keys(engine_seed, tracked.steps)
    draw = jax.jit(jax.vmap(lambda k: jax.random.uniform(
        k, (R, slot_len, K), minval=0.01, maxval=1.0)))
    fold = jax.jit(functools.partial(
        ref.fold_in, alpha=float(config["alpha"]),
        fold_iters=int(tr["fold_iters"]), tol=float(tr["residual_tol"]),
        **kw))
    thetas, iters = [], []
    block = 8
    for b in range(0, len(sample), block):
        part = sample[b:b + block]
        steps = jnp.asarray([tracked.lane[k][0] for k in part])
        lanes = np.asarray([tracked.lane[k][1] for k in part])
        u = draw(subs[steps])[np.arange(len(part)), lanes]   # [n, L, K]
        ids = np.zeros((len(part), slot_len), np.int32)
        cnt = np.zeros((len(part), slot_len), np.float32)
        for j, k in enumerate(part):
            w, c = truncate(*docs[doc_of[k]], slot_len)
            ids[j, :len(w)], cnt[j, :len(w)] = w, c
        rows, cnt = phi[ids], jnp.asarray(cnt)
        served_iters = jnp.asarray([served[k].iters for k in part],
                                   jnp.int32)
        thetas.append(fold(rows, cnt, u, iters=served_iters)[0])
        iters.append(fold(rows, cnt, u)[1])
    return (np.concatenate([np.asarray(t) for t in thetas]),
            np.concatenate([np.asarray(i) for i in iters]))


def check_against_reference(run, docs, doc_of, served, tracked, engine_seed,
                            window_compiles, control):
    """Over a seeded sample of the answered documents that holds the
    longest: the widest L1 gap between a served theta and the
    reference's after as many sweeps, and the widest gap between the
    sweeps a document was served and those the reference's own stopping
    rule runs."""
    tr = run.cell.traffic
    answered = sorted(k for k, r in served.items() if r.error is None)
    rng = np.random.default_rng(seed_words(run.seed, "check"))
    m = min(int(tr["check_docs"]), len(answered))
    sample = list(rng.choice(answered, m, replace=False)) if m else []
    if answered:
        longest = max(answered, key=lambda k: docs[doc_of[k]][1].sum())
        if longest not in sample:
            sample[-1] = longest
    if sample:
        theta_p = np.stack([np.asarray(served[k].theta) for k in sample])
        iters_p = np.asarray([served[k].iters for k in sample])
        theta_r, iters_r = reference_thetas(run, docs, doc_of, served,
                                            tracked, engine_seed, sample)
        gap = float(np.max(np.sum(np.abs(theta_p - theta_r), axis=1)))
        iters_gap = int(np.max(np.abs(iters_p - iters_r)))
    else:
        gap = iters_gap = math.inf
    run.check("theta_gap", gap, run.cell.limits["theta_gap"])
    run.check("iters_gap", iters_gap, run.cell.limits["iters_gap"])
    run.check("missing_docs", run.failed, 0)
    run.check("window_compiles", window_compiles, 0)
    if control and sample:
        import jax
        import jax.numpy as jnp
        for name, kw in (("bfloat16", {"dtype": jnp.bfloat16}),
                         ("high", {"precision": jax.lax.Precision.HIGH})):
            theta_c, iters_c = reference_thetas(
                run, docs, doc_of, served, tracked, engine_seed, sample,
                **kw)
            run.counters.setdefault("control", {})[name] = {
                "theta_gap": float(np.max(np.sum(np.abs(theta_c - theta_r),
                                                 axis=1))),
                "iters_gap": int(np.max(np.abs(iters_c - iters_r)))}
    run.counters["readings"] = {
        "theta_gap": gap, "iters_gap": iters_gap, "sample": len(sample),
        "served_iters_mean": float(np.mean(iters_p)) if sample else 0.0}
