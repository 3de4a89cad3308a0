"""Production streaming POBP driver — the paper's Fig. 4 outer loop as a
service-grade artifact (constant memory over an unbounded mini-batch
stream, §3.2 / Table 5).

One jitted, donated-carry step (`repro.core.pobp.make_train_step`)
consumes the stream with:

  - **shape-bucketed batching**: mini-batch L snaps up to a small ladder
    of buckets (`repro.data.batching`), so an arbitrary-length corpus
    compiles the step at most once per bucket instead of once per natural
    shape; D is constant by construction.
  - **asynchronous dispatch**: no ``float()``/``int()`` host sync per
    mini-batch — convergence diagnostics stay on device and are fetched
    every ``--log-every`` batches.
  - **crash-resume**: the full state (phi_acc, m, RNG, stream cursor) is
    checkpointed through `repro.dist.checkpoint`; ``--crash-at N``
    simulates a hard failure on a FRESH run (it does not re-fire on a
    resumed one), so rerunning the same command continues from the
    latest checkpoint with a matching mean_r trajectory.  Resuming
    validates the checkpoint's seed/sync/backend against the flags.
  - **periodic held-out perplexity** every ``--eval-every`` batches,
    through ``perplexity.evaluate`` — i.e. the shared token-major
    fold-in body in `repro.core.infer`, the same program the serving
    engine runs (DESIGN.md §11).
  - execution either as the vmap N-shard simulation (``--backend sim``,
    CPU tests/benchmarks) or under ``shard_map`` on the production mesh
    (``--backend shard_map`` — the dryrun cell's per-shard body, shared
    via `make_mesh_shard_fn`, not forked).
  - **dynamic vocabulary** (``--dynamic-vocab``, DESIGN.md §12): the
    stream's vocabulary drifts; external word keys map to phi rows
    through an append-only ``VocabMap``, phi_acc is allocated on a
    geometric W capacity ladder and grows (``grow_state``) when the live
    vocabulary crosses a rung — compiles stay bounded by
    #rungs x #buckets, growth events are checkpoint-fenced, and
    crash-resume reproduces the grown trajectory exactly.
  - **stream lifecycle** (DESIGN.md §14): ``--decay tau0,kappa`` turns on
    Robbins-Monro forgetting of the phi statistic (kappa=0 bit-exact with
    plain accumulation); ``--compact-every N`` adds checkpoint-fenced
    dead-row compaction (idle + mass-below-prior rows reclaimed, the
    VocabMap remap persisted in the manifest) with optional topic
    recycling (``--recycle-tol``); ``--drift-mode slide`` swaps the
    grow-only stream for the sliding-window news stream whose held-out
    set drifts with it — a month-long stream stays bounded in live rows
    AND keeps fitting the present.

  PYTHONPATH=src python -m repro.launch.lda_train --shards 4 --sync power \
      --minibatches 24 --ckpt-dir /tmp/lda_ck --crash-at 10
  # rerun the same command: resumes from the latest checkpoint

``--backend shard_map`` runs on the devices JAX finds (``--mesh-shape
4,1`` on a four-chip host); a mesh larger than the host is an error.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    # stream
    ap.add_argument("--minibatches", type=int, default=24)
    ap.add_argument("--docs-per-batch", type=int, default=64)
    ap.add_argument("--doc-len-means", default="12,24,40",
                    help="cycled per mini-batch: a variable-length stream")
    ap.add_argument("--len-buckets", default="16,32,48",
                    help="L buckets (multiples of 8); compiles <= #buckets")
    ap.add_argument("--fixed-len", action="store_true",
                    help="pad every batch to the largest bucket "
                         "(single-compile baseline for BENCH_e2e)")
    ap.add_argument("--prefetch", type=int, default=2)
    # model
    ap.add_argument("--vocab", type=int, default=500,
                    help="vocabulary size (dynamic mode: the INITIAL "
                         "external vocabulary of the drifting stream)")
    ap.add_argument("--topics", type=int, default=16)
    # dynamic vocabulary (DESIGN.md §12)
    ap.add_argument("--dynamic-vocab", action="store_true",
                    help="treat W as a managed runtime dimension: the "
                         "stream's vocabulary drifts, rows are assigned "
                         "through a VocabMap, and phi grows along the "
                         "capacity ladder (--backend sim only)")
    ap.add_argument("--vocab-growth-per-batch", type=int, default=24,
                    help="external words entering circulation per "
                         "mini-batch (drifting synthetic stream); in "
                         "--drift-mode slide, the words RETIRED per batch "
                         "as well (the window slides)")
    ap.add_argument("--drift-mode", default="grow",
                    choices=["grow", "slide"],
                    help="'grow': vocabulary only accretes "
                         "(drifting_vocab_docs, DESIGN.md §12); 'slide': "
                         "news-like drift — each batch retires as many "
                         "words as it introduces (drifting_news_stream, "
                         "§14), with --vocab as the window size")
    # stream lifecycle (DESIGN.md §14)
    ap.add_argument("--decay", default="1,0",
                    help="Robbins-Monro forgetting 'tau0,kappa' on the phi "
                         "fold-back: retain (1 - (tau0+m)^-kappa) of the "
                         "accumulated statistic each batch; kappa=0 "
                         "disables (bit-exact with the plain accumulator)")
    ap.add_argument("--compact-every", type=int, default=0,
                    help="checkpoint-fenced dead-row compaction every N "
                         "mini-batches (0 = never): reclaim rows idle "
                         ">= --compact-min-idle batches whose decayed mass "
                         "fell below the prior floor, slide survivors to a "
                         "dense prefix, and reuse the freed rows for new "
                         "admissions (dynamic vocab only)")
    ap.add_argument("--compact-min-idle", type=int, default=5,
                    help="batches a row must be untouched before it is a "
                         "compaction candidate")
    ap.add_argument("--compact-mass-tol", type=float, default=25.0,
                    help="dead-mass floor in units of K*beta: a candidate "
                         "row dies when its statistic <= tol*K*beta")
    ap.add_argument("--recycle-tol", type=float, default=0.0,
                    help="recycle topics whose live mass <= tol x the mean "
                         "topic mass, reseeding from high-residual tokens "
                         "at each compaction fence (0 = never)")
    ap.add_argument("--w-cap-min", type=int, default=64,
                    help="first W capacity rung")
    ap.add_argument("--w-growth", type=float, default=2.0,
                    help="geometric W ladder factor")
    ap.add_argument("--lambda-w", type=float, default=0.1)
    ap.add_argument("--lambda-k", type=int, default=8)
    ap.add_argument("--inner-iters", type=int, default=12)
    ap.add_argument("--tol", type=float, default=0.05)
    ap.add_argument("--sync", default="power", choices=["power", "dense"])
    ap.add_argument("--sync-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--impl", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--sweep-policy", default="auto",
                    choices=["auto", "packed", "dense_layout", "kblocked"],
                    help="selective-sweep formulation: 'auto' picks per "
                         "(T, K, Pk, P) from the measured cost model at "
                         "trace time, falling back to the K-blocked carry "
                         "megakernel when the full-K carry does not fit "
                         "VMEM (DESIGN.md §2/§13); identical math and "
                         "identical Eq. 6 sync bytes either way")
    ap.add_argument("--phi-acc-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="phi_acc storage dtype (DESIGN.md §13): 'bfloat16' "
                         "halves phi HBM + phi-delta sync bytes; the "
                         "accumulate runs in f32 with a stochastic-rounded "
                         "fold-back, so the trajectory tracks f32 within "
                         "rounding noise")
    ap.add_argument("--onehot-crossover", type=int, default=8_000_000,
                    help="T*P above which the packed path's [P, Pk] "
                         "accumulation switches from one-hot contraction "
                         "to row scatter (consumed by the cost model)")
    # execution
    ap.add_argument("--shards", type=int, default=4,
                    help="simulated data shards (--backend sim)")
    ap.add_argument("--backend", default="sim",
                    choices=["sim", "shard_map", "ps"])
    # parameter server (--backend ps, DESIGN.md §15)
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded staleness S for --backend ps: a pull for "
                         "mini-batch m may be served from a server snapshot "
                         "missing at most the last S pushes; S=0 barriers "
                         "every pull behind the previous push (trajectory "
                         "matches the allreduce backend), S>=1 lets the "
                         "prefetched pull fully overlap the sweep")
    ap.add_argument("--ps-servers", type=int, default=4,
                    help="row-sharded server shards, each owning a "
                         "contiguous phi row range (--backend ps)")
    ap.add_argument("--ps-latency", type=float, default=0.0,
                    help="injected per-operation transport latency in "
                         "seconds (SimTransport) — makes prefetch overlap "
                         "measurable on localhost; 0 = in-process speed")
    ap.add_argument("--ps-pull-timeout", type=float, default=60.0,
                    help="server-side pull wait in seconds before a "
                         "TimeoutError names the shard and awaited version "
                         "(--backend ps); the client retry deadline is "
                         "2x this")
    # chaos / fault tolerance (DESIGN.md §17)
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="FaultPlan seed: replaying the same run replays "
                         "the same drop/dup/delay decisions")
    ap.add_argument("--chaos-drop", type=float, default=0.0,
                    help="per-op drop probability for pushes AND pulls "
                         "(< 1; retries draw fresh fates)")
    ap.add_argument("--chaos-dup", type=float, default=0.0,
                    help="per-op duplicate-delivery probability for pushes "
                         "(exercises sequence-number dedup)")
    ap.add_argument("--chaos-delay", type=float, default=0.0,
                    help="injected issue-side delay in seconds when a "
                         "delay fires")
    ap.add_argument("--chaos-delay-prob", type=float, default=0.0,
                    help="per-op probability of the --chaos-delay")
    ap.add_argument("--chaos-crash", default="",
                    help="scheduled server loss as SERVER@PUSHOP (e.g. "
                         "'1@6'): shard SERVER crashes when the push op "
                         "counter reaches PUSHOP, restarts "
                         "--chaos-restart-after ops later, and recovers "
                         "from the last synced snapshot + client replay")
    ap.add_argument("--chaos-restart-after", type=int, default=2,
                    help="push ops between scheduled crash and restart")
    # elastic worker membership (--backend ps, staleness 0)
    ap.add_argument("--elastic-workers", default="w0",
                    help="comma-separated initial logical worker ids; each "
                         "gets its own PSClient (own seq space + retained "
                         "replay log) over the shared transport, and "
                         "mini-batch m goes to active[m %% len(active)]")
    ap.add_argument("--elastic-events", default="",
                    help="comma-separated membership events "
                         "'join:NAME@M', 'leave:NAME@M', 'crash:NAME@M' "
                         "applied at mini-batch index M (0-based): join/"
                         "leave repartition the stream at the batch fence; "
                         "crash kills NAME mid-batch — its un-pushed batch "
                         "is replayed by a surviving worker (trajectory "
                         "parity at S=0)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"],
                    help="production mesh for --backend shard_map")
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh as 'data,model' (smoke tests), "
                         "e.g. --mesh-shape 4,2")
    # driving
    ap.add_argument("--warmup-buckets", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pre-compile every bucket shape before the stream "
                         "starts (predictable latency: no compile hiccups "
                         "mid-stream; timed throughput is steady-state)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--eval-docs", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--crash-at", type=int, default=0,
                    help="simulate a hard failure after minibatch N")
    return ap


def default_args(**overrides) -> argparse.Namespace:
    """Programmatic entry: parser defaults + keyword overrides."""
    args = build_parser().parse_args([])
    for k, v in overrides.items():
        if not hasattr(args, k):
            raise TypeError(f"unknown driver arg: {k}")
        setattr(args, k, v)
    return args


def _csv_ints(s: str):
    return tuple(int(x) for x in str(s).split(",") if str(x).strip())


def _parse_elastic_events(spec: str) -> Dict[int, list]:
    """``"join:w1@4,leave:w0@8,crash:w1@12"`` -> {batch index: [(kind,
    name), ...]}, applied at that 0-based mini-batch (DESIGN.md §17)."""
    events: Dict[int, list] = {}
    for tok in str(spec).split(","):
        tok = tok.strip()
        if not tok:
            continue
        try:
            kind, rest = tok.split(":")
            name, at = rest.split("@")
            at = int(at)
        except ValueError:
            raise ValueError(f"bad --elastic-events entry {tok!r}; expected "
                             f"kind:NAME@M (e.g. 'join:w1@4')") from None
        if kind not in ("join", "leave", "crash"):
            raise ValueError(f"unknown elastic event kind {kind!r} in "
                             f"{tok!r} (join/leave/crash)")
        events.setdefault(at, []).append((kind, name))
    return events


def _parse_decay(s: str):
    parts = [p.strip() for p in str(s).split(",")]
    if len(parts) != 2:
        raise ValueError(f"--decay expects 'tau0,kappa', got {s!r}")
    return float(parts[0]), float(parts[1])


def _build_cfg(args, vocab_size=None):
    from repro.core.types import LDAConfig
    buckets = tuple(sorted(_csv_ints(args.len_buckets)))
    if any(b % 8 for b in buckets):
        # docs_to_padded rounds L up to a multiple of 8: an unaligned bucket
        # would warm up a shape the stream never produces and break the
        # compiles <= #buckets contract
        raise ValueError(f"--len-buckets must be multiples of 8: {buckets}")
    decay_tau0, decay_kappa = _parse_decay(getattr(args, "decay", "1,0"))
    return LDAConfig(vocab_size=vocab_size or args.vocab,
                     num_topics=args.topics,
                     lambda_w=args.lambda_w, lambda_k_abs=args.lambda_k,
                     inner_iters=args.inner_iters, residual_tol=args.tol,
                     decay_tau0=decay_tau0, decay_kappa=decay_kappa,
                     sync_dtype=args.sync_dtype, impl=args.impl,
                     sweep_policy=args.sweep_policy,
                     onehot_crossover=args.onehot_crossover,
                     phi_acc_dtype=args.phi_acc_dtype,
                     init_pad_len=buckets[-1]), buckets


def _true_phi(args):
    """One fixed ground-truth topic set shared by the whole stream
    (life-long regime: every mini-batch is drawn from the same model)."""
    return np.random.default_rng(args.seed).dirichlet(
        np.full(args.vocab, 0.06), size=args.topics).astype(np.float32)


def synthetic_stream(args, buckets, start_m: int, stacked: bool):
    """Deterministic, resumable variable-length stream factory.

    Batch m is generated purely from (seed, m), so resuming from a
    checkpoint cursor only needs `start_m` — no stream state to persist.
    Yields (MiniBatch, host_token_count); batches are [N, Dl, L] stacked
    when `stacked`, global [D, L] otherwise (shard_map shards on device).
    """
    from repro.data.batching import bucket_len, docs_to_padded, stack_shards
    from repro.data.synthetic import lda_corpus_from_phi

    phi = _true_phi(args)
    means = _csv_ints(args.doc_len_means)

    def gen():
        for m in range(start_m, args.minibatches):
            docs, stats = lda_corpus_from_phi(
                args.seed * 1_000_003 + m, args.docs_per_batch, phi,
                doc_len_mean=means[m % len(means)])
            nat = max(len(ids) for ids, _ in docs)
            L = buckets[-1] if args.fixed_len else bucket_len(nat, buckets)
            mb = docs_to_padded(docs, max_len=L)
            if stacked:
                mb = stack_shards(mb, args.shards)
            # tokens actually processed (docs_to_padded truncates docs
            # beyond the bucket); the sync runs on the prefetch thread,
            # never on the dispatch loop
            yield mb, float(mb.counts.sum())

    return gen


def drifting_stream(args, buckets, start_m: int, stacked: bool, vocab,
                    end_m: Optional[int] = None):
    """Deterministic drifting-vocabulary stream (DESIGN.md §12/§14).

    ``--drift-mode grow``: batch m draws from the first
    ``vocab + growth*m`` EXTERNAL word ids; ``--drift-mode slide``: from
    the sliding window ``[growth*m, growth*m + vocab)`` — words retire as
    fast as they arrive (``drifting_news_stream``).  Either way word
    topic scores are counter-based (a pure function of (seed, m)) and
    admission happens through `vocab` in generation order, stamping each
    translated row as touched at batch m; the per-batch live_w snapshot
    is taken right after admission, so it is deterministic however far
    the prefetch thread runs ahead.  Resume replays: a vocab restored
    from the checkpoint prefix re-admits known words as no-ops (touch
    stamps max-merge), and new admissions continue at the same rows.

    ``end_m`` fences the stream: the generator STOPS before batch
    ``end_m``, so the prefetch thread can never admit or touch past a
    compaction fence — the fence's dead-row decisions are a pure
    function of the consumed prefix (DESIGN.md §14).
    Yields (MiniBatch, host_token_count, live_w).
    """
    from repro.data.batching import bucket_len, docs_to_padded, stack_shards
    from repro.data.synthetic import drifting_news_stream, drifting_vocab_docs

    means = _csv_ints(args.doc_len_means)
    cache: Dict[str, Any] = {}
    stop = args.minibatches if end_m is None else end_m
    slide = getattr(args, "drift_mode", "grow") == "slide"

    def gen():
        for m in range(start_m, stop):
            if slide:
                docs, _ = drifting_news_stream(
                    args.seed, m, args.docs_per_batch, args.vocab,
                    args.vocab_growth_per_batch, args.topics,
                    doc_len_mean=means[m % len(means)], score_cache=cache)
            else:
                active = args.vocab + args.vocab_growth_per_batch * m
                docs, _ = drifting_vocab_docs(
                    args.seed, m, args.docs_per_batch, active, args.topics,
                    doc_len_mean=means[m % len(means)], score_cache=cache)
            docs = vocab.map_docs(docs, admit=True, step=m)
            live = vocab.live
            nat = max(len(ids) for ids, _ in docs)
            L = buckets[-1] if args.fixed_len else bucket_len(nat, buckets)
            mb = docs_to_padded(docs, max_len=L)
            if stacked:
                mb = stack_shards(mb, args.shards)
            yield mb, float(mb.counts.sum()), live

    return gen


def _eval_split(args):
    from repro.data.batching import docs_to_padded, train_test_split_counts
    from repro.data.synthetic import lda_corpus_from_phi

    # disjoint from every stream batch seed (those stay < ~minibatches)
    docs, _ = lda_corpus_from_phi(args.seed * 1_000_003 + 987_654_321,
                                  args.eval_docs, _true_phi(args),
                                  doc_len_mean=40)
    train, test = train_test_split_counts(docs, args.seed)
    return docs_to_padded(train), docs_to_padded(test)


def _eval_split_dynamic(args):
    """Held-out docs for the drifting stream, in EXTERNAL id space.

    Drawn from the batch-0 active prefix with a disjoint batch counter, so
    the split never mutates the training vocabulary; each eval call remaps
    through the vocab with OOV words routed to the first guard row, where
    the live-masked phi normalization gives them the beta-prior mass.
    """
    from repro.data.batching import train_test_split_counts
    from repro.data.synthetic import drifting_vocab_docs

    docs, _ = drifting_vocab_docs(args.seed, 987_654_321, args.eval_docs,
                                  args.vocab, args.topics, doc_len_mean=40)
    return train_test_split_counts(docs, args.seed)


def _eval_split_slide(args, m: int):
    """SLIDING held-out docs for --drift-mode slide: an independent
    document set (disjoint rng stream, ``heldout=True``) from the SAME
    window distribution batch ``m`` trains on — the held-out set drifts
    with the stream, so end-of-stream perplexity measures fit to what the
    stream looks like NOW, which is exactly where a decay-less model pays
    for its stale mass (DESIGN.md §14)."""
    from repro.data.batching import train_test_split_counts
    from repro.data.synthetic import drifting_news_stream

    docs, _ = drifting_news_stream(args.seed, m, args.eval_docs, args.vocab,
                                   args.vocab_growth_per_batch, args.topics,
                                   doc_len_mean=40, heldout=True)
    return train_test_split_counts(docs, args.seed)


def _make_mesh(args):
    import jax
    if args.mesh_shape:
        dims = _csv_ints(args.mesh_shape)
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        devices = jax.devices()
        need = int(np.prod(dims))
        if len(devices) < need:
            raise RuntimeError(f"mesh {dims} needs {need} devices, found "
                               f"{len(devices)}")
        return jax.sharding.Mesh(np.asarray(devices[:need]).reshape(dims), axes)
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=(args.mesh == "multi"))


def make_shardmap_train_step(cfg, mesh, sync_mode="power",
                             sync_dtype=None, donate: bool = True):
    """The driver step under shard_map on a real mesh: documents over the
    data (and pod) axes, topics over 'model'.  Same carry/diag contract as
    `core.pobp.make_train_step`; the per-shard body is the exact function
    `launch.dryrun.run_lda_cell` compiles (`make_mesh_shard_fn`)."""
    import jax
    import jax.numpy as jnp
    from repro.core import quantize
    from repro.core.pobp import (_SR_FOLD, _decay_factor, _delta_weight,
                                 shard_map_minibatch_fn)
    from repro.core.types import LDATrainState

    sync_dtype = jnp.float32 if sync_dtype is None else sync_dtype
    with_decay = bool(cfg.decay_kappa)
    sm, meter = shard_map_minibatch_fn(cfg, mesh, sync_mode, sync_dtype,
                                       with_decay=with_decay)
    storage = quantize.phi_acc_dtype(cfg)

    def step(state, word_ids, counts):
        rng, sub = jax.random.split(state.rng)
        weight = _delta_weight(cfg, state.m + 1)
        extra = ((_decay_factor(cfg, state.m + 1),) if with_decay else ())
        phi, iters, mean_r = sm(word_ids, counts, state.phi_acc, sub, weight,
                                *extra)
        if storage != jnp.float32:
            # compressed accumulators (§13): stochastic-rounded fold-back to
            # the storage dtype; the fold_in keeps the split stream (and so
            # every f32 trajectory) untouched
            phi = quantize.stochastic_round(
                phi, storage, jax.random.fold_in(sub, _SR_FOLD))
        new_state = LDATrainState(phi_acc=phi, m=state.m + 1, rng=rng)
        return new_state, dict(iters=iters, mean_r=mean_r, theta=None)

    return jax.jit(step, donate_argnums=(0,) if donate else ()), meter


def _with_lookahead(it):
    """Pair each stream item with its successor (None at the end) so the
    PS client can prefetch the NEXT mini-batch's touched rows while the
    current sweep runs (DESIGN.md §15).  Rides on top of the prefetched
    stream, so generation itself still overlaps too."""
    prev = None
    for item in it:
        if prev is not None:
            yield prev, item
        prev = item
    if prev is not None:
        yield prev, None


def _state_tree(state) -> Dict[str, Any]:
    """The checkpoint payload: exactly the driver carry, with stable keys."""
    return {"state": {"phi_acc": state.phi_acc, "m": state.m,
                      "rng": state.rng}}


# every flag that shapes the per-batch trajectory: resuming under ANY other
# value silently breaks the matching-mean_r guarantee, so all are saved in
# the checkpoint and validated on restore.  (minibatches / logging /
# checkpoint cadence / warmup / crash-at only affect when the run stops.)
_RESUME_KEYS = ("seed", "sync", "backend", "shards", "vocab", "topics",
                "lambda_w", "lambda_k", "inner_iters", "tol", "sync_dtype",
                "impl", "docs_per_batch", "doc_len_means", "len_buckets",
                "fixed_len", "dynamic_vocab", "vocab_growth_per_batch",
                "w_cap_min", "w_growth", "drift_mode", "decay",
                "compact_every", "compact_min_idle", "compact_mass_tol",
                "recycle_tol", "staleness", "ps_servers")
# ps_latency is NOT a resume key: injected transport latency changes wall
# clock, never the trajectory (pushes are applied in batch order either way).
# The chaos_* / ps_pull_timeout / elastic_* flags are likewise not resume
# keys: chaos faults are retried/replayed to the SAME committed state (the
# §17 bit-exactness pin), and elastic membership at S=0 only re-labels which
# client pushes a batch — the trajectory is identical (elastic requires
# staleness 0 for exactly this reason).
# NB: sweep_policy / onehot_crossover are deliberately NOT resume keys:
# both formulations compute the same trajectory (within float
# associativity) and the same sync bytes, so a resumed run may re-resolve
# the formulation for its own hardware.  phi_acc_dtype is likewise not a
# resume key: the restore casts the saved phi_acc to the requested storage
# (``cast_dtypes``), so a run may switch between float32 and bfloat16 at a
# checkpoint fence (DESIGN.md §13 — the trajectory then tracks within
# stochastic-rounding noise, not bit-exactly).


def _run_signature(args) -> Dict[str, Any]:
    return {k: getattr(args, k) for k in _RESUME_KEYS}


def _compiles(step_fn) -> int:
    """Compile count via the jitted function's cache (private jax API)."""
    return int(step_fn._cache_size())


class _CompileClock:
    """Total jax compile seconds, via a process-wide jax.monitoring listener
    (registered once; train_loop reads before/after snapshots)."""

    def __init__(self):
        self.total = 0.0
        self._registered = False

    def ensure_registered(self):
        if self._registered:
            return
        import jax

        def _on_duration(name, dur, **kw):
            if name.startswith("/jax/core/compile/"):
                self.total += dur

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        self._registered = True


_COMPILE_CLOCK = _CompileClock()


def train_loop(args, on_batch=None) -> Dict[str, Any]:
    """Run the streaming driver; returns a result dict (see bottom).

    `on_batch(step_no, state, diag)` is an optional per-batch hook (the
    example uses it for RSS tracking); `diag` values are device scalars —
    converting them forces a sync, so hooks should do that sparingly.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.core import lifecycle, perplexity
    from repro.core.pobp import DiagBuffer, init_train_state, make_train_step
    from repro.core.types import LDATrainState
    from repro.data.batching import prefetched
    from repro.data.vocab import VocabMap, next_capacity
    from repro.dist import checkpoint as ckpt

    dynamic = bool(getattr(args, "dynamic_vocab", False))
    if dynamic and args.backend != "sim":
        raise ValueError("--dynamic-vocab currently requires --backend sim "
                         "(shard_map growth is on the ROADMAP backlog)")
    ps = args.backend == "ps"
    if ps and _parse_decay(getattr(args, "decay", "1,0"))[1]:
        raise ValueError("--backend ps with --decay kappa>0 is not supported "
                         "yet: RM forgetting rescales EVERY phi row each "
                         "batch, so a touched-row delta push would silently "
                         "drop the decay on untouched server rows "
                         "(per-segment decay billing rides the multi-host "
                         "backlog item, ROADMAP)")
    chaos_on = bool(getattr(args, "chaos_drop", 0.0)
                    or getattr(args, "chaos_dup", 0.0)
                    or getattr(args, "chaos_delay_prob", 0.0)
                    or getattr(args, "chaos_crash", ""))
    elastic_events = _parse_elastic_events(
        getattr(args, "elastic_events", ""))
    worker_names = [w.strip()
                    for w in getattr(args, "elastic_workers", "w0").split(",")
                    if w.strip()] or ["w0"]
    if len(set(worker_names)) != len(worker_names):
        raise ValueError(f"duplicate --elastic-workers ids: {worker_names}")
    if not ps and (chaos_on or elastic_events or worker_names != ["w0"]):
        raise ValueError("--chaos-* and --elastic-* flags require "
                         "--backend ps (DESIGN.md §17)")
    if elastic_events and args.staleness != 0:
        raise ValueError("--elastic-events requires --staleness 0: crash "
                         "replay parity holds only when every pull reflects "
                         "every prior push (DESIGN.md §17)")
    if (getattr(args, "chaos_crash", "")
            and (len(worker_names) > 1 or elastic_events)):
        raise ValueError(
            "--chaos-crash with multiple/elastic workers is unsupported: "
            "shard recovery replays the RETAINED LOG OF ONE CLIENT, so a "
            "multi-writer shard would come back missing the other "
            "clients' post-fence deltas (DESIGN.md §17 records this "
            "limitation; use a single worker for server-crash chaos)")
    compact_every = int(getattr(args, "compact_every", 0) or 0)
    if compact_every and not dynamic:
        raise ValueError("--compact-every needs --dynamic-vocab: a fixed-W "
                         "run has no VocabMap to compact (DESIGN.md §14)")
    sync_dtype = jnp.bfloat16 if args.sync_dtype == "bfloat16" else jnp.float32

    if args.crash_at and not args.ckpt_dir:
        raise ValueError("--crash-at needs --ckpt-dir: without a checkpoint "
                         "the rerun restarts from scratch and hits the same "
                         "simulated failure forever")
    if args.crash_at and args.ckpt_dir and args.crash_at <= args.ckpt_every:
        print(f"[warn] --crash-at {args.crash_at} fires before the first "
              f"checkpoint (--ckpt-every {args.ckpt_every}); the rerun will "
              f"restart from scratch and crash again", flush=True)

    # dynamic mode: the capacity rung must be known BEFORE the restore
    # template can be built, so peek at the manifest extra first (§12).
    vocab = VocabMap()
    live_done = 0            # live vocab as of the last CONSUMED batch
    vocab_version = 0        # bumped at every compaction fence (§14)
    last_remap = None        # the latest fence's row remap (manifest payload)
    w_cap = next_capacity(0, 0, args.w_cap_min, args.w_growth)
    if dynamic and args.ckpt_dir:
        peeked = ckpt.peek_extra(args.ckpt_dir)
        if peeked is not None and "dyn" in peeked[0]:
            dyn = peeked[0]["dyn"]
            w_cap = int(dyn["w_cap"])
            live_done = int(dyn["live_w"])
            vocab = VocabMap(dyn["vocab_keys"],
                             touched=dyn.get("touched", ()))
            vocab_version = int(dyn.get("vocab_version", 0))
            last_remap = dyn.get("row_remap")

    cfg, buckets = _build_cfg(args, vocab_size=w_cap if dynamic else None)
    state = init_train_state(cfg, args.seed)
    start_m = 0
    if args.ckpt_dir:
        try:
            got = ckpt.restore_latest(args.ckpt_dir, _state_tree(state),
                                      grow_rows=("phi_acc",),
                                      cast_dtypes=("phi_acc",))
        except ValueError as e:
            raise ValueError(
                f"cannot restore checkpoint from {args.ckpt_dir} ({e}); it "
                f"was probably written by an older/other tool — use a fresh "
                f"--ckpt-dir") from e
        if got is not None:
            trees, extra, ck_step = got
            want = _run_signature(args)
            for key, saved in extra.get("run", {}).items():
                if key in want and saved != want[key]:
                    raise ValueError(
                        f"checkpoint in {args.ckpt_dir} was written with "
                        f"{key}={saved!r} but this run has "
                        f"{key}={want[key]!r}; rerun with matching flags "
                        f"or a fresh --ckpt-dir")
            state = LDATrainState(**trees["state"])
            start_m = int(extra["next_m"])
            print(f"[restore] resumed from checkpoint step {ck_step} -> "
                  f"next minibatch {start_m + 1}", flush=True)
            if start_m >= args.minibatches:
                print(f"[restore] checkpoint already covers all "
                      f"{args.minibatches} minibatches — nothing to train "
                      f"(raise --minibatches or use a fresh --ckpt-dir)",
                      flush=True)

    mesh = _make_mesh(args) if args.backend == "shard_map" else None

    def place(state):
        """The carry laid out as the shard_map step returns it (phi over
        'model', the rest replicated), so the program the first call
        compiles is the one every later call runs."""
        if mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(mesh, P())
        return jax.device_put(state, LDATrainState(
            phi_acc=NamedSharding(mesh, P(None, "model")), m=rep, rng=rep))

    def build_step(cfg):
        if args.backend == "sim":
            return make_train_step(cfg, args.shards, args.sync, sync_dtype)
        if ps:
            # the SAME shard body under the PS wire model (DESIGN.md §15):
            # in-step math is the sim backend's (N simulated shards reduced
            # over the vmap axis — the whole step is ONE PS worker), but the
            # meter bills every vocabulary-row payload as touched-granular
            # push + pull legs.  The host-side exchange is PSClient below.
            from repro.core.sync import (CommMeter, LocalReducer, MeshReducer,
                                         PSReducer)
            meter = CommMeter()
            inner = (LocalReducer(meter=meter, sync_dtype=sync_dtype)
                     if args.shards == 1 else
                     MeshReducer("shards", meter=meter,
                                 sync_dtype=sync_dtype))
            return make_train_step(cfg, args.shards, args.sync, sync_dtype,
                                   reducer=PSReducer(inner))
        return make_shardmap_train_step(cfg, mesh, args.sync, sync_dtype)

    def warm_buckets(step_fn, cfg):
        # AOT warmup: push an all-padding batch of every bucket shape
        # through the step on a throwaway state, so the stream never stalls
        # on a mid-run compile (startup cost, not steady-state cost).  The
        # dynamic variant warms with a live_w argument so the compiled
        # program is the one the stream will actually run.
        scratch = place(init_train_state(cfg, args.seed))
        for L in (buckets[-1:] if args.fixed_len else buckets):
            if args.backend in ("sim", "ps") and args.shards > 1:
                shape = (args.shards, args.docs_per_batch // args.shards, L)
            else:
                shape = (args.docs_per_batch, L)
            zargs = (jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.float32))
            if dynamic:
                scratch, _ = step_fn(scratch, *zargs,
                                     jnp.asarray(1, jnp.int32))
            else:
                scratch, _ = step_fn(scratch, *zargs)
        jax.block_until_ready(scratch.phi_acc)

    step_fn, meter = build_step(cfg)
    state = place(state)

    ps_server = ps_transport = touched_rows_of = None
    ps_workers: Dict[str, Any] = {}
    ps_active: list = []
    ps_retired: list = []       # left/crashed workers, kept for stats
    elastic_log: list = []
    if ps:
        from repro.dist.faults import ChaosTransport, FaultPlan
        from repro.dist.paramserver import (ParamServer, PSClient,
                                            SimTransport, touched_rows_of)
        # the server group owns the authoritative statistic; a resumed run
        # rehydrates it from the restored carry at version start_m (the
        # checkpoint was written server-synced, see ps_sync_state)
        ps_server = ParamServer(np.asarray(state.phi_acc, np.float32),
                                num_servers=args.ps_servers,
                                version=start_m,
                                pull_timeout=args.ps_pull_timeout)
        wire_np = (np.float32 if args.sync_dtype == "float32"
                   else jnp.bfloat16)
        ps_transport = SimTransport(ps_server, latency_s=args.ps_latency,
                                    wire_dtype=wire_np)
        if chaos_on:
            crash_server, crash_at = FaultPlan.parse_crash(args.chaos_crash)
            plan = FaultPlan(
                seed=args.chaos_seed, drop_push=args.chaos_drop,
                drop_pull=args.chaos_drop, dup_push=args.chaos_dup,
                delay_s=args.chaos_delay,
                delay_prob=args.chaos_delay_prob,
                crash_server=crash_server, crash_at_push=crash_at,
                restart_after_pushes=args.chaos_restart_after)
            ps_transport = ChaosTransport(ps_transport, plan)

        def make_worker(name: str) -> "PSClient":
            return PSClient(ps_transport, staleness=args.staleness,
                            client_id=name,
                            retry_deadline_s=2.0 * args.ps_pull_timeout,
                            meter=meter)

        ps_workers = {name: make_worker(name) for name in worker_names}
        ps_active = list(worker_names)

    def ps_sync_state():
        """Drain the PS pipeline and adopt the server-authoritative phi as
        the carry (checkpoint fences / end of stream).  At S=0 this is a
        numerical no-op (replica rows equal the server up to the delta-add
        ulp); at S>0 it also heals any bounded staleness in the replica.
        The fence is also the durability handshake (DESIGN.md §17): the
        snapshot becomes the crash-recovery base, so every worker may trim
        its retained replay log."""
        nonlocal state
        for w in ps_workers.values():
            w.flush()
        phi_srv, _ = ps_server.snapshot()
        ps_server.mark_synced()
        for w in ps_workers.values():
            w.mark_durable()
        state = LDATrainState(
            phi_acc=jnp.asarray(phi_srv, state.phi_acc.dtype),
            m=state.m, rng=state.rng)

    def make_stream(seg_start: int, seg_end: int):
        # one prefetched generator per fence segment: the generator stops
        # BEFORE seg_end, so prefetch admissions/touches can never cross a
        # compaction fence (§14 determinism)
        if dynamic:
            return prefetched(
                drifting_stream(args, buckets, seg_start,
                                stacked=(args.backend == "sim"), vocab=vocab,
                                end_m=seg_end),
                args.prefetch)
        return prefetched(
            synthetic_stream(args, buckets, seg_start,
                             stacked=(args.backend in ("sim", "ps"))),
            args.prefetch)

    _COMPILE_CLOCK.ensure_registered()
    warmup_s = 0.0
    if args.warmup_buckets:
        t0 = time.time()
        warm_buckets(step_fn, cfg)
        warmup_s = time.time() - t0

    # per-batch diagnostics: device scalars buffered and flushed to host
    # values in blocks (DiagBuffer), so the stream stays async while live
    # device buffers stay bounded on an unbounded stream (§3.2).
    buf = DiagBuffer(block=max(args.log_every, 64))
    ppl_trace = []
    eval_split = None
    consumed_m = start_m - 1     # last consumed batch index (slide eval)
    slide = dynamic and getattr(args, "drift_mode", "grow") == "slide"

    def heldout():
        nonlocal eval_split
        if slide:
            # sliding held-out set: re-drawn from the CURRENT window each
            # eval, so end-of-stream ppl measures fit to the stream NOW
            return _eval_split_slide(args, max(consumed_m, 0))
        if eval_split is None:  # built once, reused by every eval
            eval_split = (_eval_split_dynamic(args) if dynamic
                          else _eval_split(args))
        return eval_split

    def eval_ppl():
        from repro.data.batching import docs_to_padded
        tr, te = heldout()
        phi_acc = state.phi_acc
        if args.backend == "shard_map":
            # the held-out fold-in is a one-device program: its Pallas
            # kernel cannot be partitioned over the mesh's shards of phi
            phi_acc = jax.device_put(phi_acc, jax.devices()[0])
        if not dynamic:
            return perplexity.evaluate(jax.random.PRNGKey(args.seed + 1),
                                       phi_acc, tr, te, cfg)
        # dynamic: the raw split lives in external-id space — remap it at
        # the CURRENT vocabulary (lookup only, OOV -> first guard row,
        # where the live-masked phi gives the beta-prior mass)
        tr_b = docs_to_padded(vocab.map_docs(tr, admit=False,
                                             oov_row=live_done))
        te_b = docs_to_padded(vocab.map_docs(te, admit=False,
                                             oov_row=live_done))
        return perplexity.evaluate(jax.random.PRNGKey(args.seed + 1),
                                   phi_acc, tr_b, te_b, cfg,
                                   live_w=live_done)

    def dyn_extra(next_m: int, live: int) -> Dict[str, Any]:
        extra = {"next_m": next_m, "run": _run_signature(args)}
        if dynamic:
            # touched stamps saved mid-segment may include prefetch-ahead
            # touches of existing rows — harmless: resume replays those
            # batches and max-merge regenerates a superset-consistent
            # vector by the next fence (§14 determinism note).
            # row_remap is the LATEST fence's remap, the manifest payload
            # that lets an older (pre-compaction) phi restore into this
            # row space (dist.checkpoint row_remaps / restore_phi).
            extra["dyn"] = {"w_cap": cfg.vocab_size, "live_w": live,
                            "vocab_keys": vocab.keys_upto(live),
                            "touched": vocab.touched_upto(live),
                            "vocab_version": vocab_version,
                            "row_remap": last_remap}
        if ps:
            # server-side state in the manifest: saves are written with the
            # pipeline drained and the carry server-synced (ps_sync_state),
            # so the phi payload IS the server statistic at this version
            extra["ps"] = {**ps_server.manifest(),
                           "staleness": args.staleness}
        return extra

    tokens = 0.0
    eval_compile_s = 0.0
    growth_s = 0.0
    compact_s = 0.0
    growth_events = []
    compaction_events = []
    occupancy_trace = []
    compiles_prev = 0
    compile_s0 = _COMPILE_CLOCK.total
    t0 = time.time()

    def compaction_fence(fence_m: int):
        """Checkpoint-fenced dead-row compaction + topic recycling (§14).

        Runs with the pipeline drained: the segment generator stopped
        BEFORE `fence_m`, every yielded batch has been consumed, so
        ``vocab.live == live_done`` and the touched vector covers exactly
        the consumed prefix — the dead decision (and hence the remap) is
        a pure function of (stream, fence step).  The fence persists the
        post-compaction state + vocab + remap immediately: a crash on
        either side resumes onto a consistent (phi, vocab) pair.
        """
        nonlocal state, cfg, step_fn, meter, compiles_prev, live_done, \
            vocab_version, last_remap, compact_s
        jax.block_until_ready(state.phi_acc)
        t_c = time.time()
        live = vocab.live
        phi_host = np.asarray(state.phi_acc[:live]).astype(np.float32)
        floor = float(args.compact_mass_tol) * cfg.num_topics * cfg.beta
        dead = lifecycle.dead_rows(
            phi_host.sum(axis=1), vocab.touched_upto(live), fence_m - 1,
            args.compact_min_idle, floor)
        n_dead = int(dead.sum())
        live_new = live
        if n_dead:
            remap = vocab.compact(~dead)
            state = lifecycle.apply_row_remap(state, remap)
            live_new = vocab.live
            last_remap = [int(r) for r in remap]
            vocab_version += 1
        recycled = []
        if args.recycle_tol:
            phi2, recycled = lifecycle.recycle_topics(
                np.asarray(state.phi_acc).astype(np.float32), live_new,
                args.recycle_tol)
            if recycled:
                state = LDATrainState(
                    phi_acc=jnp.asarray(phi2, state.phi_acc.dtype),
                    m=state.m, rng=state.rng)
        # drop capacity rungs the compacted vocabulary no longer needs
        new_cap = next_capacity(live_new, 0, args.w_cap_min, args.w_growth)
        if new_cap < cfg.vocab_size:
            state = lifecycle.resize_state(state, new_cap, live_w=live_new)
            compiles_prev += max(_compiles(step_fn), 0)
            cfg = dataclasses.replace(cfg, vocab_size=new_cap)
            step_fn, meter = build_step(cfg)
            if args.warmup_buckets:
                warm_buckets(step_fn, cfg)
        live_done = live_new
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, fence_m, _state_tree(state),
                      extra=dyn_extra(fence_m, live_done))
        compact_s += time.time() - t_c
        if n_dead or recycled:
            compaction_events.append(
                {"m": fence_m, "dead": n_dead, "live_before": live,
                 "live_after": live_new, "w_cap": cfg.vocab_size,
                 "recycled": recycled})
            print(f"minibatch {fence_m:5d}  [compact] dead={n_dead} "
                  f"live_w={live} -> {live_new}  W_cap={cfg.vocab_size}"
                  + (f"  recycled_topics={recycled}" if recycled else ""),
                  flush=True)
        occupancy_trace.append({"m": fence_m, "live_w": live_done,
                                "w_cap": cfg.vocab_size})

    seg_start = start_m
    while seg_start < args.minibatches:
        # compaction fences cut the stream into segments: a fresh
        # prefetched generator per segment means prefetch can never run
        # past the fence, so the fence sees a fully-drained pipeline
        seg_end = (min(args.minibatches,
                       (seg_start // compact_every + 1) * compact_every)
                   if compact_every else args.minibatches)
        stream = make_stream(seg_start, seg_end)
        if ps:
            stream = _with_lookahead(stream)
        for m, item in enumerate(stream, start=seg_start):
            nxt = None
            if ps:
                item, nxt = item
            if dynamic:
                batch, ntok, live_b = item
            else:
                (batch, ntok), live_b = item, None
            if dynamic and live_b >= cfg.vocab_size:
                # capacity-rung crossing: fence the async pipeline, pad the
                # carry to the next rung (guard rows), rebuild + rewarm the
                # step, and checkpoint the grown state so a crash right here
                # resumes cleanly on the new rung (§12).  live_done (the
                # pre-growth prefix) is what the fence persists — this batch
                # has not been consumed yet.
                jax.block_until_ready(state.phi_acc)
                t_g = time.time()
                new_cap = next_capacity(live_b, cfg.vocab_size,
                                        args.w_cap_min, args.w_growth)
                state = lifecycle.resize_state(state, new_cap)
                compiles_prev += max(_compiles(step_fn), 0)
                cfg = dataclasses.replace(cfg, vocab_size=new_cap)
                step_fn, meter = build_step(cfg)
                if args.warmup_buckets:
                    warm_buckets(step_fn, cfg)
                if args.ckpt_dir:
                    ckpt.save(args.ckpt_dir, m, _state_tree(state),
                              extra=dyn_extra(m, live_done))
                growth_s += time.time() - t_g
                growth_events.append({"m": m, "w_cap": new_cap,
                                      "live_w": live_b})
                print(f"minibatch {m + 1:5d}  [grow] live_w={live_b} -> "
                      f"W_cap={new_cap}", flush=True)
            crash_victims = []
            if ps:
                # elastic membership events fence at batch index m (§17):
                # joins/leaves repartition the round-robin stream BEFORE
                # assignment; a crash fires AFTER the step (the victim
                # dies mid-batch, its push is lost)
                for kind, name in elastic_events.get(m, ()):
                    if kind == "join":
                        if name not in ps_workers:
                            ps_workers[name] = make_worker(name)
                        if name not in ps_active:
                            ps_active.append(name)
                        elastic_log.append({"m": m, "event": "join",
                                            "worker": name})
                    elif kind == "leave":
                        if name not in ps_active:
                            raise ValueError(f"elastic leave of unknown "
                                             f"worker {name!r} at batch {m}")
                        if len(ps_active) == 1:
                            raise ValueError(f"elastic leave of {name!r} at "
                                             f"batch {m} leaves no workers")
                        ps_workers[name].flush()
                        ps_retired.append(ps_workers.pop(name))
                        ps_active.remove(name)
                        elastic_log.append({"m": m, "event": "leave",
                                            "worker": name})
                    else:
                        crash_victims.append(name)
                cli = ps_workers[ps_active[m % len(ps_active)]]
                # refresh the replica's touched rows from the server (waits
                # on the prefetched pull; the wait is the overlap instrument)
                rows = touched_rows_of(batch.word_ids, batch.counts)
                state_pre = None
                if crash_victims:
                    # crash-replay restore point: DEEP copies, because the
                    # victim's step donates every carry leaf (m, rng,
                    # phi_acc) and the survivor must re-run from intact
                    # buffers
                    state_pre = LDATrainState(
                        phi_acc=jnp.array(state.phi_acc),
                        m=jnp.array(state.m), rng=jnp.array(state.rng))
                state = LDATrainState(
                    phi_acc=cli.begin_batch(m + 1, rows,
                                            state.phi_acc),
                    m=state.m, rng=state.rng)
            if dynamic:
                state, diag = step_fn(state, batch.word_ids, batch.counts,
                                      jnp.asarray(live_b, jnp.int32))
            else:
                state, diag = step_fn(state, batch.word_ids, batch.counts)
            if ps:
                for name in crash_victims:
                    if name not in ps_active:
                        continue           # already left/crashed
                    if len(ps_active) == 1:
                        raise ValueError(f"elastic crash of {name!r} at "
                                         f"batch {m} leaves no survivor")
                    assigned = ps_active[m % len(ps_active)] == name
                    ps_retired.append(ps_workers.pop(name))
                    ps_active.remove(name)
                    elastic_log.append({"m": m, "event": "crash",
                                        "worker": name,
                                        "replayed": assigned})
                    if assigned:
                        # the victim died before pushing this batch: a
                        # survivor replays it from the pre-batch carry.
                        # begin_batch re-pulls the same committed rows
                        # (the victim never pushed) and the step re-runs
                        # with the same rng, so the trajectory is
                        # identical to an uncrashed run at S=0 (pinned)
                        cli = ps_workers[ps_active[m % len(ps_active)]]
                        state = LDATrainState(
                            phi_acc=cli.begin_batch(m + 1, rows,
                                                    state_pre.phi_acc),
                            m=state_pre.m, rng=state_pre.rng)
                        if dynamic:
                            state, diag = step_fn(
                                state, batch.word_ids, batch.counts,
                                jnp.asarray(live_b, jnp.int32))
                        else:
                            state, diag = step_fn(state, batch.word_ids,
                                                  batch.counts)
                # prefetch BEFORE the push settles: at S>=1 the pull is
                # served from a bounded-stale snapshot and fully overlaps;
                # at S=0 it blocks server-side until this push commits.
                # The prefetch is issued on the worker the NEXT batch is
                # assigned to (membership events at m+1 may reroute it —
                # the mismatched prefetch is then drained, not leaked).
                if nxt is not None:
                    nb = nxt[0]
                    nxt_cli = ps_workers[ps_active[(m + 1) % len(ps_active)]]
                    nxt_cli.prefetch(
                        m + 2, touched_rows_of(nb.word_ids, nb.counts))
                cli.end_batch(m + 1, state.phi_acc, rows)
            buf.append(diag["mean_r"], diag["iters"])
            tokens += ntok
            if live_b is not None:
                live_done = live_b
            consumed_m = m
            step_no = m + 1
            if args.log_every and step_no % args.log_every == 0:
                # the ONLY recurring host sync, amortized over --log-every
                dt = time.time() - t0
                print(f"minibatch {step_no:5d}  "
                      f"mean_r={float(diag['mean_r']):.4f}"
                      f"  iters={int(diag['iters']):3d}"
                      f"  tokens/s={tokens / max(dt, 1e-9):,.0f}"
                      f"  compiles={compiles_prev + _compiles(step_fn)}",
                      flush=True)
            if args.eval_every and step_no % args.eval_every == 0:
                c_eval = _COMPILE_CLOCK.total
                ppl = eval_ppl()
                eval_compile_s += _COMPILE_CLOCK.total - c_eval
                ppl_trace.append((step_no, float(ppl)))
                print(f"minibatch {step_no:5d}  held-out ppl={ppl:.2f}",
                      flush=True)
            if on_batch is not None:
                on_batch(step_no, state, diag)
            if args.crash_at and step_no == args.crash_at and start_m == 0:
                # fresh runs only: a resumed run sails past the simulated
                # failure, so "rerun the same command" terminates
                raise SystemExit(f"[simulated crash] after minibatch "
                                 f"{step_no}")
            if args.ckpt_dir and args.ckpt_every and \
                    step_no % args.ckpt_every == 0:
                if ps:
                    ps_sync_state()
                ckpt.save(args.ckpt_dir, step_no, _state_tree(state),
                          extra=dyn_extra(step_no, live_done))
        seg_start = seg_end
        if compact_every:
            compaction_fence(seg_end)

    jax.block_until_ready(state.phi_acc)
    if ps:
        # drain + adopt the authoritative server statistic (part of the
        # run: a real fleet pays this once at shutdown)
        ps_sync_state()
    wall = time.time() - t0
    # step-function compiles only: eval jits are accounted separately
    compile_s = _COMPILE_CLOCK.total - compile_s0 - eval_compile_s

    ppl = float(eval_ppl())
    rows = buf.rows()
    mean_r = [float(r) for r, _ in rows]
    iters = [int(i) for _, i in rows]
    # steady-state throughput: mid-stream rung growth and compaction
    # fences (compile + rewarm + fence) are bounded startup-like costs,
    # excluded the same way the pre-loop warmup is; wall_s is inclusive.
    steady_s = max(wall - growth_s - compact_s, 1e-9)
    result = {
        "first_m": start_m,
        "mean_r": mean_r,
        "iters": iters,
        "compiles": compiles_prev + _compiles(step_fn),
        "len_buckets": list(buckets),
        "tokens": tokens,
        "wall_s": wall,
        "warmup_s": warmup_s,
        "compile_s": compile_s,
        "tokens_per_s": tokens / steady_s,
        "ppl": ppl,
        "ppl_trace": ppl_trace,
        "bytes_by_phase": dict(meter.bytes_by_phase),
        "per_minibatch_bytes": (meter.per_minibatch_bytes(iters[-1])
                                if iters else 0),
        "phi_acc": np.asarray(state.phi_acc),
    }
    if ps:
        # aggregate worker-side stats over every client that ever ran
        # (elastic membership: retired workers still did work)
        all_workers = list(ps_workers.values()) + ps_retired
        touched_all = [t for w in all_workers for t in w.touched_history]
        st = {
            "wire_bytes": ps_transport.total_bytes,
            "bytes_by_link": ps_transport.bytes_by_link(),
            "pull_wait_s": sum(w.pull_wait_s for w in all_workers),
            "push_wait_s": sum(w.push_wait_s for w in all_workers),
            "mean_touched_rows": (float(np.mean(touched_all))
                                  if touched_all else 0.0),
        }
        done_b = max(args.minibatches - start_m, 1)
        mt = max(int(round(st["mean_touched_rows"])), 1)
        result.update(
            staleness=args.staleness,
            ps_wire_bytes=int(st["wire_bytes"]),
            ps_wire_per_minibatch=st["wire_bytes"] / done_b,
            ps_pull_wait_s=st["pull_wait_s"],
            ps_push_wait_s=st["push_wait_s"],
            mean_touched_rows=st["mean_touched_rows"],
            ps_bytes_by_link=st["bytes_by_link"],
            # fault-tolerance instruments (DESIGN.md §17)
            ps_retries=sum(w.retries for w in all_workers),
            ps_replayed_pushes=sum(w.replayed_pushes for w in all_workers),
            ps_recoveries=sum(w.recoveries for w in all_workers),
            ps_retry_wire_bytes=sum(w.retry_wire_bytes
                                    for w in all_workers),
            ps_duplicates_dropped=ps_server.duplicates_dropped,
            ps_recovery_log=list(ps_server.recovery_log),
            chaos_events=(ps_transport.event_counts()
                          if chaos_on else {}),
            elastic_log=elastic_log,
            ps_workers=sorted(ps_workers),
            # trace-time push/pull model billed at the measured mean
            # touched-row count (CommMeter w_rows scaling) — the analytic
            # cross-check of the measured wire bytes above
            bytes_by_phase_touched=dict(meter.bytes_by_phase_at(mt)),
            per_minibatch_bytes_touched=(
                meter.per_minibatch_bytes(iters[-1], live_w=mt)
                if iters else 0))
        ps_transport.close()
    if dynamic:
        result.update(
            w_cap=cfg.vocab_size,
            live_w=live_done,
            growth_s=growth_s,
            growth_events=growth_events,
            compact_s=compact_s,
            compaction_events=compaction_events,
            occupancy_trace=occupancy_trace,
            vocab_version=vocab_version,
            vocab_keys=vocab.keys_upto(live_done),
            bytes_by_phase_live=dict(meter.bytes_by_phase_at(live_done)),
            per_minibatch_bytes_live=(
                meter.per_minibatch_bytes(iters[-1], live_w=live_done)
                if iters else 0))
    return result


def main(argv=None):
    from repro.launch.compile_cache import use_compile_cache

    args = build_parser().parse_args(argv)
    use_compile_cache()
    res = train_loop(args)
    done = args.minibatches - res["first_m"]
    print(f"[done] {done} minibatches  final mean_r="
          f"{res['mean_r'][-1] if res['mean_r'] else float('nan'):.4f}  "
          f"held-out ppl={res['ppl']:.2f}")
    print(f"[perf] tokens/s={res['tokens_per_s']:,.0f}  "
          f"compiles={res['compiles']} (buckets={res['len_buckets']})  "
          f"warmup={res['warmup_s']:.1f}s  wall={res['wall_s']:.1f}s "
          f"(+{res['compile_s']:.1f}s in-stream compile)")
    print(f"[comm] per-minibatch bytes={res['per_minibatch_bytes']:,} "
          f"(phases: {res['bytes_by_phase']})")
    if args.backend == "ps":
        print(f"[ps] staleness={res['staleness']}  wire/minibatch="
              f"{res['ps_wire_per_minibatch']:,.0f}B  mean_touched_rows="
              f"{res['mean_touched_rows']:.0f}  pull_wait="
              f"{res['ps_pull_wait_s']:.2f}s  push_wait="
              f"{res['ps_push_wait_s']:.2f}s")
        if res.get("chaos_events") or res.get("ps_retries"):
            print(f"[chaos] events={res['chaos_events']}  "
                  f"retries={res['ps_retries']}  "
                  f"replayed={res['ps_replayed_pushes']}  "
                  f"recoveries={res['ps_recoveries']}  "
                  f"dup_dropped={res['ps_duplicates_dropped']}")
        if res.get("elastic_log"):
            print(f"[elastic] workers={res['ps_workers']}  "
                  f"events={res['elastic_log']}")
    if args.dynamic_vocab:
        print(f"[vocab] live_w={res['live_w']}  W_cap={res['w_cap']}  "
              f"growths={len(res['growth_events'])} "
              f"({res['growth_s']:.1f}s)  per-minibatch bytes at live W="
              f"{res['per_minibatch_bytes_live']:,}")
        if args.compact_every:
            print(f"[lifecycle] compactions={len(res['compaction_events'])} "
                  f"({res['compact_s']:.1f}s)  vocab_version="
                  f"{res['vocab_version']}  occupancy="
                  f"{res['live_w']}/{res['w_cap']}")
    return res


if __name__ == "__main__":
    main()
