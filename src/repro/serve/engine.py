"""Serving engines: the paper's train-once / fold-in-forever deployment
(Eq. 20 protocol) as a production request loop (DESIGN.md §11, §16).

Two admission runtimes share one inference core (`core.infer`):

  - **`SlabEngine` — continuous batching (DESIGN.md §16, the default).**
    A fixed [slots, slot_len] in-flight slab where every slot holds one
    live document; the jitted step advances all slots a few fold-in
    sweeps, slots whose residual bound clears retire and are refilled
    from the queue mid-flight.  No bucket barriers: a request never
    waits for a batch to fill and a converged document never holds its
    slot while stragglers finish.  Compiles are bounded by the slab
    geometry (ONE step shape), never by request shapes.  On top: a
    per-tenant theta LRU (`serve.cache.ThetaCache`) serving or
    warm-starting repeat documents, and an `OOVTrigger` turning the
    oov_rate stat into hot-OOV admission batches for the train side.
  - **`FoldInEngine` — bucket-ladder admission (DESIGN.md §11).**
    Requests queue per length bucket and dispatch when `batch_docs`
    accumulate (or on flush).  Kept as the barrier baseline BENCH_serve
    measures the slab against, and for strictly batch-at-a-time
    deployments (offline eval sweeps).

Shared contracts: asynchronous dispatch (submit never blocks on device
work), per-request latency measured when the result is actually ready,
`CommMeter`-billed sync bytes for a topic-sharded phi — the slab bills
per retired document at retirement (requests share a step, so batch-level
attribution would be wrong), the bucket engine per dispatched batch —
OOV admission through the guard row (never an exception, DESIGN.md §12),
and version-stamped `swap_phi` hot-swap (DESIGN.md §14): queued work
drains under the generation that admitted it, so no request ever
observes a torn phi.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter, deque
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import infer, perplexity
from repro.core.types import LDAConfig
from repro.data.batching import bucket_len, docs_to_padded, slab_refill
from repro.serve.cache import ThetaCache, doc_digest

_EMPTY_DOC = (np.zeros(1, np.int32), np.zeros(1, np.float32))
# stats() reads latency percentiles over the most recent requests only
LATENCY_WINDOW = 65536


@dataclasses.dataclass
class ServeResult:
    """One served request: the topic mixture plus serving diagnostics."""

    req_id: int
    theta: np.ndarray              # [K] normalized topic mixture
    latency_s: float               # submit -> batch result ready
    bucket: int                    # L bucket / slab slot that admitted it
    iters: int                     # fold-in sweeps run (0 for a cache hit)
    mean_r: float                  # residual at exit (per-doc on the slab)
    oov_tokens: float = 0.0        # token mass folded in via the OOV row
    phi_version: int = 0           # vocab/phi generation that served it (§14)
    comm_bytes: float = 0.0        # sync bytes billed to this request (§16)
    cached: bool = False           # served straight from the theta cache
    tenant: Optional[Hashable] = None
    error: Optional[str] = None    # quarantine flag: "nonfinite_input" /
    #                                "nonfinite_theta" — theta is the prior
    #                                mixture, never cached (§17)


@dataclasses.dataclass
class Shed:
    """A typed admission rejection (DESIGN.md §17): the queue would blow
    the SLO deadline, so the request is refused at submit time instead of
    queueing unboundedly.  Returned by ``SlabEngine.submit`` when
    ``admission_slo_s`` is set; never mixed into served results."""

    req_id: int
    est_wait_s: float              # drain-model estimate that tripped it
    slo_s: float
    queue_depth: int
    tenant: Optional[Hashable] = None


def _prepare_phi(phi_acc, cfg: LDAConfig, live_words: Optional[int],
                 normalized: bool) -> Tuple[jnp.ndarray, int, int]:
    """Normalize a phi statistic for serving: f32 upcast, guard-row
    guarantee, live-W beta-prior normalization (DESIGN.md §12).

    Returns ``(phi_norm [W', K], live, w_cap)`` where W' >= w_cap includes
    at least one guard row above ``live`` serving the OOV mass.
    """
    phi_in = jnp.asarray(phi_acc)
    if jnp.issubdtype(phi_in.dtype, jnp.floating) \
            and phi_in.dtype != jnp.float32:
        # compressed accumulators (DESIGN.md §13): the statistic may
        # arrive bf16 from a phi_acc_dtype='bfloat16' run — serving
        # math (normalization, fold-in) always runs in f32
        phi_in = phi_in.astype(jnp.float32)
    w_cap = int(phi_in.shape[0])
    live = int(live_words) if live_words is not None else w_cap
    if not 0 < live <= w_cap:
        # live_words=0 (a checkpoint fenced before any admission) is
        # rejected too: there is no trained row to serve from
        raise ValueError(f"live_words={live_words} outside phi's "
                         f"{w_cap} rows")
    if live == w_cap:
        # guarantee a guard row to serve OOV words from (appended rows
        # are zero statistic == pure beta prior after normalization)
        phi_in = jnp.concatenate(
            [phi_in, jnp.zeros((1, phi_in.shape[1]), phi_in.dtype)])
    if normalized:
        # caller-normalized phi: guard rows fall back to the uniform
        # topic prior (no statistic left to derive beta/denom from)
        guard = jnp.arange(phi_in.shape[0])[:, None] >= live
        phi_norm = jnp.where(guard, 1.0 / phi_in.shape[1], phi_in)
    else:
        phi_norm = perplexity.normalize_phi(phi_in, cfg.beta, live_w=live)
    return phi_norm, live, w_cap


class OOVTrigger:
    """Close the serve->train loop on vocabulary drift (DESIGN.md §16).

    The engines already *measure* OOV pressure (``oov_rate`` in
    ``stats()``); this turns the measurement into an actionable training
    signal.  Every admitted request reports its OOV keys here; once at
    least ``min_docs`` documents accumulated AND their windowed OOV token
    rate crossed ``rate_threshold``, the hottest unseen keys are emitted
    as an *admission batch*: a list of raw external-key documents shaped
    exactly like a training corpus chunk, ready for
    ``data.batching.vocab_mapped_minibatch_stream(batch, vocab,
    admit=True)`` (or the streaming driver's admission path) to fold the
    hot vocabulary into the next training segment.  The window resets on
    emission, so a sustained drift emits a batch per window rather than
    one giant batch at shutdown.
    """

    def __init__(self, rate_threshold: float = 0.05, min_docs: int = 64,
                 batch_keys: int = 128):
        self.rate_threshold = float(rate_threshold)
        self.min_docs = int(min_docs)
        self.batch_keys = int(batch_keys)
        self._hot: Counter = Counter()
        self._docs = 0
        self._tokens = 0.0
        self._oov_tokens = 0.0
        self._batches: List[list] = []
        self.emitted = 0

    def observe(self, oov_keys, oov_counts, total_tokens: float) -> None:
        """One admitted request: its OOV (external key, count) pairs and
        its total token mass."""
        self._docs += 1
        self._tokens += float(total_tokens)
        for k, c in zip(oov_keys, oov_counts):
            self._hot[k] += float(c)
            self._oov_tokens += float(c)
        self._maybe_emit()

    def _maybe_emit(self) -> None:
        if self._docs < self.min_docs or self._tokens <= 0:
            return
        if self._oov_tokens / self._tokens < self.rate_threshold:
            return
        hot = self._hot.most_common(self.batch_keys)
        if not hot:
            return
        keys = np.asarray([k for k, _ in hot], np.int64)
        cnts = np.asarray([c for _, c in hot], np.float32)
        # one admission batch == one corpus chunk of raw external-key docs
        self._batches.append([(keys, cnts)])
        self.emitted += 1
        self._hot.clear()
        self._docs = 0
        self._tokens = 0.0
        self._oov_tokens = 0.0

    def take(self) -> List[list]:
        """Pop every pending admission batch (the train side's poll)."""
        out, self._batches = self._batches, []
        return out


def _load_serving_checkpoint(ckpt_dir: str, cfg: Optional[LDAConfig],
                             step: Optional[int], sharding, kw: dict):
    """Shared checkpoint-to-serve loader for both engines: restore phi,
    pick up a dynamic-vocabulary table, and (when `cfg` is omitted) derive
    the model geometry from the driver's saved run signature."""
    from repro.data.vocab import VocabMap
    from repro.dist import checkpoint as ckpt

    # dtype=float32 up-casts a compressed (bf16) checkpoint at load:
    # serving math always runs in f32 whatever the training storage
    phi_acc, extra, _ = ckpt.restore_phi(ckpt_dir, step=step,
                                         sharding=sharding,
                                         dtype=jnp.float32)
    dyn = extra.get("dyn")
    if dyn is not None:
        # dynamic-vocabulary checkpoint: pick up the vocab table and
        # live size saved with phi — rows above live_w are guard rows.
        # vocab_version stamps which compaction generation this table
        # belongs to (served back as phi_version on every result, §14)
        kw.setdefault("live_words", int(dyn["live_w"]))
        kw.setdefault("phi_version", int(dyn.get("vocab_version", 0)))
        if dyn.get("vocab_keys") is not None:
            kw.setdefault("vocab", VocabMap(dyn["vocab_keys"]))
    if cfg is None:
        run = extra.get("run", {})
        # geometry comes from phi itself (always right, including the
        # capacity rung of a dynamic checkpoint); the saved run
        # signature only routes the knobs the fold-in body reads —
        # impl (jnp vs Pallas) and sync_dtype (reducer payload width)
        if not run:
            raise ValueError(
                f"checkpoint in {ckpt_dir!r} carries no run signature, so "
                f"the kernel path it was trained for is unknown; pass cfg=")
        cfg = LDAConfig(vocab_size=int(phi_acc.shape[0]),
                        num_topics=int(phi_acc.shape[1]),
                        impl=str(run.get("impl", "jnp")),
                        sync_dtype=str(run.get("sync_dtype",
                                               "float32")))
    return phi_acc, cfg, kw


@dataclasses.dataclass
class _Dispatch:
    bucket: int
    reqs: List[Tuple[int, float, float]]    # (req_id, t_submit, oov_tokens)
    theta: jnp.ndarray                      # device future [D, K]
    iters: jnp.ndarray                      # device scalar
    mean_r: jnp.ndarray                     # device scalar
    phi_version: int = 0                    # phi generation at dispatch


class FoldInEngine:
    """Serve topic mixtures for incoming documents with phi fixed.

    `phi_acc` is the trained sufficient statistic ([W, K], as checkpointed
    by the streaming driver); pass ``normalized=True`` when handing an
    already-normalized topic-word matrix.  ``topic_shards > 1`` serves a
    topic-sharded phi ([N, W, K/N] internally) with psum'd renormalization
    under the vmap simulation — bit-identical collectives to a model-axis
    mesh, metered per batch.

    **OOV admission** (DESIGN.md §12): a serving process must never crash
    or silently corrupt on an unseen word.  `live_words` marks rows
    [live_words, W) of phi as guard rows (a dynamic-vocabulary
    checkpoint); when absent, one guard row is appended.  phi is
    normalized over the live rows only and every guard row carries the
    beta-prior mass beta/denom — the posterior of one unseen word — so
    folding an OOV token in is exact smoothed-LDA math, not a clamp.
    Incoming word ids are translated through `vocab` (an external-key
    ``data.vocab.VocabMap``, lookup only) when given, else range-checked;
    unknown/out-of-range words route to the first guard row and their
    token mass is reported as ``oov_rate`` in ``stats()`` and
    ``oov_tokens`` per result.
    """

    def __init__(self, phi_acc, cfg: LDAConfig, *,
                 len_buckets: Sequence[int] = (16, 32, 64, 128),
                 batch_docs: int = 32, fold_iters: int = 30,
                 residual_tol: float = 1e-2, topic_shards: int = 1,
                 sync_dtype=None, normalized: bool = False,
                 impl: Optional[str] = None, seed: int = 0,
                 warmup: bool = True, vocab=None,
                 live_words: Optional[int] = None,
                 phi_version: int = 0):
        self.len_buckets = tuple(sorted(int(b) for b in len_buckets))
        if any(b % 8 for b in self.len_buckets):
            raise ValueError(f"len_buckets must be multiples of 8 "
                             f"(docs_to_padded pads L to 8): "
                             f"{self.len_buckets}")
        # the driver's L-invariant init contract carries over to serving:
        # the random field is drawn at the largest bucket and sliced, so a
        # document's theta does not depend on which bucket admitted it
        self.cfg = cfg = dataclasses.replace(
            cfg, init_pad_len=max(self.len_buckets[-1],
                                  cfg.init_pad_len or 0))
        if sync_dtype is None:
            sync_dtype = (jnp.bfloat16 if cfg.sync_dtype == "bfloat16"
                          else jnp.float32)
        self.batch_docs = int(batch_docs)
        self.fold_iters = int(fold_iters)
        self.residual_tol = float(residual_tol)
        self.phi_version = int(phi_version)
        self._topic_shards = int(topic_shards)
        self._sync_dtype = sync_dtype
        self._impl = impl
        phi_norm, self.live_words, self.w_cap = _prepare_phi(
            phi_acc, cfg, live_words, normalized)
        self._oov_row = self.live_words
        self._vocab = vocab
        # the step's compiled W (and the Pallas guard-row index) is the
        # padded serving capacity, not the user-visible cfg.vocab_size
        self._cfg = dataclasses.replace(cfg, vocab_size=phi_norm.shape[0])
        self._phi = infer.split_topic_shards(phi_norm, topic_shards)
        self._step, self.meter = infer.make_fold_in_step(
            self._cfg, fold_iters=self.fold_iters,
            residual_tol=self.residual_tol, topic_shards=topic_shards,
            sync_dtype=sync_dtype, impl=impl)
        self._key = jax.random.PRNGKey(seed)
        self._queues: Dict[int, List[Tuple[int, tuple, float, float]]] = {
            b: [] for b in self.len_buckets}
        self._pending: List[_Dispatch] = []
        self._next_id = 0
        self._dispatches = 0
        self._iters_sum = 0
        self._latencies: "deque[float]" = deque(maxlen=LATENCY_WINDOW)
        self._served = 0
        self._oov_tokens = 0.0
        self._total_tokens = 0.0
        self._t_first: Optional[float] = None
        self._t_last_done: Optional[float] = None
        self.warmup_s = 0.0
        self._warm = bool(warmup)
        if warmup:
            self._warmup()

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, cfg: Optional[LDAConfig] = None,
                        step: Optional[int] = None, sharding=None,
                        **kw) -> "FoldInEngine":
        """Checkpoint-to-serve: load phi (and, when `cfg` is omitted, the
        model geometry from the driver's saved run signature) and build an
        engine — no training carry ever touches the serving process."""
        phi_acc, cfg, kw = _load_serving_checkpoint(ckpt_dir, cfg, step,
                                                    sharding, kw)
        return cls(phi_acc, cfg, **kw)

    # ----------------------------------------------------- lifecycle swap

    def swap_phi(self, phi_acc, *, live_words: Optional[int] = None,
                 vocab=None, phi_version: Optional[int] = None) -> None:
        """Install a new (phi statistic, vocab table) generation — the
        serving half of a training-side lifecycle event (DESIGN.md §14:
        a compaction remap, a decayed refresh, a recycled topic set).

        Torn-remap-proof by construction: requests already queued were
        admitted (translated to rows) under the OLD vocab, so they are
        flushed and dispatched against the old phi first — a dispatched
        batch captures the phi it runs on, and its results keep the old
        ``phi_version`` stamp.  Everything submitted after the swap
        translates and folds in under the new generation.  The jitted
        step is rebuilt only when the serving capacity actually changes
        (a compaction that dropped a rung); same-capacity swaps — a
        remap within the rung — reuse the compiled program.
        """
        self.flush()
        phi_norm, live, self.w_cap = _prepare_phi(phi_acc, self.cfg,
                                                  live_words, False)
        rebuilt = phi_norm.shape[0] != self._cfg.vocab_size
        if rebuilt:
            self._cfg = dataclasses.replace(self._cfg,
                                            vocab_size=phi_norm.shape[0])
            self._step, self.meter = infer.make_fold_in_step(
                self._cfg, fold_iters=self.fold_iters,
                residual_tol=self.residual_tol,
                topic_shards=self._topic_shards,
                sync_dtype=self._sync_dtype, impl=self._impl)
        self.live_words = live
        self._oov_row = live
        if vocab is not None:
            self._vocab = vocab
        self._phi = infer.split_topic_shards(phi_norm, self._topic_shards)
        self.phi_version = (int(phi_version) if phi_version is not None
                            else self.phi_version + 1)
        if rebuilt and self._warm:
            self._warmup()

    # ---------------------------------------------------------- admission

    def _admit_doc(self, doc: Tuple[np.ndarray, np.ndarray]
                   ) -> Tuple[tuple, float]:
        """Translate a document into live phi rows; never raises on OOV.

        With a vocab table the ids are EXTERNAL keys (lookup only — a
        serving process must not move the vocabulary); without one they
        are raw rows, range-checked against the live vocabulary.  Either
        way unknown words land on the first guard row, whose normalized
        phi value is the beta-prior mass (finite theta by construction).
        Returns ((rows, counts), oov_token_mass).
        """
        ids, counts = doc
        counts = np.asarray(counts, np.float32)
        if self._vocab is not None:
            rows = self._vocab.rows(
                ids.tolist() if hasattr(ids, "tolist") else ids,
                admit=False, oov_row=self._oov_row)
        else:
            ids = np.asarray(ids)
            rows = np.where((ids >= 0) & (ids < self.live_words),
                            ids, self._oov_row).astype(np.int32)
        oov = float(counts[rows == self._oov_row].sum())
        self._oov_tokens += oov
        self._total_tokens += float(counts.sum())
        return (rows, counts), oov

    def submit(self, doc: Tuple[np.ndarray, np.ndarray],
               req_id: Optional[int] = None) -> int:
        """Enqueue one document (word_ids, counts); never blocks on device
        work.  Returns the request id its `ServeResult` will carry."""
        if req_id is None:
            req_id = self._next_id
        self._next_id = max(self._next_id, req_id) + 1
        now = time.time()
        if self._t_first is None:
            self._t_first = now
        doc, oov = self._admit_doc(doc)
        b = bucket_len(len(doc[0]), self.len_buckets)
        q = self._queues[b]
        q.append((req_id, doc, now, oov))
        if len(q) >= self.batch_docs:
            self._dispatch(b)
        return req_id

    def flush(self) -> None:
        """Dispatch every partially-filled bucket (padded with empty docs,
        so D — and therefore the compiled shapes — never varies)."""
        for b in self.len_buckets:
            while self._queues[b]:
                self._dispatch(b)

    def flush_stale(self, max_age_s: float, now: Optional[float] = None
                    ) -> int:
        """Dispatch buckets whose OLDEST queued request has waited at
        least ``max_age_s`` — the open-loop latency bound of bucket-ladder
        admission.  Under a sustained arrival process a bucket may fill
        too slowly (mixed-length traffic spreads over the ladder); this
        caps a request's queueing delay at the cost of padded-slot work
        (a partial flush still computes the full ``batch_docs``).
        Returns the number of dispatches."""
        now = time.time() if now is None else now
        n = 0
        for b in self.len_buckets:
            while self._queues[b] and now - self._queues[b][0][2] >= \
                    max_age_s:
                self._dispatch(b)
                n += 1
        return n

    def _dispatch(self, bucket: int) -> None:
        q = self._queues[bucket]
        take, self._queues[bucket] = q[:self.batch_docs], q[self.batch_docs:]
        docs = [doc for _, doc, _, _ in take]
        docs += [_EMPTY_DOC] * (self.batch_docs - len(docs))
        mb = docs_to_padded(docs, max_len=bucket)
        self._key, sub = jax.random.split(self._key)
        theta, iters, mean_r = self._step(self._phi, sub,
                                          mb.word_ids, mb.counts)
        self._pending.append(_Dispatch(
            bucket=bucket, reqs=[(rid, t, oov) for rid, _, t, oov in take],
            theta=theta, iters=iters, mean_r=mean_r,
            phi_version=self.phi_version))
        self._dispatches += 1

    def _warmup(self) -> None:
        """AOT-compile the step for every bucket shape before any request
        arrives (the driver's --warmup-buckets contract carries over)."""
        t0 = time.time()
        key = jax.random.PRNGKey(0)
        out = None
        for b in self.len_buckets:
            out = self._step(self._phi, key,
                             jnp.zeros((self.batch_docs, b), jnp.int32),
                             jnp.zeros((self.batch_docs, b), jnp.float32))
            key = jax.random.PRNGKey(0)
        if out is not None:
            jax.block_until_ready(out[0])
        self.warmup_s = time.time() - t0

    # ------------------------------------------------------------ harvest

    def _materialize(self, d: _Dispatch) -> List[ServeResult]:
        theta = np.asarray(jax.block_until_ready(d.theta))
        t_done = time.time()
        iters, mean_r = int(d.iters), float(d.mean_r)
        self._iters_sum += iters
        results = []
        for row, (rid, t_sub, oov) in enumerate(d.reqs):
            lat = t_done - t_sub
            self._latencies.append(lat)
            results.append(ServeResult(
                req_id=rid, theta=theta[row], latency_s=lat,
                bucket=d.bucket, iters=iters, mean_r=mean_r,
                oov_tokens=oov, phi_version=d.phi_version))
        self._t_last_done = t_done
        self._served += len(results)
        return results

    def drain(self) -> List[ServeResult]:
        """Flush partial buckets, then materialize every pending batch in
        dispatch order.  Per-request latency is measured when the batch's
        theta is actually ready — the first host sync any request pays."""
        self.flush()
        results: List[ServeResult] = []
        for d in self._pending:
            results.extend(self._materialize(d))
        self._pending.clear()
        return results

    def poll(self) -> List[ServeResult]:
        """Materialize only the dispatched batches whose device work has
        ALREADY finished (never blocks, never flushes) — the open-loop
        driver's harvest.  Dispatches complete in order on one stream, so
        the ready set is a prefix of the pending list."""
        results: List[ServeResult] = []
        while self._pending:
            head = self._pending[0]
            if not head.theta.is_ready():
                break
            results.extend(self._materialize(head))
            self._pending.pop(0)
        return results

    def in_flight(self) -> int:
        """Requests submitted but not yet returned (queued + dispatched)."""
        return (sum(len(q) for q in self._queues.values())
                + sum(len(d.reqs) for d in self._pending))

    # -------------------------------------------------------------- stats

    def _compiles(self) -> int:
        return int(self._step._cache_size())

    def stats(self) -> Dict[str, object]:
        """Serving scorecard: docs/s, latency percentiles, compile bound,
        and the per-request communication bytes of a sharded phi."""
        lats = np.asarray(self._latencies, np.float64)
        span = ((self._t_last_done - self._t_first)
                if self._latencies and self._t_first is not None else 0.0)
        mean_iters = (self._iters_sum / self._dispatches
                      if self._dispatches else 0.0)
        per_batch_bytes = self.meter.per_minibatch_bytes(max(mean_iters, 1))
        return {
            "served": self._served,
            "dispatches": self._dispatches,
            "docs_per_s": self._served / span if span > 0 else float("nan"),
            "latency_p50_s": float(np.percentile(lats, 50)) if lats.size else
            float("nan"),
            "latency_p99_s": float(np.percentile(lats, 99)) if lats.size else
            float("nan"),
            "mean_fold_iters": mean_iters,
            "compiles": self._compiles(),
            "len_buckets": list(self.len_buckets),
            "warmup_s": self.warmup_s,
            "bytes_by_phase": dict(self.meter.bytes_by_phase),
            "per_request_bytes": per_batch_bytes / max(self.batch_docs, 1),
            "live_words": self.live_words,
            "w_cap": self.w_cap,
            # ladder occupancy: how full the trained capacity rung is —
            # climbing toward 1.0 means the next admission wave grows the
            # ladder; falling after a swap means compaction reclaimed rows
            "occupancy": self.live_words / max(self.w_cap, 1),
            "phi_version": self.phi_version,
            "oov_rate": (self._oov_tokens / self._total_tokens
                         if self._total_tokens else 0.0),
        }


# ---------------------------------------------------------------------------
# continuous-batching slab engine (DESIGN.md §16)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SlabReq:
    """Host-side record of one admitted request (queued or in a slot)."""

    req_id: int
    t_submit: float
    oov: float
    tenant: Optional[Hashable] = None
    digest: Optional[str] = None
    warm: Optional[np.ndarray] = None    # cached theta for warm-start


@dataclasses.dataclass
class _StepOut:
    """Device futures of one slab step, awaiting harvest.  Steps chain on
    the donated state without any host sync; the retirement mask is read
    back lazily (`is_ready` probe, or blocking once the pipeline window
    fills), so the jitted steps dispatch back-to-back."""

    retired: jnp.ndarray               # [B] bool (device future)
    theta: jnp.ndarray                 # [B, K]
    iters: jnp.ndarray                 # [B] int32
    r_doc: jnp.ndarray                 # [B] f32
    phi_version: int
    t_dispatch_ns: int                 # host clock of the dispatch (obs)


class SlabEngine:
    """Continuous-batching serving: one persistent in-flight slab instead
    of bucket barriers (DESIGN.md §16).

    Admission state machine, per slot: **admit** (translate, queue) ->
    **iterate** (the jitted `core.infer.make_slab_step` advances every
    live slot ``sweeps_per_step`` fold-in sweeps) -> **retire** (the
    slot's geometric-tail residual bound clears ``residual_tol`` or hits
    ``fold_iters``; its theta is harvested and billed) -> **refill** (the
    freed slot takes the next queued request mid-flight, no barrier).
    The compiled step shape is fixed by the slab geometry — requests of
    any length share ONE compile (over-long documents are truncated to
    ``slot_len`` by top-count mass, the same argument the paper applies
    to the vocabulary tail).

    On top of the slab:

      - **theta cache** (``theta_cache=``, an int capacity or a
        `serve.cache.ThetaCache`): repeat (tenant, content) documents
        either skip fold-in entirely (``cache_mode='serve'``) or
        warm-start their slot from the cached theta and retire in fewer
        sweeps (``cache_mode='warm'``); entries are phi_version-stamped,
        so a hot-swap invalidates them for free;
      - **OOV retraining trigger** (``oov_trigger=``, an `OOVTrigger`):
        admitted OOV keys feed a windowed rate threshold that emits
        hot-OOV admission batches for the train side
        (``take_retrain_batches()``);
      - **per-request byte billing**: requests share a step, so batch
        attribution would be wrong — each retired document is billed its
        own sweeps' share of the slab's metered collective bytes
        (``ServeResult.comm_bytes``), at retirement.

    ``swap_phi`` pumps the slab to empty first: queued work was
    row-translated under the admitting vocabulary, so it completes under
    the old (phi, version) and post-swap submissions fold in under the
    new one — no request ever observes a torn phi.  phi is a step
    *argument*, so a capacity change merely re-specializes the jit (the
    ``compiles`` stat counts it); same-capacity swaps reuse the program.
    """

    def __init__(self, phi_acc, cfg: LDAConfig, *, slots: int = 64,
                 slot_len: int = 64, sweeps_per_step: int = 4,
                 refill_cap: Optional[int] = None, fold_iters: int = 30,
                 residual_tol: float = 1e-2, topic_shards: int = 1,
                 sync_dtype=None, normalized: bool = False,
                 impl: Optional[str] = None, seed: int = 0,
                 warmup: bool = True, vocab=None,
                 live_words: Optional[int] = None, phi_version: int = 0,
                 theta_cache=None, cache_mode: str = "serve",
                 oov_trigger: Optional[OOVTrigger] = None,
                 pipeline: int = 4,
                 admission_slo_s: Optional[float] = None):
        if cache_mode not in ("serve", "warm"):
            raise ValueError(f"cache_mode must be 'serve' or 'warm': "
                             f"{cache_mode!r}")
        self.cfg = cfg
        self.slots = int(slots)
        self.slot_len = int(slot_len)
        self.sweeps_per_step = int(sweeps_per_step)
        # default refill lanes = slots/4: the refill scatter + in-step
        # random init run EVERY step whether lanes are used or not, so
        # full-width lanes tax steady state to speed up only cold start
        self._refill_cap = (max(1, self.slots // 4) if refill_cap is None
                            else int(refill_cap))
        self.fold_iters = int(fold_iters)
        self.residual_tol = float(residual_tol)
        self.phi_version = int(phi_version)
        self._topic_shards = int(topic_shards)
        self._K = int(cfg.num_topics)
        self.cache = (ThetaCache(theta_cache)
                      if isinstance(theta_cache, int) else theta_cache)
        self.cache_mode = cache_mode
        self.trigger = oov_trigger
        if sync_dtype is None:
            sync_dtype = (jnp.bfloat16 if cfg.sync_dtype == "bfloat16"
                          else jnp.float32)
        phi_norm, self.live_words, self.w_cap = _prepare_phi(
            phi_acc, cfg, live_words, normalized)
        self._oov_row = self.live_words
        self._vocab = vocab
        self._cfg = dataclasses.replace(cfg,
                                        vocab_size=int(phi_norm.shape[0]))
        self._phi = infer.split_topic_shards(phi_norm, topic_shards)
        self._init_state, self._step, self.meter = infer.make_slab_step(
            self._cfg, slots=self.slots, slot_len=self.slot_len,
            refill_cap=self._refill_cap,
            sweeps_per_step=self.sweeps_per_step,
            fold_iters=self.fold_iters, residual_tol=self.residual_tol,
            topic_shards=topic_shards, sync_dtype=sync_dtype, impl=impl)
        self._state = self._init_state()
        self._key = jax.random.PRNGKey(seed)
        self._queue: "deque[Tuple[_SlabReq, np.ndarray, np.ndarray]]" = \
            deque()
        self._slot_req: List[Optional[_SlabReq]] = [None] * self.slots
        self._free: "deque[int]" = deque(range(self.slots))
        self._done: List[ServeResult] = []
        # steps in flight on the device, harvested lazily: deeper windows
        # pipeline better but delay retire->refill by up to that many steps
        self._pipeline = max(0, int(pipeline))
        self._pending: "deque[_StepOut]" = deque()
        self._next_id = 0
        self._steps = 0
        self._occ_sum = 0
        self._served = 0
        self._cache_served = 0
        self._warm_served = 0
        self._cold_served = 0
        self._iters_sum = 0
        self._warm_iters = 0
        self._cold_iters = 0
        self._billed_bytes = 0.0
        self._latencies: "deque[float]" = deque(maxlen=LATENCY_WINDOW)
        self._oov_tokens = 0.0
        self._total_tokens = 0.0
        self._t_first: Optional[float] = None
        self._t_last_done: Optional[float] = None
        self._rates: Optional[Tuple[float, float]] = None
        self.admission_slo_s = (float(admission_slo_s)
                                if admission_slo_s is not None else None)
        self._shed_count = 0
        self._quarantined = 0
        self._step_ema_s: Optional[float] = None
        self.warmup_s = 0.0
        self._warm_flag = bool(warmup)
        if warmup:
            self._warmup()

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, cfg: Optional[LDAConfig] = None,
                        step: Optional[int] = None, sharding=None,
                        **kw) -> "SlabEngine":
        """Checkpoint-to-serve for the slab runtime (same contract as
        `FoldInEngine.from_checkpoint`)."""
        phi_acc, cfg, kw = _load_serving_checkpoint(ckpt_dir, cfg, step,
                                                    sharding, kw)
        return cls(phi_acc, cfg, **kw)

    # ---------------------------------------------------------- admission

    def _admit_doc(self, doc: Tuple[np.ndarray, np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray, float]:
        """Translate external ids to live phi rows (OOV -> guard row,
        never an exception — DESIGN.md §12) and feed the OOV trigger."""
        ids, counts = doc
        ids = np.asarray(ids)
        counts = np.asarray(counts, np.float32)
        if self._vocab is not None:
            rows = np.asarray(self._vocab.rows(
                ids.tolist(), admit=False, oov_row=self._oov_row),
                np.int32)
        else:
            rows = np.where((ids >= 0) & (ids < self.live_words),
                            ids, self._oov_row).astype(np.int32)
        oov_mask = rows == self._oov_row
        oov = float(counts[oov_mask].sum())
        self._oov_tokens += oov
        self._total_tokens += float(counts.sum())
        if self.trigger is not None:
            self.trigger.observe(ids[oov_mask].tolist(), counts[oov_mask],
                                 float(counts.sum()))
        return rows, counts, oov

    def submit(self, doc: Tuple[np.ndarray, np.ndarray],
               req_id: Optional[int] = None,
               tenant: Optional[Hashable] = None) -> "int | Shed":
        """Admit one document; never blocks on device work.  A theta-cache
        hit in ``serve`` mode completes immediately (harvest via
        ``poll``/``drain``); otherwise the request queues for the next
        free slot.  With ``admission_slo_s`` set, a request whose
        drain-model wait estimate exceeds the SLO is refused with a
        typed ``Shed`` instead of queueing (DESIGN.md §17); a document
        with non-finite counts retires immediately with
        ``error='nonfinite_input'`` instead of poisoning the slab."""
        with obs.span("slab.submit"):
            return self._submit(doc, req_id, tenant)

    def _submit(self, doc, req_id, tenant) -> "int | Shed":
        t_ns = time.perf_counter_ns()
        if req_id is None:
            req_id = self._next_id
        self._next_id = max(self._next_id, req_id) + 1
        now = time.time()
        if self._t_first is None:
            self._t_first = now
        if not np.isfinite(np.asarray(doc[1], np.float32)).all():
            # poisoned payload: quarantine at admission — flat-prior theta
            # with an error flag, never a slab crash, never cached
            self._quarantined += 1
            t_done = time.time()
            lat = t_done - now
            self._done.append(ServeResult(
                req_id=req_id,
                theta=np.full((self._K,), 1.0 / self._K, np.float32),
                latency_s=lat, bucket=-1, iters=0, mean_r=0.0,
                oov_tokens=0.0, phi_version=self.phi_version,
                comm_bytes=0.0, cached=False, tenant=tenant,
                error="nonfinite_input"))
            self._latencies.append(lat)
            self._served += 1
            self._t_last_done = t_done
            return req_id
        # digest hashes the RAW payload, before vocab translation: repeat
        # content collides whatever rows this generation maps it to
        digest = (doc_digest(doc[0], doc[1])
                  if self.cache is not None else None)
        rows, counts, oov = self._admit_doc(doc)
        req = _SlabReq(req_id=req_id, t_submit=now, oov=oov,
                       tenant=tenant, digest=digest)
        if self.cache is not None:
            hit = self.cache.get(tenant, digest, self.phi_version)
            if hit is not None:
                if self.cache_mode == "serve":
                    t_done = time.time()
                    lat = t_done - now
                    self._done.append(ServeResult(
                        req_id=req_id, theta=np.asarray(hit),
                        latency_s=lat, bucket=-1, iters=0, mean_r=0.0,
                        oov_tokens=oov, phi_version=self.phi_version,
                        comm_bytes=0.0, cached=True, tenant=tenant))
                    self._latencies.append(lat)
                    self._served += 1
                    self._cache_served += 1
                    self._t_last_done = t_done
                    return req_id
                req.warm = np.asarray(hit, np.float32)
        if self.admission_slo_s is not None:
            est = self._est_wait_s()
            if est > self.admission_slo_s:
                self._shed_count += 1
                return Shed(req_id=req_id, est_wait_s=est,
                            slo_s=self.admission_slo_s,
                            queue_depth=len(self._queue), tenant=tenant)
        self._queue.append((req, rows, counts))
        obs.stamp("submit", req_id, t_ns)
        return req_id

    def _est_wait_s(self) -> float:
        """Drain-model wait estimate for a request queued NOW: queue-ahead
        dispatch delay plus one slot tenure, priced at the measured step
        EMA.  Dispatch rate per step is bounded by both the refill lanes
        and the steady-state slot turnover (slots freed per step at mean
        tenure).  Cold engine (no step yet) estimates 0 — always admit."""
        if self._step_ema_s is None:
            return 0.0
        tenure = max(1.0, self.fold_iters / self.sweeps_per_step)
        rate = max(1e-9, min(float(self._refill_cap), self.slots / tenure))
        return self._step_ema_s * (len(self._queue) / rate + tenure)

    # ------------------------------------------------------------ iterate

    def live_slots(self) -> int:
        return self.slots - len(self._free)

    def in_flight(self) -> int:
        """Requests admitted but not yet retired (queued + in a slot)."""
        return len(self._queue) + self.live_slots()

    def step(self) -> int:
        """One slab step: refill free slots from the queue, dispatch the
        jitted advance (``sweeps_per_step`` sweeps over every live slot),
        and harvest whatever earlier steps have finished.  The dispatch
        never blocks — retirement masks are read back lazily through a
        bounded pipeline window, so consecutive steps chain on the device
        while the host runs ahead.  Returns how many documents were
        harvested (possibly from earlier steps)."""
        t0 = time.time()
        with obs.span("slab.refill"):
            n_take = min(len(self._queue), len(self._free), self._refill_cap)
            take = [self._queue.popleft() for _ in range(n_take)]
            slot_ids = [self._free.popleft() for _ in range(n_take)]
            wid, cnt, slot, _ = slab_refill(
                [(rows, counts) for _, rows, counts in take], slot_ids,
                capacity=self._refill_cap, slot_len=self.slot_len,
                pad_slot=self.slots)
            warm = np.zeros((self._refill_cap, self._K), np.float32)
            wmask = np.zeros((self._refill_cap,), bool)
            for i, (req, _, _) in enumerate(take):
                if req.warm is not None:
                    warm[i] = req.warm
                    wmask[i] = True
            for s, (req, _, _) in zip(slot_ids, take):
                self._slot_req[s] = req
            self._occ_sum += self.live_slots()
        with obs.span("slab.dispatch") as sp:
            self._key, sub = jax.random.split(self._key)
            self._state, retired, theta_out, iters, r_doc = self._step(
                self._phi, self._state, wid, cnt, slot, warm, wmask, sub)
        for req, _, _ in take:
            obs.stamp("refill", req.req_id, sp.t0_ns)
        self._steps += 1
        self._pending.append(_StepOut(retired, theta_out, iters, r_doc,
                                      self.phi_version, sp.t0_ns))
        n = self._harvest(block=len(self._pending) > self._pipeline)
        dt = time.time() - t0
        self._step_ema_s = (dt if self._step_ema_s is None
                            else 0.8 * self._step_ema_s + 0.2 * dt)
        return n

    def _harvest(self, block: bool = False) -> int:
        """Materialize finished steps off the pipeline head.  ``block``
        forces the oldest step to completion (used when the window fills
        or on drain); otherwise only steps whose retirement mask is
        already on host are consumed."""
        n = 0
        while self._pending:
            head = self._pending[0]
            if not block and not head.retired.is_ready():
                break
            self._pending.popleft()
            n += self._materialize(head)
            block = False            # only the first is forced
        return n

    def _materialize(self, out: _StepOut) -> int:
        with obs.span("slab.harvest.block"):
            ret = np.asarray(out.retired)    # the (only) host sync point
        if not ret.any():
            return 0
        with obs.span("slab.harvest.fetch"):
            th = np.asarray(out.theta)
            itn = np.asarray(out.iters)
            rn = np.asarray(out.r_doc)
        t_done = time.time()
        t_done_ns = time.perf_counter_ns()
        with obs.span("slab.harvest.retire"):
            return self._retire(out, ret, th, itn, rn, t_done, t_done_ns)

    def _retire(self, out: _StepOut, ret, th, itn, rn, t_done: float,
                t_done_ns: int) -> int:
        """Hand each document the step retired its theta, free its slot,
        and bill and count it."""
        sweep_b, once_b = self._billing_rates()
        n = 0
        for s in np.nonzero(ret)[0]:
            s = int(s)
            req = self._slot_req[s]
            if req is None:     # retired in an older pipelined step and
                continue        # already harvested from it
            self._slot_req[s] = None
            self._free.append(s)
            doc_iters = int(itn[s])
            bytes_d = sweep_b * doc_iters + once_b
            lat = t_done - req.t_submit
            theta_d = th[s]
            # NaN/Inf quarantine: one poisoned document retires with an
            # error flag (and never enters the cache) instead of crashing
            # the slab or serving garbage to a repeat request (§17)
            finite = bool(np.isfinite(theta_d).all())
            if not finite:
                self._quarantined += 1
            if (self.cache is not None and req.digest is not None
                    and finite):
                self.cache.put(req.tenant, req.digest, out.phi_version,
                               theta_d)
            self._done.append(ServeResult(
                req_id=req.req_id, theta=theta_d, latency_s=lat,
                bucket=s, iters=doc_iters, mean_r=float(rn[s]),
                oov_tokens=req.oov, phi_version=out.phi_version,
                comm_bytes=bytes_d, cached=False, tenant=req.tenant,
                error=None if finite else "nonfinite_theta"))
            obs.stamp("retire_dispatch", req.req_id, out.t_dispatch_ns)
            obs.stamp("done", req.req_id, t_done_ns)
            self._latencies.append(lat)
            self._iters_sum += doc_iters
            if req.warm is not None:
                self._warm_iters += doc_iters
                self._warm_served += 1
            else:
                self._cold_iters += doc_iters
                self._cold_served += 1
            self._billed_bytes += bytes_d
            self._served += 1
            n += 1
        self._t_last_done = t_done
        return n

    def pump(self, max_steps: Optional[int] = None) -> int:
        """Step until the queue, slab and pipeline are all empty (or
        ``max_steps``).  ``fold_iters`` bounds every slot's tenure, so
        this terminates.  Returns the number of steps run."""
        steps = 0
        while max_steps is None or steps < max_steps:
            if self._queue or self.live_slots():
                self.step()
                steps += 1
            elif self._pending:
                self._harvest(block=True)
            else:
                break
        return steps

    # ------------------------------------------------------------ harvest

    def poll(self) -> List[ServeResult]:
        """Pop every result harvested so far (cache hits and retirements);
        never blocks, never steps."""
        out, self._done = self._done, []
        return out

    def drain(self) -> List[ServeResult]:
        """Pump the slab to empty and return every outstanding result."""
        self.pump()
        return self.poll()

    # ----------------------------------------------------- lifecycle swap

    def swap_phi(self, phi_acc, *, live_words: Optional[int] = None,
                 vocab=None, phi_version: Optional[int] = None) -> None:
        """Install a new (phi statistic, vocab table) generation.  The
        slab is pumped to empty FIRST: everything already admitted was
        row-translated under the old vocabulary, so it retires under the
        old (phi, version) and only post-swap submissions see the new
        generation — torn-phi-proof by construction (DESIGN.md §16)."""
        self.pump()
        phi_norm, live, self.w_cap = _prepare_phi(phi_acc, self.cfg,
                                                  live_words, False)
        recompiled = int(phi_norm.shape[0]) != self._cfg.vocab_size
        self._cfg = dataclasses.replace(self._cfg,
                                        vocab_size=int(phi_norm.shape[0]))
        self.live_words = live
        self._oov_row = live
        if vocab is not None:
            self._vocab = vocab
        self._phi = infer.split_topic_shards(phi_norm, self._topic_shards)
        self.phi_version = (int(phi_version) if phi_version is not None
                            else self.phi_version + 1)
        # phi is a step ARGUMENT: a capacity change re-specializes the jit
        # on the next call — warm the new shape eagerly off the request path
        if recompiled and self._warm_flag:
            self._warmup()

    # ----------------------------------------------------- serve -> train

    def take_retrain_batches(self) -> List[list]:
        """Pop pending hot-OOV admission batches from the trigger (empty
        when no trigger is attached or the rate stayed under threshold)."""
        return self.trigger.take() if self.trigger is not None else []

    # -------------------------------------------------------------- stats

    def _warmup(self) -> None:
        """Compile the (single) step shape before any request arrives: an
        all-empty refill advances an empty slab — semantically a no-op."""
        t0 = time.time()
        R = self._refill_cap
        self._state, retired, *_ = self._step(
            self._phi, self._state,
            np.zeros((R, self.slot_len), np.int32),
            np.zeros((R, self.slot_len), np.float32),
            np.full((R,), self.slots, np.int32),
            np.zeros((R, self._K), np.float32),
            np.zeros((R,), bool), jax.random.PRNGKey(0))
        jax.block_until_ready(retired)
        self.warmup_s = time.time() - t0

    def _billing_rates(self) -> Tuple[float, float]:
        """(bytes per slot-sweep, bytes per document) attribution rates
        from the metered step trace.  Loop-phase bytes split evenly over
        the ``sweeps_per_step`` sweeps and ``slots`` lanes of one step; a
        document's bill is its OWN iteration count times that rate, plus
        its share of the once-per-document phases (init over the refill
        lanes, theta renorm over the slots).  Zero (local reducer) when
        phi is unsharded."""
        if self._rates is None:
            by = self.meter.bytes_by_phase
            loop = (by.get("slab_norm_loop", 0.0)
                    + by.get("slab_rw_loop", 0.0))
            once = (by.get("slab_init_norm", 0.0)
                    / max(self._refill_cap, 1)
                    + by.get("slab_theta_norm", 0.0) / self.slots)
            self._rates = (loop / self.sweeps_per_step / self.slots, once)
        return self._rates

    def _compiles(self) -> int:
        return int(self._step._cache_size())

    def stats(self) -> Dict[str, object]:
        """Serving scorecard (superset of the bucket engine's): goodput,
        latency percentiles, the ONE-compile bound, slab occupancy, warm
        vs cold sweep counts, cache and retraining-trigger state."""
        lats = np.asarray(self._latencies, np.float64)
        span = ((self._t_last_done - self._t_first)
                if self._latencies and self._t_first is not None else 0.0)
        folded = self._cold_served + self._warm_served
        out: Dict[str, object] = {
            "served": self._served,
            "steps": self._steps,
            "docs_per_s": self._served / span if span > 0 else float("nan"),
            "latency_p50_s": float(np.percentile(lats, 50)) if lats.size
            else float("nan"),
            "latency_p99_s": float(np.percentile(lats, 99)) if lats.size
            else float("nan"),
            "mean_fold_iters": (self._iters_sum / folded if folded
                                else 0.0),
            "cold_fold_iters": (self._cold_iters / self._cold_served
                                if self._cold_served else 0.0),
            "warm_fold_iters": (self._warm_iters / self._warm_served
                                if self._warm_served else 0.0),
            "compiles": self._compiles(),
            "slots": self.slots,
            "slot_len": self.slot_len,
            "sweeps_per_step": self.sweeps_per_step,
            # mean fraction of slots doing useful work per step — the
            # slab's analogue of padded-lane efficiency
            "slot_occupancy": (self._occ_sum / self._steps / self.slots
                               if self._steps else 0.0),
            "warmup_s": self.warmup_s,
            "bytes_by_phase": dict(self.meter.bytes_by_phase),
            "per_request_bytes": (self._billed_bytes / folded if folded
                                  else 0.0),
            "live_words": self.live_words,
            "w_cap": self.w_cap,
            "occupancy": self.live_words / max(self.w_cap, 1),
            "phi_version": self.phi_version,
            "oov_rate": (self._oov_tokens / self._total_tokens
                         if self._total_tokens else 0.0),
            "cache_served": self._cache_served,
            "warm_starts": self._warm_served,
            "retrain_batches": (self.trigger.emitted if self.trigger
                                else 0),
            # graceful-degradation counters (§17): sheds are refused at
            # submit and never enter served/latency stats
            "shed": self._shed_count,
            "shed_frac": (self._shed_count
                          / max(1, self._shed_count + self._served
                                + self.in_flight())),
            "quarantined": self._quarantined,
            "admission_slo_s": self.admission_slo_s,
            "step_ema_s": (self._step_ema_s if self._step_ema_s is not None
                           else 0.0),
        }
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out
