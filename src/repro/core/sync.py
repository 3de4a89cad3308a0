"""Synchronization layer: dense (Eq. 4 — the PGS/MPA baseline) and
power-selected sparse (Eq. 6 — the paper's contribution) all-reduces,
with trace-time byte accounting.

The `Reducer` abstraction lets the same POBP code run
  - under ``shard_map`` on a real mesh (``MeshReducer`` -> lax.psum), and
  - in single-device N-shard simulation (``SimReducer`` -> sum over a
    stacked axis), used by CPU tests and paper-figure benchmarks.

Byte accounting happens at *trace time*: payload shapes are static, so each
``psum`` registers its logical payload (size x itemsize) in a phase bucket.
Recording is **idempotent under retracing**: a reshape-triggered retrace of
the same program (e.g. a variable-length mini-batch stream hitting a new
padded shape) must not inflate the totals, so every record is attributed to
the trace it happens under and two traces whose record sequences are
identical count once (see ``CommMeter``).  Per-mini-batch totals are then
``dense_bytes + (iters-1) * sparse_bytes`` with `iters` known only at run
time (``CommMeter.per_minibatch_bytes``).  This reproduces Eqs. (5)/(6)
exactly and is cross-checked against HLO collective parsing in the
roofline pass.
"""

from __future__ import annotations

import weakref
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

AxisName = Union[str, Sequence[str]]

# phases recorded once per *inner-loop iteration* (their psums live in
# trace-once while bodies — core/pobp.py names every in-body psum with a
# distinct loop phase); everything else is a once-per-mini-batch payload.
_BASE_LOOP_PHASES = ("power", "dense_loop", "model_rw_loop", "model_norm_loop",
                     "topic_merge")
# the parameter-server reducer splits every vocabulary-proportional wire
# payload into a ``.push`` and a ``.pull`` leg (see ``PSReducer``); the
# loop-phase set covers both so ``per_minibatch_bytes`` stays correct
# under either reducer.
LOOP_PHASES = _BASE_LOOP_PHASES + tuple(
    f"{p}{leg}" for p in _BASE_LOOP_PHASES for leg in (".push", ".pull"))


class CommMeter:
    """Trace-time logical-byte counter, bucketed by phase label.

    Each ``record`` is keyed to the jax trace it happens under: retracing —
    a new padded shape on a variable-length stream, a fresh ``vmap``
    application — creates new trace objects, so each traced program section
    yields its own ordered log of (phase, shape, dtype) records.  Logs then
    merge into per-phase totals as follows:

      - identical logs count ONCE (a plain retrace of the same section must
        not double-count — the bug this class replaces);
      - logs with the same *phase sequence* but different payload shapes
        are shape-bucket variants of one section (e.g. the L-dependent
        ``model_norm`` psum across length buckets): the per-phase MAX is
        taken — what the worst single mini-batch pays — never the sum;
      - distinct phase sequences are genuinely different program sections
        (dense body vs power loop, another sync mode) and add up.

    Records from eager (untraced) psums accumulate per call, since each one
    is a real execution.  Traces are held only by weakref, so the meter
    neither extends trace lifetimes nor trips jax's tracer-leak checker;
    a log whose trace id gets reused by a later trace is frozen first.
    """

    def __init__(self) -> None:
        self.calls: List[str] = []                 # every record ever (debug)
        self._archived: List[Tuple[Tuple, ...]] = []   # frozen trace logs
        # live trace id -> [weakref-to-trace (or the trace itself when it
        # rejects weakrefs), ordered (phase, shape, dtype, nbytes, w_rows)
        # records]
        self._live: Dict[int, list] = {}
        self._eager: List[Tuple] = []

    def record(self, phase: str, arr: jnp.ndarray,
               w_rows: Optional[int] = None) -> None:
        """Register one psum payload.

        ``w_rows`` marks a payload whose size is proportional to the
        vocabulary capacity: it is recorded at the full W_cap = ``w_rows``
        shape (what the compiled program allocates), but only the live
        fraction logically crosses the interconnect — guard rows are
        identically zero on every shard, so a deployment transmits
        ``live_w`` rows (DESIGN.md §12).  ``bytes_by_phase_at(live_w)``
        scales marked records by ``live_w / w_rows``.
        """
        nbytes = int(arr.size) * arr.dtype.itemsize
        sig = (phase, tuple(arr.shape), str(arr.dtype), nbytes,
               int(w_rows) if w_rows else 0)
        self.calls.append(f"{phase}:{tuple(arr.shape)}:{arr.dtype}:{nbytes}")
        trace = getattr(arr, "_trace", None)
        if trace is None:
            self._eager.append(sig)
            return
        tid = id(trace)
        entry = self._live.get(tid)
        if entry is not None:
            ref, log = entry
            cur = ref() if isinstance(ref, weakref.ref) else ref
            if cur is not trace:           # id reused by a newer trace
                self._archived.append(tuple(log))
                entry = None
        if entry is None:
            try:
                ref = weakref.ref(trace)
            except TypeError:
                ref = trace
            entry = [ref, []]
            self._live[tid] = entry
        entry[1].append(sig)

    def record_host(self, phase: str, nbytes: int,
                    w_rows: int = 0) -> None:
        """Register host-side wire traffic that never flows through a
        traced psum — parameter-server retry re-issues and
        crash-recovery replays (DESIGN.md §17).  Accumulates per call
        (eager path), under its own phase (``ps.retry.push``,
        ``ps.retry.pull``, ``ps.replay``) so clean-run Eq. 5/6 phases
        stay untouched and the overhead is separately auditable."""
        nbytes = int(nbytes)
        sig = (phase, (), "host", nbytes, int(w_rows))
        self.calls.append(f"{phase}:host:{nbytes}")
        self._eager.append(sig)

    def _logs(self) -> List[Tuple[Tuple, ...]]:
        return self._archived + [tuple(log) for _, log in self._live.values()]

    def _merged(self, live_w: Optional[int] = None) -> Dict[str, int]:
        # group deduplicated logs by phase sequence; max-merge within a
        # group (shape-bucket variants), sum across groups and eager records

        def scaled(nbytes: int, w_rows: int) -> int:
            if live_w is None or not w_rows:
                return nbytes
            return int(nbytes * min(int(live_w), w_rows) // w_rows)

        groups: Dict[Tuple[str, ...], Dict[str, int]] = {}
        for log in set(self._logs()):
            per: Dict[str, int] = {}
            for phase, _, _, nbytes, w_rows in log:
                per[phase] = per.get(phase, 0) + scaled(nbytes, w_rows)
            g = groups.setdefault(tuple(s[0] for s in log), {})
            for phase, nbytes in per.items():
                g[phase] = max(g.get(phase, 0), nbytes)
        out: Dict[str, int] = {}
        for phase, _, _, nbytes, w_rows in self._eager:
            out[phase] = out.get(phase, 0) + scaled(nbytes, w_rows)
        for g in groups.values():
            for phase, nbytes in g.items():
                out[phase] = out.get(phase, 0) + nbytes
        return out

    @property
    def bytes_by_phase(self) -> Dict[str, int]:
        return self._merged()

    def bytes_by_phase_at(self, live_w: int) -> Dict[str, int]:
        """Per-phase bytes with W-proportional payloads (``record``'s
        ``w_rows`` mark) scaled to the live vocabulary — the honest
        Eq. 5/6 accounting of a capacity-laddered run: guard rows are
        structurally zero, so they never cross the interconnect."""
        return self._merged(live_w)

    def phase_bytes(self, phase: str) -> int:
        return self.bytes_by_phase.get(phase, 0)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_phase.values())

    def per_minibatch_bytes(self, iters,
                            loop_phases: Sequence[str] = LOOP_PHASES,
                            live_w: Optional[int] = None) -> int:
        """The documented ``dense + (iters-1) * sparse`` mini-batch total.

        `loop_phases` payloads cross the interconnect once per inner
        iteration (their psums live in a trace-once while body); every
        other phase is paid once per mini-batch.  `iters` includes the
        first dense iteration, mirroring ``MinibatchResult.iters``.
        `live_w` scales W-proportional payloads to the live vocabulary
        (capacity-laddered runs; see ``bytes_by_phase_at``).
        """
        by = self._merged(live_w)
        once = sum(v for p, v in by.items() if p not in loop_phases)
        loop = sum(v for p, v in by.items() if p in loop_phases)
        return int(once + max(int(iters) - 1, 0) * loop)

    def reset(self) -> None:
        self.calls.clear()
        self._archived.clear()
        self._live.clear()
        self._eager.clear()


class Reducer:
    """All-reduce provider; subclasses define where the sum happens."""

    def __init__(self, meter: Optional[CommMeter] = None, sync_dtype=jnp.float32):
        self.meter = meter or CommMeter()
        self.sync_dtype = sync_dtype

    def _sum(self, x: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def psum(self, x: jnp.ndarray, phase: str, compress: bool = True,
             w_rows: Optional[int] = None, dtype=None) -> jnp.ndarray:
        """All-reduce `x`; payload cast to sync_dtype when `compress`.

        ``w_rows`` marks a vocabulary-proportional payload (recorded at
        capacity, billed at live W by ``CommMeter.bytes_by_phase_at``).
        ``dtype`` overrides the payload dtype for this call (compressed
        phi-statistic runs ship their deltas at phi_acc_dtype width —
        the meter bills the cast payload, so bytes halve for real)."""
        orig = x.dtype
        wire = dtype if dtype is not None else self.sync_dtype
        if compress and x.dtype != wire:
            x = x.astype(wire)
        self.meter.record(phase, x, w_rows=w_rows)
        out = self._sum(x)
        return out.astype(orig)

    def shard_index(self) -> jnp.ndarray:
        """This shard's position along the reduced axis (0 unsharded)."""
        return jnp.zeros((), jnp.int32)

    def _gather(self, x: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def all_gather(self, x: jnp.ndarray, phase: str) -> jnp.ndarray:
        """Every shard's `x`, stacked in shard order along a new leading
        axis; billed at the gathered size (what each shard receives)."""
        out = self._gather(x)
        self.meter.record(phase, out)
        return out

    def bill(self, x: jnp.ndarray, phase: str,
             w_rows: Optional[int] = None) -> jnp.ndarray:
        """Record a *local* full-statistic touch without reducing.

        The RM decay step (DESIGN.md §14) rescales every shard's resident
        phi-accumulator slice in place — no payload crosses the
        interconnect, but the [W, K] statistic read-modify-write is real
        memory traffic the cost model must see.  Billed once per
        mini-batch (the ``decay`` phase is not in ``LOOP_PHASES``),
        scaled to live W like any vocabulary-proportional record."""
        self.meter.record(phase, x, w_rows=w_rows)
        return x


class MeshReducer(Reducer):
    """psum over named mesh axes — for shard_map'd POBP."""

    def __init__(self, axis_name: AxisName, **kw):
        super().__init__(**kw)
        self.axis_name = axis_name

    def _sum(self, x):
        return jax.lax.psum(x, self.axis_name)

    def _gather(self, x):
        return jax.lax.all_gather(x, self.axis_name)

    def shard_index(self):
        return jax.lax.axis_index(self.axis_name)


class SimReducer(Reducer):
    """Per-shard values carry a leading N axis; 'all-reduce' = sum + broadcast.

    Used by the single-device simulation path (tests, CPU benchmarks); the
    byte meter still records exactly what one shard would send.
    """

    def _sum(self, x):
        return jnp.broadcast_to(jnp.sum(x, axis=0, keepdims=True), x.shape)


class LocalReducer(Reducer):
    """N=1 degenerate reducer (OBP on a single processor) — no communication,
    so nothing is recorded in the meter.  The sync_dtype cast round-trip is
    still applied under `compress`, so an N=1 run is numerically identical
    to an N-shard run with the same sync_dtype (the payload precision is a
    property of the algorithm configuration, not of the shard count)."""

    def psum(self, x, phase: str, compress: bool = True,
             w_rows: Optional[int] = None, dtype=None):
        wire = dtype if dtype is not None else self.sync_dtype
        if compress and x.dtype != wire:
            return x.astype(wire).astype(x.dtype)
        return x

    def all_gather(self, x, phase: str):
        return x[None]

    def _sum(self, x):
        return x


class PSReducer(Reducer):
    """Parameter-server billing peer of ``MeshReducer``/``LocalReducer``.

    Under the pull-based PS architecture (DESIGN.md §15,
    ``dist/paramserver.py``) the in-step math is unchanged — the shard
    body still reduces the same payloads, so ``PSReducer`` delegates the
    actual sum to a wrapped inner reducer and the training trajectory at
    staleness 0 matches the allreduce backend.  What changes is the wire
    model:

      - every vocabulary-proportional payload (``w_rows``-marked) crosses
        the interconnect TWICE — once as a touched-row delta *push* to
        the owning server shards and once as a touched-row slice *pull*
        for the next mini-batch — so it is billed as two phases,
        ``{phase}.push`` and ``{phase}.pull``, both ``w_rows``-marked so
        ``bytes_by_phase_at(live_w)`` scales each leg down to the rows
        that actually travel (pass the measured mean touched-row count as
        ``live_w`` for touched-granularity billing);
      - payloads that are NOT vocabulary rows (per-topic scalars, r_k)
        never live on the row-sharded servers: with a single worker
        (``LocalReducer`` inner) they need no communication at all and
        are not billed; with several workers they still need a worker
        all-reduce and are billed unchanged.

    The host-side transport (``dist.paramserver.SimTransport``) counts
    the *measured* wire truth; this reducer is the trace-time model the
    bench cross-checks it against.
    """

    def __init__(self, inner: Reducer, **kw):
        kw.setdefault("meter", inner.meter)
        kw.setdefault("sync_dtype", inner.sync_dtype)
        super().__init__(**kw)
        self.inner = inner

    def psum(self, x: jnp.ndarray, phase: str, compress: bool = True,
             w_rows: Optional[int] = None, dtype=None) -> jnp.ndarray:
        orig = x.dtype
        wire = dtype if dtype is not None else self.sync_dtype
        if compress and x.dtype != wire:
            x = x.astype(wire)
        if w_rows:
            self.meter.record(f"{phase}.push", x, w_rows=w_rows)
            self.meter.record(f"{phase}.pull", x, w_rows=w_rows)
        elif not isinstance(self.inner, LocalReducer):
            self.meter.record(phase, x)
        out = self.inner._sum(x)
        return out.astype(orig)

    def bill(self, x: jnp.ndarray, phase: str,
             w_rows: Optional[int] = None) -> jnp.ndarray:
        # local statistic touches (decay) are identical under PS
        self.meter.record(phase, x, w_rows=w_rows)
        return x

    def _sum(self, x):
        return self.inner._sum(x)

    def shard_index(self):
        return self.inner.shard_index()


def dense_sync_bytes(W: int, K: int, itemsize: int = 4) -> int:
    """Eq. (5) per-iteration payload of the MPA baseline: the full phi matrix.

    ``W`` is the LIVE vocabulary: on a capacity-laddered run the guard
    rows above live W are identically zero on every shard and never need
    to travel (DESIGN.md §12) — pass live W here, not the rung capacity.
    """
    return W * K * itemsize


def power_sync_bytes(P: int, Pk: int, W: int, itemsize: int = 4,
                     rw_itemsize: int = 4) -> int:
    """Eq. (6) per-iteration payload of POBP: packed phi + packed r at
    `itemsize` (the sync_dtype width) plus the [W] word-residual vector at
    `rw_itemsize`.

    `rw_itemsize` defaults to 4 because ``core/pobp.py`` syncs residuals
    with ``compress=False`` — those psums always travel at float32 width
    regardless of sync_dtype.  Pass ``rw_itemsize=itemsize`` only for a
    deployment that compresses the r_w sync too.

    ``W`` (and a ``P`` derived from it) is the LIVE vocabulary on a
    capacity-laddered run — guard rows carry zero residual and zero
    packed mass, so the honest Eq. 6 payload scales with live W, not
    with the rung capacity (DESIGN.md §12).
    """
    return 2 * P * Pk * itemsize + W * rw_itemsize


def touched_power_sync_bytes(P: int, Pk: int, touched_w: int,
                             itemsize: int = 4,
                             rw_itemsize: int = 4) -> int:
    """Touched-W refinement of Eq. (6): the per-iteration payload when a
    worker exchanges only the rows its current mini-batch touched
    (DESIGN.md §15 — the parameter-server wire model).

    The packed submatrix can cover at most ``min(P, touched_w)`` rows —
    power-selected rows the batch never touched carry no delta and need
    no pull — and the word-residual leg shrinks from the full [W] vector
    to the touched rows.  With the corpus-wide touched fraction ``f``
    this is ~``f`` × the allreduce payload, which is where the PS mode's
    measured-bytes win comes from (BENCH_comm gates the measured wire
    against exactly this model).
    """
    Pt = min(P, touched_w)
    return 2 * Pt * Pk * itemsize + touched_w * rw_itemsize
