"""Pull-based power-slice parameter server (DESIGN.md §15).

The allreduce backends ship the full packed ``[P, Pk]`` + ``[W]`` r_w
payload on every Eq. 6 sync and the full ``[W, K]`` statistic on every
dense sync — each worker pays for vocabulary it never touched in that
mini-batch.  This module is the peer architecture the paper's
communication claim actually describes: **server shards own contiguous
phi row ranges**, and a worker

  (a) *pushes* sparse packed deltas only for the rows its current
      mini-batch touched,
  (b) *pulls* only the row slices its NEXT mini-batch needs, prefetched
      one segment ahead so the pull overlaps the sweep, and
  (c) tolerates a configurable bounded staleness ``S`` — a pull for
      batch ``m`` may be served from a server snapshot missing at most
      the last ``S`` committed pushes.  ``S = 0`` is the barriered mode:
      every pull reflects every prior push, so the training trajectory
      matches the allreduce backend (pinned ≤ 1e-6 in BENCH_comm and
      tests/test_paramserver.py).

Layering:

  - ``RowShards``      pure metadata: contiguous row ranges per server.
  - ``ParamServer``    the authoritative row-sharded [W, K] statistic
                       (host numpy; per-shard locks; a committed-version
                       counter + condition variable gives the staleness
                       bound its teeth).
  - ``Transport``      ABC between ONE worker and the server shards.
                       ``SimTransport`` is the in-process/threaded
                       backend (optional per-op link latency so prefetch
                       overlap is measurable) with per-link byte
                       counters — the *measured* wire truth BENCH_comm
                       gates on.  ``JaxDistributedTransport`` is the
                       multi-host slot: it validates the environment and
                       raises until the jax.distributed backend lands
                       (ROADMAP backlog head).
  - ``PSClient``       worker-side replica manager: keeps the full
                       [W, K] device replica the unchanged POBP shard
                       body consumes, refreshing touched rows from pulls
                       and emitting touched-row delta pushes.

The worker's replica is exact at S=0 and stale-bounded at S>0: a pull
may overwrite local rows with a snapshot missing ≤ S of the worker's own
recent pushes — those deltas are never lost (the server holds them);
they reappear in the next pull that covers the row.  This is classic
stale-synchronous-parallel semantics (Petterson & Caetano's async LDA is
the ancestry; see PAPERS.md).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

_ROW_ID_BYTES = 4      # int32 row ids accompany every pushed/pulled slice


class TransportError(RuntimeError):
    """Base class for retryable transport-layer failures: the op did not
    take effect (or its effect is unknown) and may be safely re-issued —
    pushes are idempotent under the per-client sequence-number protocol
    (DESIGN.md §17)."""


class ServerUnavailableError(TransportError):
    """An op addressed a server shard that is currently down."""

    def __init__(self, server: int, detail: str = ""):
        self.server = int(server)
        super().__init__(f"server shard {server} is down"
                         + (f": {detail}" if detail else ""))


# --------------------------------------------------------------------------
# row sharding metadata
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RowShards:
    """Contiguous-range row ownership: server ``s`` owns rows
    ``[ranges[s][0], ranges[s][1])``.  Ranges are balanced to within one
    row and cover ``[0, w_cap)`` exactly."""

    w_cap: int
    num_servers: int

    def __post_init__(self):
        if self.num_servers < 1 or self.w_cap < 1:
            raise ValueError(f"need w_cap >= 1, num_servers >= 1, got "
                             f"({self.w_cap}, {self.num_servers})")

    @property
    def ranges(self) -> List[Tuple[int, int]]:
        base, rem = divmod(self.w_cap, self.num_servers)
        out, lo = [], 0
        for s in range(self.num_servers):
            hi = lo + base + (1 if s < rem else 0)
            out.append((lo, hi))
            lo = hi
        return out

    def owner(self, row: int) -> int:
        for s, (lo, hi) in enumerate(self.ranges):
            if lo <= row < hi:
                return s
        raise ValueError(f"row {row} outside [0, {self.w_cap})")

    def split(self, rows: np.ndarray) -> Dict[int, np.ndarray]:
        """Partition sorted unique `rows` into per-server id arrays; only
        servers with at least one row appear (a touched-row push never
        wakes a shard it does not address)."""
        rows = np.asarray(rows, np.int64)
        out: Dict[int, np.ndarray] = {}
        for s, (lo, hi) in enumerate(self.ranges):
            sel = rows[(rows >= lo) & (rows < hi)]
            if sel.size:
                out[s] = sel
        return out


# --------------------------------------------------------------------------
# the authoritative server group
# --------------------------------------------------------------------------

class ParamServer:
    """Row-sharded owner of the accumulated [W, K] statistic.

    Pushes are *deltas* (commutative adds — multiple writers compose);
    a batch push spans several shards and becomes visible atomically
    through ``commit(version)``.  Pulls carry a ``min_version``: the
    caller blocks until at least that many batch pushes have committed —
    the server-side half of the bounded-staleness contract.

    Chaos hardening (DESIGN.md §17): pushes may carry a per-client
    monotonic ``(client_id, seq)`` tag — a shard applies each tag at most
    once per shard lifetime, so duplicated or replayed deliveries are
    idempotent.  ``crash(s)`` loses a shard's in-memory rows and dedup
    memory; ``restart(s)`` restores the rows from the last server-synced
    snapshot (``mark_synced()``, the checkpoint-fence handshake) and
    holds pulls from that shard until a client replays its retained
    post-fence deltas and calls ``mark_recovered(s)``.
    """

    def __init__(self, phi0: np.ndarray, num_servers: int = 1,
                 version: int = 0, pull_timeout: float = 60.0):
        phi0 = np.asarray(phi0, np.float32)
        self.shards = RowShards(phi0.shape[0], num_servers)
        self._phi = phi0.copy()
        self._locks = [threading.Lock() for _ in range(num_servers)]
        self._cv = threading.Condition()
        self._committed = int(version)
        self.pull_timeout = float(pull_timeout)
        # -- fault-tolerance state --
        self._down: set = set()           # crashed shard ids
        self._replaying: set = set()      # restarted, awaiting delta replay
        self._applied: List[Dict[str, set]] = [dict()
                                               for _ in range(num_servers)]
        # the last server-synced snapshot: stands in for the checkpoint
        # bytes the fence persisted — what a restarted shard reloads
        self._sync_phi = phi0.copy()
        self._sync_version = int(version)
        self.duplicates_dropped = 0
        self.recovery_log: List[Dict[str, Any]] = []

    @property
    def committed(self) -> int:
        with self._cv:
            return self._committed

    def apply_push(self, server: int, rows: np.ndarray,
                   deltas: np.ndarray, client_id: Optional[str] = None,
                   seq: Optional[int] = None, replay: bool = False) -> bool:
        """Apply a delta push to one shard; returns False when the
        ``(client_id, seq)`` tag was already applied (duplicate/replay).

        A shard awaiting replay accepts ONLY replay-tagged pushes: letting
        an in-flight retry land before the replayed backlog would re-sum
        the shard's rows in a different order (float addition is not
        associative) and break the S=0 bit-exactness pin.
        """
        with self._cv:
            if server in self._down:
                raise ServerUnavailableError(server, "push rejected")
            if server in self._replaying and not replay:
                raise ServerUnavailableError(
                    server, "shard replaying retained deltas; ordinary "
                            "pushes fenced until recovery")
        lo, hi = self.shards.ranges[server]
        rows = np.asarray(rows, np.int64)
        if rows.size and not ((rows >= lo) & (rows < hi)).all():
            raise ValueError(f"push to server {server} carries rows outside "
                             f"[{lo}, {hi})")
        with self._locks[server]:
            if client_id is not None and seq is not None:
                seen = self._applied[server].setdefault(client_id, set())
                if seq in seen:
                    self.duplicates_dropped += 1
                    return False
                seen.add(seq)
            np.add.at(self._phi, rows, np.asarray(deltas, np.float32))
        return True

    def commit(self, version: int) -> None:
        with self._cv:
            self._committed = max(self._committed, int(version))
            self._cv.notify_all()

    def serve_pull(self, server: int, rows: np.ndarray, min_version: int,
                   timeout: Optional[float] = None) -> Tuple[np.ndarray, int]:
        if timeout is None:
            timeout = self.pull_timeout
        lo, hi = self.shards.ranges[server]
        with self._cv:
            # ready, OR down (wake to fail fast so the client can back
            # off + recover instead of burning the whole timeout)
            ok = self._cv.wait_for(
                lambda: (server in self._down
                         or (self._committed >= min_version
                             and server not in self._replaying)),
                timeout=timeout)
            if server in self._down:
                raise ServerUnavailableError(server, "pull rejected")
            if not ok:
                raise TimeoutError(
                    f"pull from server shard {server} (rows [{lo}, {hi})) "
                    f"waited {timeout}s for committed version "
                    f">= {min_version} (at {self._committed}"
                    + (", shard awaiting delta replay"
                       if server in self._replaying else "")
                    + "); a push was lost or never committed")
            version = self._committed
        rows = np.asarray(rows, np.int64)
        if rows.size and not ((rows >= lo) & (rows < hi)).all():
            raise ValueError(f"pull from server {server} asks rows outside "
                             f"[{lo}, {hi})")
        with self._locks[server]:
            return self._phi[rows].copy(), version

    # ---- crash / recovery state machine (DESIGN.md §17) ----
    def is_up(self, server: int) -> bool:
        with self._cv:
            return server not in self._down

    def needs_replay(self) -> frozenset:
        with self._cv:
            return frozenset(self._replaying)

    def crash(self, server: int) -> None:
        """Lose a shard: its rows and its dedup memory are gone (the
        replica of a real process death).  In-flight ops observe
        ``ServerUnavailableError``."""
        lo, hi = self.shards.ranges[server]
        with self._locks[server]:
            with self._cv:
                self._down.add(server)
                self._cv.notify_all()
            self._phi[lo:hi] = 0.0
            self._applied[server] = dict()
        self.recovery_log.append({"event": "crash", "server": int(server)})

    def restart(self, server: int) -> None:
        """Bring a crashed shard back: rows reload from the last synced
        snapshot; the shard then refuses pulls until a client replays
        its retained post-fence deltas (``mark_recovered``)."""
        lo, hi = self.shards.ranges[server]
        with self._locks[server]:
            self._phi[lo:hi] = self._sync_phi[lo:hi]
            with self._cv:
                self._down.discard(server)
                self._replaying.add(server)
                self._cv.notify_all()
        self.recovery_log.append({"event": "restart", "server": int(server),
                                  "restored_version": self._sync_version})

    def mark_recovered(self, server: int) -> None:
        with self._cv:
            self._replaying.discard(server)
            self._cv.notify_all()
        self.recovery_log.append({"event": "recovered",
                                  "server": int(server)})

    def mark_synced(self) -> None:
        """Checkpoint-fence handshake: the current committed state is now
        durable — it becomes the restart-recovery base, and clients may
        trim their retained delta logs (``PSClient.mark_durable``)."""
        for lock in self._locks:
            lock.acquire()
        try:
            with self._cv:
                self._sync_version = self._committed
            self._sync_phi = self._phi.copy()
        finally:
            for lock in self._locks:
                lock.release()

    # ---- checkpoint handshake (DESIGN.md §15): the server copy is the
    # authoritative statistic a fence persists / a resume rehydrates.
    def snapshot(self) -> Tuple[np.ndarray, int]:
        with self._cv:
            version = self._committed
        for lock in self._locks:
            lock.acquire()
        try:
            return self._phi.copy(), version
        finally:
            for lock in self._locks:
                lock.release()

    def manifest(self) -> Dict[str, Any]:
        """JSON-able server-side state for the checkpoint manifest
        (``extra['ps']``); the phi payload itself rides the normal
        checkpoint tree."""
        return {"num_servers": self.shards.num_servers,
                "w_cap": self.shards.w_cap,
                "ranges": [list(r) for r in self.shards.ranges],
                "version": self.committed}


# --------------------------------------------------------------------------
# transports
# --------------------------------------------------------------------------

class Transport:
    """Worker <-> server-shard message layer.

    Implementations must be safe to call from one worker thread; pulls
    are asynchronous (a ``Future``) so the client can prefetch one
    segment ahead.  Byte counters are per link (= per server shard, per
    direction) and count the real encoded payload: row ids at int32 plus
    values at the wire dtype.
    """

    def __init__(self, num_servers: int):
        self.pushed_bytes = [0] * num_servers
        self.pulled_bytes = [0] * num_servers

    def push_batch(self, version: int, rows: np.ndarray,
                   deltas: np.ndarray, *, client_id: Optional[str] = None,
                   seq: Optional[int] = None,
                   replay: bool = False) -> Future:
        raise NotImplementedError

    def pull(self, rows: np.ndarray, min_version: int) -> Future:
        """-> Future[(values [len(rows), K], served_version)]."""
        raise NotImplementedError

    def close(self) -> None:
        pass

    # ---- recovery surface (no-ops for transports without failures) ----
    def needs_replay(self) -> frozenset:
        """Shard ids that restarted and await client delta replay."""
        return frozenset()

    def mark_recovered(self, server: int) -> None:
        pass

    def crash_server(self, server: int) -> None:
        raise NotImplementedError(f"{type(self).__name__} cannot inject "
                                  "server crashes")

    def restart_server(self, server: int) -> None:
        raise NotImplementedError(f"{type(self).__name__} cannot restart "
                                  "servers")

    # ---- shared accounting ----
    def _bill(self, counter: List[int], server: int, n_rows: int,
              k: int, itemsize: int) -> None:
        counter[server] += n_rows * (k * itemsize + _ROW_ID_BYTES)

    @property
    def total_bytes(self) -> int:
        return sum(self.pushed_bytes) + sum(self.pulled_bytes)

    def bytes_by_link(self) -> Dict[str, int]:
        out = {}
        for s, b in enumerate(self.pushed_bytes):
            out[f"push:s{s}"] = b
        for s, b in enumerate(self.pulled_bytes):
            out[f"pull:s{s}"] = b
        return out


class SimTransport(Transport):
    """In-process threaded transport over a live ``ParamServer``.

    ``latency_s`` injects a per-operation link delay (one way) so the
    prefetch-overlap claim is measurable on localhost: a barriered S=0
    run pays the pull latency on the critical path; an S>=1 run hides it
    under the sweep (BENCH_comm's overlap gate).  ``wire_dtype`` is the
    value encoding on the wire (numpy dtype; bf16 halves PS wire bytes
    exactly like the allreduce sync_dtype path — the round-trip cast is
    applied so billed bytes and delivered precision agree).
    """

    def __init__(self, server: ParamServer, latency_s: float = 0.0,
                 wire_dtype=np.float32, max_workers: int = 4):
        super().__init__(server.shards.num_servers)
        self.server = server
        self.latency_s = float(latency_s)
        self.wire_dtype = np.dtype(wire_dtype)
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="repro-ps")

    def _encode(self, values: np.ndarray) -> np.ndarray:
        if values.dtype != self.wire_dtype:
            # the wire cast round-trip (mirrors Reducer.psum compress)
            return values.astype(self.wire_dtype).astype(np.float32)
        return np.asarray(values, np.float32)

    def _do_push(self, version, by_server, deltas, k, client_id, seq,
                 replay):
        if self.latency_s:
            time.sleep(self.latency_s)
        for s, (rows, idx) in by_server.items():
            # bill before applying: the payload is on the wire whether
            # the shard dedupes it (duplicate) or rejects it (down) —
            # retry/duplicate overhead shows up in the measured truth
            self._bill(self.pushed_bytes, s, len(rows), k,
                       self.wire_dtype.itemsize)
            self.server.apply_push(s, rows, deltas[idx],
                                   client_id=client_id, seq=seq,
                                   replay=replay)
        self.server.commit(version)

    def push_batch(self, version: int, rows: np.ndarray,
                   deltas: np.ndarray, *, client_id: Optional[str] = None,
                   seq: Optional[int] = None,
                   replay: bool = False) -> Future:
        rows = np.asarray(rows, np.int64)
        deltas = self._encode(np.asarray(deltas))
        k = deltas.shape[1] if deltas.ndim == 2 else 1
        order = np.argsort(rows, kind="stable")
        rows_s, idx_s = rows[order], order
        by_server = {}
        for s, sel in self.server.shards.split(rows_s).items():
            mask = np.isin(rows_s, sel)
            by_server[s] = (rows_s[mask], idx_s[mask])
        return self._pool.submit(self._do_push, version, by_server, deltas,
                                 k, client_id, seq, replay)

    def _do_pull(self, by_server, n_rows, k, min_version):
        if self.latency_s:
            time.sleep(self.latency_s)
        out = np.zeros((n_rows, k), np.float32)
        version = min_version
        for s, (rows, idx) in by_server.items():
            vals, version = self.server.serve_pull(s, rows, min_version)
            out[idx] = self._encode(vals)
            self._bill(self.pulled_bytes, s, len(rows), k,
                       self.wire_dtype.itemsize)
        return out, version

    def pull(self, rows: np.ndarray, min_version: int) -> Future:
        rows = np.asarray(rows, np.int64)
        k = self.server._phi.shape[1]
        idx_all = np.arange(rows.size)
        by_server = {}
        for s, sel in self.server.shards.split(rows).items():
            mask = np.isin(rows, sel)
            by_server[s] = (rows[mask], idx_all[mask])
        return self._pool.submit(self._do_pull, by_server, rows.size, k,
                                 min_version)

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    # ---- recovery surface: delegate to the live server group ----
    def needs_replay(self) -> frozenset:
        return self.server.needs_replay()

    def mark_recovered(self, server: int) -> None:
        self.server.mark_recovered(server)

    def crash_server(self, server: int) -> None:
        self.server.crash(server)

    def restart_server(self, server: int) -> None:
        self.server.restart(server)


class JaxDistributedTransport(Transport):
    """Multi-host slot: the same push/pull contract over
    ``jax.distributed`` collectives.

    Deliberately a validated stub (ROADMAP: real multi-host PS training
    rides the jax.distributed init + per-host data loading work): it
    fails loudly with the wiring instructions instead of silently
    falling back to the simulator, so a cluster launch can never
    *appear* to run multi-host while actually running in-process.
    """

    def __init__(self, num_servers: int):
        import jax

        if not getattr(jax.distributed, "is_initialized", lambda: False)():
            raise RuntimeError(
                "JaxDistributedTransport requires jax.distributed."
                "initialize() (coordinator address + process ids) before "
                "construction; for single-host runs use SimTransport "
                "(--backend ps defaults to it)")
        super().__init__(num_servers)

    def push_batch(self, version, rows, deltas, **kw) -> Future:
        raise NotImplementedError(
            "multi-host PS push is the ROADMAP backlog head: encode "
            "(rows, deltas) per owning host and send over a "
            "jax.distributed side channel; SimTransport defines the "
            "contract this must satisfy (tests/test_paramserver.py)")

    def pull(self, rows, min_version) -> Future:
        raise NotImplementedError(
            "multi-host PS pull is the ROADMAP backlog head; see "
            "push_batch")


# --------------------------------------------------------------------------
# the worker-side client
# --------------------------------------------------------------------------

def _pad_rows(rows: np.ndarray,
              min_bucket: int = 64) -> Tuple[np.ndarray, int]:
    """Pad a touched-row id vector to the next power-of-two bucket
    (>= min_bucket) by repeating ``rows[0]``, returning (padded, pad).

    Touched counts vary freely per batch; without bucketing every
    distinct count compiles a fresh device gather/scatter executable.
    Bucketing bounds compiled shapes by ~log2(W), and the duplicated row
    is written with its own pulled value so the extra scatter lanes are
    idempotent."""
    n = rows.size
    b = min_bucket
    while b < n:
        b *= 2
    return np.concatenate([rows, np.full(b - n, rows[0], rows.dtype)]), b - n

@dataclasses.dataclass
class _PushRec:
    """One issued delta push, retained until a checkpoint fence makes it
    durable — the unit of retry re-issue and crash-recovery replay."""

    seq: int
    version: int
    rows: np.ndarray
    delta: np.ndarray
    future: Optional[Future] = None


class PSClient:
    """Keeps one worker's full-capacity device replica fresh through
    touched-row pulls and emits touched-row delta pushes.

    The replica is what the unchanged POBP shard body consumes, so the
    training step never knows it runs under a parameter server.  Per
    batch ``m`` (1-indexed):

      ``begin_batch(m, rows, phi)``  waits for the prefetched pull
          covering `rows` (issuing a blocking pull if none was
          prefetched), overwrites the replica's touched rows with the
          pulled server slice, and caches the pulled base values the
          push will difference against.  The wait is timed —
          ``pull_wait_s`` is the prefetch-overlap instrument.
      ``prefetch(m_next, rows_next)``  issues the next pull with
          ``min_version = m_next - 1 - S``: at S=0 the transport thread
          blocks until this batch's push commits (barriered); at S>0 it
          can be served immediately from a bounded-stale snapshot, fully
          overlapping the sweep.
      ``end_batch(m, phi_new, rows)``  gathers the updated touched rows,
          pushes ``new - pulled_base`` as version ``m``, and bounds the
          number of uncommitted pushes by S + 1.

    Chaos hardening (DESIGN.md §17): every push carries a monotonic
    ``(client_id, seq)`` tag so re-issue is idempotent; failed push/pull
    ops retry with exponential backoff + deterministic jitter under a
    per-op ``retry_deadline_s``; every push since the last durable fence
    is retained (``mark_durable`` trims), and when a restarted shard
    advertises ``needs_replay`` the client replays the retained log in
    version order — at S=0 the recovered phi is bit-exact with the
    clean run.  Retry/replay wire overhead is billed into ``meter``
    under ``ps.retry.*`` / ``ps.replay`` phases (core/sync.py).
    """

    _RETRYABLE = (TransportError, TimeoutError)

    def __init__(self, transport: Transport, staleness: int = 0,
                 client_id: str = "w0", retry_deadline_s: float = 60.0,
                 backoff0_s: float = 0.01, backoff_max_s: float = 0.5,
                 meter=None):
        if staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        self.transport = transport
        self.staleness = int(staleness)
        self.client_id = str(client_id)
        self.retry_deadline_s = float(retry_deadline_s)
        self.backoff0_s = float(backoff0_s)
        self.backoff_max_s = float(backoff_max_s)
        self.meter = meter
        self.pull_wait_s = 0.0
        self.push_wait_s = 0.0
        self.touched_history: List[int] = []
        self.retries = 0
        self.replayed_pushes = 0
        self.recoveries = 0
        self.retry_wire_bytes = 0
        self._prefetched: Optional[Tuple[int, np.ndarray, Future]] = None
        self._base_rows: Optional[np.ndarray] = None       # pulled values
        self._k: Optional[int] = None                      # replica width
        self._pending: List[_PushRec] = []
        self._retained: List[_PushRec] = []   # since the last durable fence
        self._seq = 0
        self._retry_counter = 0
        import zlib
        self._jitter_key = zlib.crc32(self.client_id.encode())

    # -- helpers ----------------------------------------------------------
    def _min_version(self, m: int) -> int:
        return max(0, m - 1 - self.staleness)

    def _wire_itemsize(self) -> int:
        return np.dtype(getattr(self.transport, "wire_dtype",
                                np.float32)).itemsize

    def _op_nbytes(self, rows: np.ndarray, k: int) -> int:
        return int(rows.size) * (k * self._wire_itemsize() + _ROW_ID_BYTES)

    def _bill_retry(self, phase: str, nbytes: int) -> None:
        self.retry_wire_bytes += nbytes
        if self.meter is not None:
            self.meter.record_host(phase, nbytes)

    def _backoff(self, attempt: int) -> None:
        """Exponential backoff with deterministic jitter: the sleep for
        retry ``n`` of this client is a pure function of
        ``(client_id, retry counter)`` — chaos runs stay replayable."""
        # the exponent is capped: past ~60 doublings every base is the cap,
        # and 2.0 ** attempt overflows a float at attempt 1024
        base = min(self.backoff_max_s,
                   self.backoff0_s * (2.0 ** min(attempt, 60)))
        rng = np.random.default_rng((self._jitter_key, self._retry_counter))
        self._retry_counter += 1
        time.sleep(base * (0.5 + rng.random()))

    # -- retry / recovery core --------------------------------------------
    def _recover_if_needed(self) -> None:
        """If any shard restarted and awaits replay, re-push the retained
        post-fence deltas in version order, then clear the barrier.
        Dedup on still-healthy shards makes the replay a no-op there;
        the restarted shard re-applies exactly the deltas it lost."""
        need = sorted(self.transport.needs_replay())
        if not need:
            return
        self.recoveries += len(need)
        for rec in self._retained:
            k = rec.delta.shape[1] if rec.delta.ndim == 2 else 1
            self._bill_retry("ps.replay", self._op_nbytes(rec.rows, k))
            # replay=True: a replaying shard fences ordinary pushes, so
            # the retained backlog re-applies in version order BEFORE any
            # in-flight retry can land out of order (float adds are not
            # associative — order is part of the bit-exactness contract)
            fut = self.transport.push_batch(rec.version, rec.rows, rec.delta,
                                            client_id=self.client_id,
                                            seq=rec.seq, replay=True)
            t0, attempt = time.time(), 0
            while True:
                try:
                    fut.result()
                    break
                except self._RETRYABLE as e:
                    if time.time() - t0 > self.retry_deadline_s:
                        raise TimeoutError(
                            f"replay of push seq {rec.seq} (version "
                            f"{rec.version}) exceeded retry deadline "
                            f"{self.retry_deadline_s}s: {e}") from e
                    self._backoff(attempt)
                    attempt += 1
                    self.retries += 1
                    self._bill_retry("ps.replay",
                                     self._op_nbytes(rec.rows, k))
                    fut = self.transport.push_batch(
                        rec.version, rec.rows, rec.delta,
                        client_id=self.client_id, seq=rec.seq, replay=True)
            self.replayed_pushes += 1
        for s in need:
            self.transport.mark_recovered(s)

    def _await_push(self, rec: _PushRec) -> None:
        t0, attempt = time.time(), 0
        while True:
            try:
                rec.future.result()
                return
            except self._RETRYABLE as e:
                self._recover_if_needed()
                if time.time() - t0 > self.retry_deadline_s:
                    raise TimeoutError(
                        f"push seq {rec.seq} (version {rec.version}) by "
                        f"client {self.client_id!r} exceeded retry deadline "
                        f"{self.retry_deadline_s}s: {e}") from e
                self._backoff(attempt)
                attempt += 1
                self.retries += 1
                k = rec.delta.shape[1] if rec.delta.ndim == 2 else 1
                self._bill_retry("ps.retry.push",
                                 self._op_nbytes(rec.rows, k))
                rec.future = self.transport.push_batch(
                    rec.version, rec.rows, rec.delta,
                    client_id=self.client_id, seq=rec.seq)

    def _repair_pending(self) -> None:
        """Re-issue any in-flight push whose future already failed — a
        pull timeout is often downstream of our own dropped push."""
        for rec in self._pending:
            if rec.future.done() and rec.future.exception() is not None:
                exc = rec.future.exception()
                if not isinstance(exc, self._RETRYABLE):
                    continue
                self.retries += 1
                k = rec.delta.shape[1] if rec.delta.ndim == 2 else 1
                self._bill_retry("ps.retry.push",
                                 self._op_nbytes(rec.rows, k))
                rec.future = self.transport.push_batch(
                    rec.version, rec.rows, rec.delta,
                    client_id=self.client_id, seq=rec.seq)

    def _pull_with_retry(self, rows: np.ndarray, min_version: int,
                         fut: Optional[Future] = None):
        if fut is None:
            fut = self.transport.pull(rows, min_version)
        t0, attempt = time.time(), 0
        while True:
            try:
                return fut.result()
            except self._RETRYABLE as e:
                self._recover_if_needed()
                self._repair_pending()
                if time.time() - t0 > self.retry_deadline_s:
                    raise TimeoutError(
                        f"pull (min_version {min_version}, {rows.size} "
                        f"rows) by client {self.client_id!r} exceeded retry "
                        f"deadline {self.retry_deadline_s}s: {e}") from e
                self._backoff(attempt)
                attempt += 1
                self.retries += 1
                self._bill_retry("ps.retry.pull",
                                 self._op_nbytes(rows, self._k or 1))
                fut = self.transport.pull(rows, min_version)

    def prefetch(self, m_next: int, rows_next: np.ndarray) -> None:
        if self._prefetched is not None:
            # a stale prefetch (e.g. a fence rebuilt the stream) is
            # drained, not leaked
            self._prefetched[2].result()
        rows_next = np.asarray(rows_next, np.int64)
        self._prefetched = (m_next, rows_next,
                            self.transport.pull(rows_next,
                                                self._min_version(m_next)))

    def begin_batch(self, m: int, rows: np.ndarray, phi):
        """Refresh the replica's `rows` from the server; returns the
        updated replica (a new device array — safe under donation)."""
        import jax.numpy as jnp

        rows = np.asarray(rows, np.int64)
        t0 = time.time()
        if (self._prefetched is not None and self._prefetched[0] == m
                and np.array_equal(self._prefetched[1], rows)):
            vals, _ = self._pull_with_retry(rows, self._min_version(m),
                                            fut=self._prefetched[2])
        else:
            if self._prefetched is not None:
                try:                             # drain a mismatched pull
                    self._prefetched[2].result()
                except self._RETRYABLE:
                    pass                         # value unused; not retried
            vals, _ = self._pull_with_retry(rows, self._min_version(m))
        self._prefetched = None
        self.pull_wait_s += time.time() - t0
        self.touched_history.append(int(rows.size))
        self._base_rows = vals
        if vals.ndim == 2:
            self._k = int(vals.shape[1])
        if not rows.size:
            return phi
        # the device scatter runs at a BUCKETED row count (_pad_rows):
        # per-batch touched counts vary freely, but the compiled scatter
        # shapes stay bounded by #buckets — the same discipline the
        # stream applies to L.  Padding duplicates rows[0] with its own
        # pulled value, so the duplicate writes are idempotent.
        rows_p, pad = _pad_rows(rows)
        vals_p = np.concatenate([vals, np.broadcast_to(vals[:1],
                                                       (pad,) + vals.shape[1:])])
        pulled = jnp.asarray(vals_p, phi.dtype)
        return phi.at[jnp.asarray(rows_p)].set(pulled)

    def end_batch(self, m: int, phi_new, rows: np.ndarray) -> None:
        """Push this batch's touched-row delta as version `m`."""
        import jax.numpy as jnp

        rows = np.asarray(rows, np.int64)
        if rows.size:
            # bucketed gather (see begin_batch): duplicate trailing rows
            # are sliced off after the fetch
            rows_p, _ = _pad_rows(rows)
            new_rows = np.asarray(phi_new[jnp.asarray(rows_p)],
                                  np.float32)[:rows.size]
        else:
            new_rows = np.zeros((0,) + np.shape(phi_new)[1:], np.float32)
        if self._base_rows is None or self._base_rows.shape != new_rows.shape:
            raise RuntimeError("end_batch without a matching begin_batch")
        delta = new_rows - self._base_rows
        self._base_rows = None
        rec = _PushRec(seq=self._seq, version=m, rows=rows, delta=delta)
        self._seq += 1
        rec.future = self.transport.push_batch(
            m, rows, delta, client_id=self.client_id, seq=rec.seq)
        # retained until the next durable fence: the crash-recovery
        # replay source (trimmed by mark_durable, bounded by ckpt_every)
        self._retained.append(rec)
        self._pending.append(rec)
        # bounded staleness also bounds worker memory: at most S + 1
        # pushes may be uncommitted before the oldest must land
        t0 = time.time()
        while len(self._pending) > self.staleness:
            self._await_push(self._pending.pop(0))
        self.push_wait_s += time.time() - t0

    def flush(self) -> None:
        """Commit every outstanding push (checkpoint fences, shutdown)."""
        while self._pending:
            self._await_push(self._pending.pop(0))
        if self._prefetched is not None:
            try:
                self._prefetched[2].result()
            except self._RETRYABLE:
                pass          # value unused; the next begin_batch re-pulls
            self._prefetched = None

    def mark_durable(self) -> None:
        """Checkpoint-fence handshake: every retained push is now covered
        by a server-synced snapshot (``ParamServer.mark_synced``) — the
        replay log can be trimmed."""
        self._retained.clear()

    @property
    def mean_touched_rows(self) -> float:
        if not self.touched_history:
            return 0.0
        return float(np.mean(self.touched_history))

    def stats(self) -> Dict[str, Any]:
        return {"pull_wait_s": self.pull_wait_s,
                "push_wait_s": self.push_wait_s,
                "mean_touched_rows": self.mean_touched_rows,
                "wire_bytes": self.transport.total_bytes,
                "bytes_by_link": self.transport.bytes_by_link(),
                "retries": self.retries,
                "replayed_pushes": self.replayed_pushes,
                "recoveries": self.recoveries,
                "retry_wire_bytes": self.retry_wire_bytes,
                "retained_pushes": len(self._retained)}


def touched_rows_of(word_ids, counts) -> np.ndarray:
    """Sorted unique vocabulary rows a mini-batch actually touches
    (padding slots carry zero counts and never count).  Accepts [D, L]
    or [N, Dl, L] stacked arrays."""
    wid = np.asarray(word_ids).reshape(-1)
    cnt = np.asarray(counts).reshape(-1)
    return np.unique(wid[cnt > 0]).astype(np.int64)


def sliced_sum(deltas_by_shard: Sequence[np.ndarray],
               touched_by_shard: Sequence[np.ndarray],
               w_cap: int) -> np.ndarray:
    """The PS sum a server group computes: each shard contributes ONLY
    its touched-row slice, applied in shard order.

    This is the algebra the sliced exchange stands on: when each shard's
    dense delta is zero off its touched rows (true by construction for
    POBP's token-scatter payloads), the union-of-touched-row slice sum
    equals the full dense allreduce BIT-EXACTLY — per-row, the same
    floats add in the same order; rows outside every touched set add
    nothing at all.  tests/test_ps_properties.py pins this, including
    live-W guard rows and the bf16 wire-cast path.
    """
    k = deltas_by_shard[0].shape[1]
    out = np.zeros((w_cap, k), deltas_by_shard[0].dtype)
    for delta, touched in zip(deltas_by_shard, touched_by_shard):
        touched = np.asarray(touched, np.int64)
        out[touched] += np.asarray(delta)[touched]
    return out
