"""The program's own tracing (``repro.obs``: host spans, per-request
stamps, ``jax.named_scope`` names) joined to a traced run.

The program stamps its spans and requests with ``time.perf_counter_ns``,
as the benchmark's own `harness.Spans` does; the profiler's trace keeps
another clock.  `clock_offset` finds the offset between the two from
the benchmark spans that are on both (each is in ``run.spans.records``
and, as a ``TraceAnnotation``, in ``run.trace.host``), and the other
functions hand the program's records over on the trace's clock, beside
``run.trace``'s device events and window.

`step_split` charges the device time of the training step's ops to the
POBP phase (``pobp.*`` scope) each was traced under.  The trace names an
op by its HLO instruction only, so the instruction's scope comes from
the text of the step compiled again after the window, from the cell's
configuration, with the run's donation and shardings (through the
persistent compile cache the run filled).

Every function returns None where the program keeps no such records (a
checkout without ``repro.obs``), or where the compiled step is not the
traced one: a reader then reports nothing rather than a wrong number.
"""

from __future__ import annotations

import bisect
import re
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import Event, _merge

# benchmark spans on both clocks: the training loop's and the slab loop's
ALIGN_SPANS = ("dispatch", "wait", "slab_step", "harvest")
STEP_SCOPES = ("pobp.init", "pobp.dense_sweep", "pobp.dense_sync",
               "pobp.select", "pobp.selective_sweep", "pobp.power_sync",
               "pobp.scatter", "pobp.accumulate")
# ops whose device event encloses the events of the computations they call
CONTAINERS = ("while", "call", "conditional")
_MATCH_FIRST = 32


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def _pair_offsets(ring: List[Tuple[int, int]], trace: List[Tuple[int, int]]
                  ) -> List[int]:
    """Trace start minus ring start of each span of one name.  The trace
    holds the spans of its session only, a contiguous run of the ring's:
    that run is the one whose durations and relative starts match."""
    if not trace or len(trace) > len(ring):
        return []
    m = min(len(trace), _MATCH_FIRST)

    def cost(j):
        return sum(abs((ring[j + i][1] - ring[j + i][0])
                       - (trace[i][1] - trace[i][0]))
                   + abs((ring[j + i][0] - ring[j][0])
                         - (trace[i][0] - trace[0][0]))
                   for i in range(m))

    j = min(range(len(ring) - len(trace) + 1), key=cost)
    return [t[0] - r[0] for t, r in zip(trace, ring[j:])]


def clock_offset(run, names: Sequence[str] = ALIGN_SPANS) -> Optional[int]:
    """Nanoseconds that put a ``perf_counter_ns`` time on the trace's
    clock: the median, over the benchmark spans of ``names`` held on both
    clocks, of trace start minus ring start."""
    if run.trace is None:
        return None
    offsets: List[int] = []
    for name in names:
        ring = sorted((s, e) for n, s, e in run.spans.records if n == name)
        trace = sorted((e.start, e.end) for e in run.trace.host
                       if e.name == name)
        offsets += _pair_offsets(ring, trace)
    return int(statistics.median(offsets)) if offsets else None


def program_spans(run, prefix: str = "") -> Optional[List[Event]]:
    """The program's spans whose name starts with ``prefix`` that lie
    inside the trace's window, on the trace's clock."""
    obs, off = _obs(), clock_offset(run)
    if obs is None or off is None:
        return None
    t0, t1 = run.trace.t0 - off, run.trace.t1 - off
    return [Event(n, s + off, e + off) for n, s, e in obs.records(t0, t1)
            if n.startswith(prefix)]


def window_requests(run, kinds: Sequence[str]) -> Optional[List[dict]]:
    """``{kind: t}`` on the trace's clock, for ``kinds`` and ``done``, of
    every request whose ``done`` stamp lies inside the trace's window and
    that holds a stamp of each of ``kinds``."""
    obs, off = _obs(), clock_offset(run)
    if obs is None or off is None:
        return None
    t0, t1 = run.trace.t0 - off, run.trace.t1 - off
    out = []
    for rec in obs.requests(t0, t1).values():
        if ("done" in rec and t0 <= rec["done"] <= t1
                and all(k in rec for k in kinds)):
            out.append({k: rec[k] + off for k in (*kinds, "done")})
    return out


def device_gaps(trace) -> List[Tuple[int, int]]:
    """The window's idle intervals on the first chip: the gaps in the
    union of its ops."""
    first = sorted(trace.ops)[0] if trace.ops else None
    gaps, cur = [], trace.t0
    for s, e in _merge((x.start, x.end) for x in trace.ops.get(first, [])):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < trace.t1:
        gaps.append((cur, trace.t1))
    return gaps


def overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two unions of intervals."""
    a, b = _merge(a), _merge(b)
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


# ---------------------------------------------------------------------------
# the training step's phases
# ---------------------------------------------------------------------------

def _lower_step(run):
    """``lower()`` of the cell's step at the cell's shapes, with the
    donation and shardings of `bench.train_cell.run_train`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.train_cell import lda_config
    from repro.core.types import LDATrainState
    config, traffic = run.cell.config, run.cell.traffic
    chips = run.cell.chips
    n_docs = int(traffic.get("docs_per_chip") or config["docs_per_batch"]
                 ) * chips
    L = int(config["len_bucket"])
    W, K = int(config["vocab_size"]), int(config["num_topics"])
    cfg = lda_config(config)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    if chips == 1:
        from repro.core.pobp import make_train_step
        step, _ = make_train_step(cfg)
        rows = phi = rep = None
    else:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.launch.lda_train import make_shardmap_train_step
        mesh = Mesh(np.asarray(jax.devices()[:chips]).reshape(chips, 1),
                    ("data", "model"))
        step, _ = make_shardmap_train_step(cfg, mesh)
        rows = NamedSharding(mesh, P("data", None))
        phi = NamedSharding(mesh, P(None, "model"))
        rep = NamedSharding(mesh, P())
    sds = jax.ShapeDtypeStruct
    state = LDATrainState(phi_acc=sds((W, K), jnp.float32, sharding=phi),
                          m=sds((), jnp.int32, sharding=rep),
                          rng=sds(key.shape, key.dtype, sharding=rep))
    return lambda: step.lower(state, sds((n_docs, L), jnp.int32,
                                         sharding=rows),
                              sds((n_docs, L), jnp.float32, sharding=rows))


def _compiled_text(lower, scopes: Sequence[str]) -> str:
    """The compiled module's text.  The persistent cache's key leaves
    out op metadata, so an entry written by a build of the same program
    without scopes would come back without them: then compile anew."""
    text = lower().compile().as_text()
    if any(s in text for s in scopes):
        return text
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lower().compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def split_ops(trace, hlo_text: str, scopes: Sequence[str]
              ) -> Optional[Dict[Optional[str], float]]:
    """Device ms per call of the compiled module, by the scope of each
    leaf op (`repro.obs.op_scopes`; None: none of ``scopes``), averaged
    over the chips; None where the module is not the traced one (another
    name, or a traced op it does not hold) or no op has a scope."""
    from repro.obs import hlo_ops, op_scopes
    m = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    if m is None:
        return None
    ops = hlo_ops(hlo_text)
    scope = op_scopes(hlo_text, scopes)
    calls = 0
    out: Dict[Optional[str], float] = defaultdict(float)
    for plane, mods in trace.modules.items():
        spans = _merge((e.start, e.end) for e in mods
                       if e.name.split("(", 1)[0] == m.group(1))
        calls += sum(1 for e in mods
                     if e.name.split("(", 1)[0] == m.group(1))
        starts = [s for s, _ in spans]
        for e in trace.ops.get(plane, []):
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start >= spans[i][1]:
                continue                  # outside the module's calls
            if e.name not in ops:
                return None
            if ops[e.name][0] not in CONTAINERS:
                out[scope[e.name]] += e.end - e.start
    if not calls or not any(k is not None for k in out):
        return None
    return {k: v * 1e-6 / calls for k, v in out.items()}


def step_split(run) -> Optional[Dict[Optional[str], float]]:
    """`split_ops` of the training step over ``STEP_SCOPES``, computed
    once a run."""
    c = run.counters
    if "step_split" not in c:
        c["step_split"] = None
        if run.trace is not None and _obs() is not None:
            try:
                text = _compiled_text(_lower_step(run), STEP_SCOPES)
            except Exception as e:        # a reader never fails the run
                print(f"[bench] the step did not compile again: {e!r}",
                      file=sys.stderr, flush=True)
                return None
            c["step_split"] = split_ops(run.trace, text, STEP_SCOPES)
    return c["step_split"]


def phase_ms(run, scopes: Sequence[str]) -> Optional[float]:
    """Device ms per step under ``scopes`` (leaf ops only)."""
    split = step_split(run)
    if split is None:
        return None
    return sum(split.get(s, 0.0) for s in scopes)
