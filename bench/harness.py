"""What every cell shares: finding its pieces by name, the compile cache
and compile counter, host spans, the result line.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration (a
file under ``bench/configs/``), a traffic mix (``bench/traffic/<name>.json``,
whose ``kind`` picks the runner ``bench/<kind>_cell.py`` and whose
``arrivals``, where it serves, picks ``bench/arrivals/<name>.py``) and,
through ``per_layer``, the metric readers (``bench/metrics/<name>.py``)
that apply to it.  Correctness
limits live in ``bench/limits/<workload>.json``.  Adding a cell, a mix or
a metric adds files; nothing here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One cell with everything it reads from data files."""

    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    wl = find(bench["workloads"], name, "workload")
    cfg_entry = find(bench["configs"], wl["config"], "configuration")
    with open(ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{wl['traffic']}.json") as f:
        traffic = json.load(f)
    with open(BENCH / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    return Cell(wl, config, traffic, limits,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def load_piece(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``: a metric's reader, an
    arrival process."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} piece named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<metric>.py``."""
    return load_piece("metrics", metric).read


def runner_for(kind: str) -> Callable:
    """The runner of a traffic ``kind``: ``run_<kind>`` of
    ``bench/<kind>_cell.py``."""
    return getattr(importlib.import_module(f"bench.{kind}_cell"),
                   f"run_{kind}")


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is cached, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's trace and compile events (a process-wide listener)."""

    def __init__(self):
        import jax
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.events += 1


class GcPauses:
    """Freezes what set-up allocated (compiled programs, the generated
    pool) out of the collector's reach, so the window's collections do
    not walk it, and records every collection's pause from then on."""

    def __init__(self):
        import gc
        gc.collect()
        gc.freeze()
        self.pauses: List[tuple] = []       # (generation, seconds)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def close(self) -> dict:
        import gc
        gc.callbacks.remove(self._on)
        gc.unfreeze()
        return {"gc_collections": len(self.pauses),
                "gc_max_pause_s": max((p for _, p in self.pauses),
                                      default=0.0)}


class Spans:
    """The benchmark's own host spans: kept in memory, and written into
    the profiler's trace as ``TraceAnnotation`` so that device gaps can
    be named by what the host was doing."""

    def __init__(self):
        self.records: List[tuple] = []      # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter_ns()))

    def total_ns(self, name: str, t0: int = 0, t1: int = 2**63) -> int:
        return sum(e - s for n, s, e in self.records
                   if n == name and s >= t0 and e <= t1)

    def longest_ns(self, name: str, t0: int = 0, t1: int = 2**63) -> int:
        return max((e - s for n, s, e in self.records
                    if n == name and s >= t0 and e <= t1), default=0)

    def count(self, name: str, t0: int = 0, t1: int = 2**63) -> int:
        return sum(1 for n, s, e in self.records
                   if n == name and s >= t0 and e <= t1)


@dataclasses.dataclass
class Run:
    """What a run hands to the metric readers and to the result line."""

    cell: Cell
    seed: int
    seconds: float
    device: Dict[str, Any] = dataclasses.field(default_factory=dict)
    peaks: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spans: Spans = dataclasses.field(default_factory=Spans)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    attempted: int = 0
    failed: int = 0
    trace: Any = None                   # bench.trace.Trace of the window
    compiles: Any = None                # CompileCounter

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks.values())


def result_line(run: Run, traced: bool) -> dict:
    """The JSON object printed as the last line of standard output."""
    cell = run.cell
    metrics: Dict[str, dict] = {}
    if traced:
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                  "unit": m["unit"]}
    out = {"correct": run.correct, "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics,
           "device": dict(run.device)}
    if traced and run.trace is not None:
        out["device"]["busy_s"] = run.trace.busy_s
        out["device"]["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = run.checks
    return out


def print_checks(run: Run) -> None:
    """Each compared number beside its limit, as the last lines of
    standard error."""
    for name, c in run.checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
