"""Reduction of a profiler trace of the measured window to device
numbers: busy and idle time, per-op device time, kernel time, the idle
gaps named by the benchmark's host spans.

The window is the benchmark's own ``window`` host span.  Device
operations are the events of each TPU plane's ``XLA Ops`` line, named
by their HLO instruction (a loop's event encloses those of its body, so
busy time is a union of intervals), and jitted programs those of its
``XLA Modules`` line; host spans are the
``TraceAnnotation`` events of the host plane.  All times are in
nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

HOST_SPANS = ("feed", "dispatch", "wait", "submit", "slab_step", "harvest",
              "poll", "generator_wait")
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int


def _merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: List[Event], t0: int, t1: int) -> List[Event]:
    return [Event(e.name, max(e.start, t0), min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


@dataclasses.dataclass
class Trace:
    """The device and host events of one traced window."""

    ops: Dict[str, List[Event]]          # device plane -> XLA ops
    modules: Dict[str, List[Event]]      # device plane -> XLA modules
    host: List[Event]                    # benchmark host spans
    t0: int
    t1: int

    @classmethod
    def from_events(cls, ops, modules, host) -> "Trace":
        win = [e for e in host if e.name == "window"]
        if not win:
            raise ValueError("the trace holds no 'window' host span")
        t0, t1 = win[0].start, win[0].end
        return cls({k: _clip(v, t0, t1) for k, v in ops.items()},
                   {k: _clip(v, t0, t1) for k, v in modules.items()},
                   [e for e in host if e.name in HOST_SPANS], t0, t1)

    @property
    def chips(self) -> int:
        return max(1, len(self.ops))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        tot = sum(sum(e - s for s, e in _merge((x.start, x.end) for x in ev))
                  for ev in self.ops.values())
        return tot * 1e-9 / self.chips

    def op_seconds(self, match) -> Tuple[float, float]:
        """(calls, seconds) of the ops whose name ``match`` accepts, per
        chip (averaged over the chips)."""
        n = s = 0
        for ev in self.ops.values():
            for e in ev:
                if match(e.name):
                    n += 1
                    s += e.end - e.start
        return n / self.chips, s * 1e-9 / self.chips

    def kernel_seconds(self, kernel: str) -> Tuple[float, float]:
        """(calls, seconds) of a Pallas kernel, by the name of the jitted
        function that wraps its ``pallas_call`` (the op is named after
        it: ``bp_update_tokens.1``)."""
        return self.op_seconds(lambda n: _SUFFIX.sub("", n) == kernel)

    def module_seconds(self, prefix: str) -> Tuple[float, float]:
        n = s = 0
        for ev in self.modules.values():
            for e in ev:
                if e.name.startswith(prefix):
                    n += 1
                    s += e.end - e.start
        return n / self.chips, s * 1e-9 / self.chips

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds of the first chip, each gap charged to the host
        span that overlaps it most, the innermost of nested spans that
        overlap it alike ("none" where no span does)."""
        first = sorted(self.ops)[0] if self.ops else None
        busy = _merge((e.start, e.end) for e in self.ops.get(first, []))
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        host = sorted(self.host, key=lambda e: e.start)
        starts = [e.start for e in host]
        out: Dict[str, float] = defaultdict(float)
        longest = max((e.end - e.start for e in host), default=0)
        for gs, ge in gaps:
            best, name = 0, "none"
            i = bisect.bisect_left(starts, gs - longest)
            while i < len(host) and host[i].start < ge:
                ov = min(ge, host[i].end) - max(gs, host[i].start)
                if ov > 0 and ov >= best:
                    best, name = ov, host[i].name
                i += 1
            out[name] += (ge - gs) * 1e-9
        return dict(out)

    def breakdown(self) -> dict:
        per_op: Dict[str, float] = defaultdict(float)
        for ev in self.ops.values():
            for e in ev:
                per_op[e.name] += (e.end - e.start) * 1e-9 / self.chips
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event, whose name is the
    instruction's text (``%fusion.16 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and plane_name[
        len("/device:TPU:"):].isdigit()


def read_xspace(path: str) -> Trace:
    """A `Trace` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                evs = [Event(op_name(e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS or e.name == "window":
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    return Trace.from_events(ops, modules, host)


def find_xspace(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None
