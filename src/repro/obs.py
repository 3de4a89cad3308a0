"""The program's own tracing: host spans, per-request stamps, and the
join of a compiled module's instructions to the ``jax.named_scope``
names they were traced under.

``span(name)`` times a block of host code twice: as a
``jax.profiler.TraceAnnotation``, which lands on a profiler trace's host
plane while a trace is being taken, and as a ``(name, start_ns, end_ns)``
record in a process-wide bounded ring.  ``stamp(kind, key)`` keeps
per-request times (submit, refill, ...) in a second ring.  Both rings
are stamped with ``time.perf_counter_ns()``; a profiler trace keeps its
own clock, a fixed offset away, so a reader of a trace first finds that
offset from spans it holds on both clocks and then shifts the rings by
it.  The rings live here, not on the objects that fill them, so they
can be read after those objects are gone.  There is no switch: a span
costs about a microsecond on the host whether or not a trace is taken.

``op_scopes(hlo_text, scopes)`` reads a compiled module's text
(``jit(f).lower(...).compile().as_text()``) and gives each instruction
the innermost of ``scopes`` in its ``op_name`` metadata.  A device
trace names each op by its instruction (``fusion.202``), so the map
gives each op of the trace its scope.
"""

from __future__ import annotations

import collections
import re
from time import perf_counter_ns
from typing import Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

SPAN_RING = 65536
STAMP_RING = 262144      # a request takes four stamps

_tracing = TraceAnnotation.is_enabled
_spans: "collections.deque[Tuple[str, int, int]]" = collections.deque(
    maxlen=SPAN_RING)
_stamps: "collections.deque[tuple]" = collections.deque(maxlen=STAMP_RING)


class span:
    """``with span("slab.dispatch"): ...`` — a host span in the ring, and
    on the profiler's trace while one is being taken."""

    __slots__ = ("name", "t0_ns", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self.t0_ns = perf_counter_ns()
        # TraceMe records nothing outside a trace; asking first spares
        # its construction (most of a span's cost) in an untraced run
        self._ann = TraceAnnotation(self.name) if _tracing() else None
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _spans.append((self.name, self.t0_ns, perf_counter_ns()))


def stamp(kind: str, key, t_ns: Optional[int] = None, **fields) -> None:
    """One time of one request (``kind`` names the moment, ``key`` the
    request), now or at ``t_ns`` on the ring's clock, with any
    ``fields`` kept beside it."""
    _stamps.append((kind, key, perf_counter_ns() if t_ns is None else t_ns,
                    fields))


def records(t0: int = 0, t1: int = 2**63) -> List[Tuple[str, int, int]]:
    """The spans in the ring that lie inside ``[t0, t1]``, in the order
    they ended."""
    return [r for r in list(_spans) if r[1] >= t0 and r[2] <= t1]


def requests(t0: int = 0, t1: int = 2**63) -> Dict[object, dict]:
    """``{key: {kind: t_ns, **fields}}`` for every request with a stamp
    inside ``[t0, t1]``, with all of its stamps (those outside the range
    too); a later stamp of a kind replaces an earlier one."""
    stamps = list(_stamps)
    keys = {key for _, key, t, _ in stamps if t0 <= t <= t1}
    out: Dict[object, dict] = {}
    for kind, key, t, fields in stamps:
        if key in keys:
            rec = out.setdefault(key, {})
            rec[kind] = t
            rec.update(fields)
    return out


def clear() -> None:
    """Empties both rings."""
    _spans.clear()
    _stamps.clear()


# ---------------------------------------------------------------------------
# compiled module text -> scopes
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TRANSFORM = re.compile(r"[\w\-]+\((.*)\)$")


def _after_shape(rest: str) -> str:
    """What follows an instruction's result shape: a tuple shape is
    parenthesized (and holds spaces), any other holds none."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[i + 1:]
        return ""
    return rest.split(" ", 1)[1] if " " in rest else ""


def _parse(hlo_text: str):
    """``({instruction: (opcode, op_name, called)}, {computation:
    [instruction]})``; ``called`` is the computation a fusion calls."""
    ops: Dict[str, Tuple[str, str, Optional[str]]] = {}
    members: Dict[str, List[str]] = collections.defaultdict(list)
    comp = ""
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split(" (", 1)[0].replace("ENTRY ", "").lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OPCODE.match(_after_shape(m.group(2)))
        if op is None:
            continue
        meta, calls = _OP_NAME.search(line), _CALLS.search(line)
        ops[m.group(1)] = (op.group(1), meta.group(1) if meta else "",
                           calls.group(1) if calls else None)
        members[comp].append(m.group(1))
    return ops, members


def hlo_ops(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """``{instruction: (opcode, op_name)}`` of every instruction of every
    computation in a module's text (``op_name`` empty where the
    instruction carries none)."""
    return {n: (op, name) for n, (op, name, _) in _parse(hlo_text)[0].items()}


def innermost_scope(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The last path component of ``op_name`` that is one of ``scopes``,
    also where a transformation wraps it (``vmap(pobp.init)``)."""
    scopes = set(scopes)
    for part in reversed(op_name.split("/")):
        m = _TRANSFORM.match(part)
        while m is not None and part not in scopes:
            part = m.group(1)
            m = _TRANSFORM.match(part)
        if part in scopes:
            return part
    return None


def op_scopes(hlo_text: str, scopes: Iterable[str]
              ) -> Dict[str, Optional[str]]:
    """``{instruction: scope}``: each instruction of a compiled module
    and the innermost of ``scopes`` it was traced under (None where it
    was traced under none of them).  A fusion whose own metadata names
    no scope (XLA gives a fusion its root's, and a root that a pass
    made carries none) takes the scope most of its fused instructions
    name."""
    scopes = set(scopes)
    ops, members = _parse(hlo_text)
    out: Dict[str, Optional[str]] = {}

    def scope_of(name: str) -> Optional[str]:
        if name not in out:
            opcode, op_name, called = ops[name]
            out[name] = innermost_scope(op_name, scopes)
            if out[name] is None and called in members:
                votes = collections.Counter(
                    scope_of(x) for x in members[called])
                votes.pop(None, None)
                out[name] = votes.most_common(1)[0][0] if votes else None
        return out[name]

    for name in ops:
        scope_of(name)
    return out
