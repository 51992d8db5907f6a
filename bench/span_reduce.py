"""Reduce the program's own spans and device scopes in a JAX profiler trace.

``trace_reduce`` names each idle gap by the innermost host event over it,
Python frames included.  This module reads the program's spans instead:
the host spans named ``fsi.*`` (the fleet call), ``payload.*`` (the payload
codec), ``serve.*`` (the serving loop) and ``bench.*`` (the harness), and
the device scopes the jitted decode step opens
(``jax.named_scope("serve.pool_gather")`` and its siblings), which reach
the compiled program as each instruction's ``op_name`` metadata.  The
profiler keeps every program it ran, as a serialized ``HloProto``, in the
trace's ``/host:metadata`` plane; :func:`programs` reads them from the
``.xplane.pb`` and :func:`instruction_scopes` gives each instruction its
scope, the copies and relayouts the compiler inserts without an
``op_name`` included.  Everything is clipped to the window span, on the
window's thread:

* :func:`span_seconds`: host seconds per span name, the union of that
  name's intervals, so nested or repeated spans never count twice
  (optionally only inside the spans of one name);
* :func:`span_count`: the spans of each name that start in the window;
* :func:`idle_by_span`: the device's idle gaps, each named by the innermost
  program span over its middle (``"no program span"`` where none is);
* :func:`scope_seconds`: device seconds per scope, the union of the
  intervals of the operations under it, so that a ``while`` and the body
  it runs are not summed twice;
* :func:`step_positions`: the valid and the gathered cache positions the
  ``serve.step`` spans carry.

:func:`layer_metrics` turns these into per-layer numbers.  By hand, from
the root of the checkout, on a trace kept with ``run_cell.py --trace 1
--trace-dir <dir>``::

    python3 -m bench.span_reduce <dir>
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import (Dict, Iterable, Iterator, List, Mapping, Optional,
                    Sequence, Tuple)

from bench import trace_reduce as tr
from bench.trace_reduce import Event

PROGRAM_SPAN = re.compile(r"^(fsi|payload|serve|bench)\.")
NO_SPAN = "no program span"
WINDOW_SPAN = "bench.window"
DEVICE_SCOPES = ("serve.pool_gather", "serve.pool_scatter", "serve.decode",
                 "serve.sample")


@dataclasses.dataclass
class ScopedOp:
    """A device operation and the scope it runs under (``""`` for none);
    ``inherited`` where the operation carries no scope of its own and takes
    that of the operations its data comes from (see
    :func:`instruction_scopes`)."""

    event: Event
    scope: str = ""
    inherited: bool = False


@dataclasses.dataclass
class SpanTrace:
    """What the span reduction reads; tests build one by hand."""

    trace: tr.Trace                       # as ``trace_reduce`` loads it
    device_scoped: Dict[str, List[ScopedOp]]   # device plane -> ops
    # each ``serve.step`` span: its start (ns) and its stats
    steps: List[Tuple[float, Dict[str, float]]] = dataclasses.field(
        default_factory=list)


# -- the programs' HLO, from the trace's metadata plane -----------------------
#
# A minimal protobuf reader, for the fields used here of tsl's xplane.proto
# (XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5, map
# entries key 1 / value 2; XEventMetadata.name 2, stats 5; XStatMetadata.name
# 2; XStat.metadata_id 1, bytes_value 6) and xla's hlo.proto
# (HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# metadata 7, id 35, operand_ids 36; OpMetadata.op_name 2).

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


@dataclasses.dataclass
class Instruction:
    id: int
    name: str
    op_name: str
    operands: Tuple[int, ...]


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of a serialized message: an
    int for a varint, the bytes of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _varints(v) -> Tuple[int, ...]:
    """A repeated varint field's values: packed, or one unpacked value."""
    if isinstance(v, int):
        return (v,)
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return tuple(out)


def _map_value(entry):
    """The value of a serialized map entry (key 1, value 2)."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def hlo_instructions(hlo_proto) -> List[Instruction]:
    """Every instruction of every computation of a serialized ``HloProto``
    (instruction ids are unique in a module)."""
    out = []
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                iid, name, op_name, operands = 0, "", "", []
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(x) for j, x in _fields(v)
                                        if j == 2), "")
                    elif k == 35:
                        iid = v
                    elif k == 36:
                        operands.extend(_varints(v))
                out.append(Instruction(iid, name, op_name, tuple(operands)))
    return out


def programs(xspace: bytes) -> Dict[str, List[Instruction]]:
    """The programs a serialized ``XSpace`` holds in its metadata plane, by
    the name the device's program line gives them
    (``jit_step(<program id>)``): their HLO instructions."""
    buf = memoryview(xspace)
    out: Dict[str, List[Instruction]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = _fields(plane)
        # fields come in number order: the name before the metadata maps
        if next((_text(v) for g, v in fields if g == 2), "") \
                != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for g, v in fields:
            if g == 4:
                events.append(_map_value(v))
            elif g == 5:
                d = dict(_fields(_map_value(v)))
                stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        for md in events:
            name, blobs = "", []
            for g, v in _fields(md):
                if g == 2:
                    name = _text(v)
                elif g == 5:
                    d = dict(_fields(v))
                    if 6 in d:
                        blobs.append((d.get(1, 0), d[6]))
            for stat, blob in blobs:
                if stat_names.get(stat) == HLO_PROTO_STAT:
                    out[name] = hlo_instructions(blob)
    return out


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is one component of the ``op_name`` path."""
    return scope in op_name.split("/")


def instruction_scopes(instructions: Sequence[Instruction],
                       scopes: Sequence[str]
                       ) -> Dict[str, Tuple[str, bool]]:
    """Each instruction's name -> ``(scope, inherited)``.  An instruction's
    own scope is the first of ``scopes`` on its ``op_name`` path.  One with
    none (the copies, relayouts and concatenations the compiler inserts
    carry no ``op_name``) inherits the one scope it reaches by walking its
    operands back through other unscoped instructions, or, if that reaches
    none, its users forward.  No scope, or more than one, gives ``""``."""
    own = {i.id: next((s for s in scopes if in_scope(i.op_name, s)), "")
           for i in instructions}
    operands = {i.id: i.operands for i in instructions}
    users: Dict[int, List[int]] = defaultdict(list)
    for i in instructions:
        for o in i.operands:
            users[o].append(i.id)

    def reached(start: int, edges: Mapping) -> set:
        found, seen, todo = set(), {start}, list(edges.get(start, ()))
        while todo:
            j = todo.pop()
            if j in seen or j not in own:
                continue
            seen.add(j)
            if own[j]:
                found.add(own[j])
            else:
                todo.extend(edges.get(j, ()))
        return found

    out: Dict[str, Tuple[str, bool]] = {}
    if not any(own.values()):           # a program with no scope at all
        return {i.name: ("", False) for i in instructions}
    for i in instructions:
        if own[i.id]:
            out[i.name] = (own[i.id], False)
            continue
        found = reached(i.id, operands) or reached(i.id, users)
        out[i.name] = (found.pop(), True) if len(found) == 1 else ("", False)
    return out


def scope_ops(ops: Sequence[Event], modules: Sequence[Event],
              by_program: Mapping[str, Mapping[str, Tuple[str, bool]]]
              ) -> List[ScopedOp]:
    """Each device operation with its scope: the program it runs in is the
    device's program span over its start, and its instruction is the name
    ``trace_reduce`` gives it (``copy.44 (copy)``)."""
    # a program line's name (``jit_step(<id>)``) is the metadata's key; by
    # the name alone where the ids differ and the name is unique
    names = defaultdict(list)
    for key in by_program:
        names[key.partition("(")[0]].append(key)
    mods = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        k = bisect.bisect_right(starts, e.start) - 1
        prog = None
        if k >= 0 and e.start < mods[k].end:
            key = mods[k].name
            if key not in by_program:
                alike = names.get(key.partition("(")[0], [])
                key = alike[0] if len(alike) == 1 else key
            prog = by_program.get(key)
        scope, inherited = (prog.get(e.name.partition(" (")[0], ("", False))
                            if prog else ("", False))
        out.append(ScopedOp(e, scope, inherited))
    return out


def load(trace_dir: str) -> SpanTrace:
    """Read the ``.xplane.pb`` under ``trace_dir``: the trace as
    ``trace_reduce.load`` gives it, each device operation's scope, and the
    ``serve.step`` spans' stats."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    trace = tr.load(trace_dir)
    by_program = {k: instruction_scopes(v, DEVICE_SCOPES)
                  for k, v in programs(files[-1].read_bytes()).items()}
    scoped = {plane: scope_ops(ops, trace.device_modules.get(plane, []),
                               by_program)
              for plane, ops in trace.device_ops.items()}
    pd = ProfileData.from_file(str(files[-1]))
    steps = [(float(e.start_ns), dict(e.stats))
             for plane in pd.planes if plane.name == tr.HOST_PLANE
             for line in plane.lines for e in line.events
             if e.name == "serve.step"]
    return SpanTrace(trace, scoped, steps)


def _window(trace: tr.Trace, window_span: str):
    thread, win = tr.find_span(trace, window_span)
    return trace.host_threads[thread], win.start, win.end


def _seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in tr.union(intervals)) * 1e-9


def _within(intervals: Sequence[Tuple[float, float]],
            outer: Sequence[Tuple[float, float]]
            ) -> List[Tuple[float, float]]:
    """The parts of ``intervals`` inside ``outer`` (disjoint, sorted)."""
    return [(max(a, c), min(b, d)) for a, b in intervals for c, d in outer
            if a < d and c < b]


def span_seconds(trace: tr.Trace, window_span: str = WINDOW_SPAN,
                 under: Optional[str] = None) -> Dict[str, float]:
    """Seconds per program span name; with ``under``, only the time inside
    the spans of that name (``under="fsi.call"``: the codec's spans of the
    fleet call, not those of another path)."""
    host, lo, hi = _window(trace, window_span)
    by_name: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for e in tr._clip(host, lo, hi):
        if PROGRAM_SPAN.match(e.name):
            by_name[e.name].append((e.start, e.end))
    if under is not None:
        outer = tr.union(by_name.get(under, []))
        by_name = {n: _within(iv, outer) for n, iv in by_name.items()}
    return {n: _seconds(iv) for n, iv in by_name.items() if iv}


def span_count(trace: tr.Trace,
               window_span: str = WINDOW_SPAN) -> Dict[str, int]:
    host, lo, hi = _window(trace, window_span)
    out: Dict[str, int] = defaultdict(int)
    for e in host:
        if PROGRAM_SPAN.match(e.name) and lo <= e.start < hi:
            out[e.name] += 1
    return dict(out)


def idle_by_span(trace: tr.Trace,
                 window_span: str = WINDOW_SPAN) -> Dict[str, float]:
    """Idle device seconds (averaged over the devices) by the innermost
    program span over each gap's middle; Python frames are skipped."""
    host, lo, hi = _window(trace, window_span)
    spans = [e for e in host if PROGRAM_SPAN.match(e.name)]
    out: Dict[str, float] = defaultdict(float)
    n = len(trace.device_ops)
    for events in trace.device_ops.values():
        busy = tr.union((e.start, e.end) for e in tr._clip(events, lo, hi))
        idle = tr.gaps(busy, lo, hi)
        names = tr.innermost(spans, [(a + b) / 2 for a, b in idle])
        for (a, b), who in zip(idle, names):
            out[who or NO_SPAN] += (b - a) * 1e-9 / n
    return dict(out)


def scope_seconds(st: SpanTrace, scopes: Sequence[str],
                  window_span: str = WINDOW_SPAN,
                  inherited: bool = True) -> Dict[str, float]:
    """Device seconds (averaged over the devices) of the operations under
    each scope: the union of their intervals, clipped to the window; with
    ``inherited=False`` only the operations that carry the scope
    themselves.  A scope no operation runs under is left out."""
    _, lo, hi = _window(st.trace, window_span)
    out: Dict[str, float] = defaultdict(float)
    n = len(st.device_scoped) or 1
    for ops in st.device_scoped.values():
        for scope in scopes:
            iv = [(max(o.event.start, lo), min(o.event.end, hi))
                  for o in ops if o.scope == scope
                  and (inherited or not o.inherited)
                  and o.event.end > lo and o.event.start < hi]
            if iv:
                out[scope] += _seconds(iv) / n
    return dict(out)


def step_positions(st: SpanTrace, window_span: str = WINDOW_SPAN
                   ) -> Tuple[float, float]:
    """The cache positions the decode steps that start in the window attend
    over, and those ``pool.gather`` materializes for them (the
    ``serve.step`` spans' ``valid`` and ``capacity``)."""
    _, lo, hi = _window(st.trace, window_span)
    steps = [stats for t, stats in st.steps if lo <= t < hi]
    return (float(sum(s.get("valid", 0) for s in steps)),
            float(sum(s.get("capacity", 0) for s in steps)))


# -- per-layer numbers ------------------------------------------------------

def _per(total: float, count: int) -> float:
    return 1e3 * total / count


def layer_metrics(spans: Mapping[str, float], counts: Mapping[str, int],
                  idle: Mapping[str, float], scopes: Mapping[str, float],
                  busy_s: float,
                  positions: Tuple[float, float] = (0.0, 0.0)
                  ) -> Dict[str, float]:
    """The per-layer numbers the spans, scopes and step positions give; a
    number whose inputs are absent is left out.  ``spans`` are the seconds
    inside the ``fsi.call`` spans (``span_seconds(..., under="fsi.call")``).

    Fleet call, per ``fsi.call``, in ms: ``fsi.prepare_ms`` (partition,
    plans, artifacts and the fleet's device operands), ``fsi.compress_ms``
    (the payloads' zlib streams), ``fsi.channel_ms`` (send, local overlap
    and drain, less compression), ``fsi.apply_ms`` (the layer on the
    device: dispatch, run, readback).  Serving: ``lm.pool_copy_pct`` (the
    device's busy time under the pool's gather and scatter, with the
    copies the compiler inserts on their data), ``lm.kv_valid_pct`` (valid
    over materialized cache positions), ``lm.step_gap_ms`` (device idle
    while the host waits on a step's tokens or dispatches the next, per
    decode step)."""
    s = lambda *names: sum(spans.get(n, 0.0) for n in names)  # noqa: E731
    out: Dict[str, float] = {}
    calls = counts.get("fsi.call")
    if calls:
        prep = s("fsi.partition", "fsi.plans", "fsi.prepare")
        if prep:
            out["fsi.prepare_ms"] = _per(prep, calls)
        if "payload.compress" in spans:
            out["fsi.compress_ms"] = _per(spans["payload.compress"], calls)
        if "fsi.send" in spans:
            out["fsi.channel_ms"] = _per(
                s("fsi.send", "fsi.local", "fsi.recv")
                - spans.get("payload.compress", 0.0), calls)
        if "fsi.apply" in spans:
            out["fsi.apply_ms"] = _per(spans["fsi.apply"], calls)
    copies = [scopes[k] for k in ("serve.pool_gather", "serve.pool_scatter")
              if k in scopes]
    if copies and busy_s > 0:
        out["lm.pool_copy_pct"] = 100.0 * sum(copies) / busy_s
    valid, capacity = positions
    if capacity:
        out["lm.kv_valid_pct"] = 100.0 * valid / capacity
    steps = counts.get("serve.step")
    if steps:
        out["lm.step_gap_ms"] = _per(
            idle.get("serve.token_wait", 0.0) + idle.get("serve.step", 0.0),
            steps)
    return out


def report(trace_dir: str, window_span: str = WINDOW_SPAN) -> dict:
    """Everything above for one kept trace."""
    st = load(trace_dir)
    base = tr.reduce(st.trace, window_span)
    counts = span_count(st.trace, window_span)
    idle = idle_by_span(st.trace, window_span)
    scopes = scope_seconds(st, DEVICE_SCOPES, window_span)
    positions = step_positions(st, window_span)
    ops = [o for plane in st.device_scoped.values() for o in plane]
    return {"window_s": base.window_s, "busy_s": base.busy_s,
            "span_seconds": span_seconds(st.trace, window_span),
            "span_count": counts, "idle_by_span": idle,
            "scope_seconds": scopes,
            "scope_seconds_own": scope_seconds(st, DEVICE_SCOPES,
                                               window_span, inherited=False),
            "ops_scoped": sum(bool(o.scope) for o in ops),
            "ops_inherited": sum(o.inherited for o in ops),
            "ops": len(ops), "step_positions": positions,
            "metrics": layer_metrics(
                span_seconds(st.trace, window_span, under="fsi.call"),
                counts, idle, scopes, base.busy_s, positions)}


if __name__ == "__main__":
    import sys

    print(json.dumps(report(sys.argv[1]), indent=1, sort_keys=True))
