"""Reduce a JAX profiler trace to what the per-layer metrics read.

A trace holds planes: one per device (``/device:TPU:<n>``), whose lines
hold the device's operations (``XLA Ops``) and programs (``XLA Modules``),
and the host (``/host:CPU``), whose lines are threads holding host spans
(the benchmark's ``TraceAnnotation`` spans among them).  Times are in
nanoseconds on one clock.

:func:`reduce` clips everything to the window, the host span the harness
opens around the measured work, and gives:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
* ``op_seconds`` / ``module_seconds``: device time summed by operation
  (named ``name (opcode)``, see :func:`short_name`) and by program name;
* ``idle_by_host``: the device's idle gaps in the window, each named by
  the innermost host span that covered its middle on the window's thread.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclasses.dataclass
class Trace:
    """The parts of a trace the reduction reads; tests build one by hand."""

    device_ops: Dict[str, List[Event]]        # device plane -> ops
    device_modules: Dict[str, List[Event]]    # device plane -> programs
    host_threads: Dict[str, List[Event]]      # host thread -> spans


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]
    module_seconds: Dict[str, float]
    idle_by_host: Dict[str, float]
    n_devices: int

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_total(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches ``pattern``
        (a regular expression, searched), summed over devices."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds.items() if rx.search(n))

    def module_total(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.module_seconds.items() if rx.search(n))

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[n, s] for n, s in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.op_seconds),
                "idle_gaps": head(self.idle_by_host)}


def load(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    ops, modules, host = {}, {}, {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line.events, short=True)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line.events)
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                host[f"{line.name}#{i}"] = _events(line.events)
    return Trace(ops, modules, host)


def _events(events: Iterable, short: bool = False) -> List[Event]:
    name = short_name if short else (lambda n: n)
    return [Event(name(e.name), float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns)) for e in events]


_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def short_name(hlo: str) -> str:
    """``name (opcode)`` of a device operation the trace names by its whole
    HLO instruction (``%fusion.88 = (bf16[..], ..) fusion(...), ...``); a
    custom call also gets its target.  Other names pass through."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    rest = rest.lstrip()
    if rest.startswith("("):          # a tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    op = m.group(1) if m else "?"
    t = _TARGET.search(rest)
    if op == "custom-call" and t:
        op = f"custom-call {t.group(1)}"
    return f"{lhs.strip().lstrip('%')} ({op})"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``busy`` (disjoint, sorted) leaves
    uncovered."""
    out, t = [], lo
    for a, b in busy:
        if b <= lo or a >= hi:
            continue
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def find_span(trace: Trace, name: str) -> Tuple[str, Event]:
    """The host thread and the (first) span called ``name``."""
    for thread, events in trace.host_threads.items():
        for e in events:
            if e.name == name:
                return thread, e
    raise KeyError(f"no host span {name!r} in the trace")


def innermost(events: Sequence[Event],
              times: Sequence[float]) -> List[Optional[str]]:
    """For each time, the name of the innermost span of one thread that
    covers it (spans of one thread nest), or ``None``."""
    evs = sorted((e for e in events if e.end > e.start),
                 key=lambda e: (e.start, -e.end))
    out: List[Optional[str]] = [None] * len(times)
    stack: List[Event] = []
    j = 0
    for i in sorted(range(len(times)), key=lambda i: times[i]):
        t = times[i]
        while j < len(evs) and evs[j].start <= t:
            while stack and stack[-1].end < evs[j].start:
                stack.pop()
            stack.append(evs[j])
            j += 1
        while stack and stack[-1].end < t:
            stack.pop()
        out[i] = stack[-1].name if stack else None
    return out


def reduce(trace: Trace, window_span: str) -> Reduced:
    thread, win = find_span(trace, window_span)
    lo, hi = win.start, win.end
    if not trace.device_ops:
        raise ValueError("the trace holds no device operations")
    busy_total = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    mod_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    host = trace.host_threads[thread]
    for plane, events in trace.device_ops.items():
        ops = _clip(events, lo, hi)
        busy = union((e.start, e.end) for e in ops)
        busy_total += sum(b - a for a, b in busy)
        for e in ops:
            op_s[e.name] += (e.end - e.start) * 1e-9
        idle_gaps = gaps(busy, lo, hi)
        names = innermost(host, [(a + b) / 2 for a, b in idle_gaps])
        for (a, b), who in zip(idle_gaps, names):
            idle[who or "no host span"] += (b - a) * 1e-9 / len(
                trace.device_ops)
    for plane, events in trace.device_modules.items():
        for e in _clip(events, lo, hi):
            mod_s[e.name] += (e.end - e.start) * 1e-9
    n = len(trace.device_ops)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   op_seconds=dict(op_s), module_seconds=dict(mod_s),
                   idle_by_host=dict(idle), n_devices=n)


def summarize(trace_dir: str, top: int = 25) -> str:
    """Every plane and line of a trace with its event count and its most
    frequent event names: for looking at a trace by hand."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = []
    for f in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        pd = ProfileData.from_file(str(f))
        for plane in pd.planes:
            out.append(f"PLANE {plane.name}")
            for line in plane.lines:
                evs = list(line.events)
                names = Counter(e.name for e in evs)
                dur = Counter()
                for e in evs:
                    dur[e.name] += e.duration_ns
                out.append(f"  LINE {line.name!r}: {len(evs)} events")
                for n, d in dur.most_common(top):
                    out.append(f"    {d / 1e6:12.3f} ms  x{names[n]:<6d} {n}")
                if evs:
                    e = evs[0]
                    stats = {k: str(v)[:80] for k, v in e.stats}
                    out.append(f"    first: {e.name} @{e.start_ns} "
                               f"+{e.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summarize(sys.argv[1]))
