#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration file (``bench/configs/``), the traffic mix
(``bench/traffic/<mix>.json``), the limits of its correctness check
(``bench/limits/<workload>.json``), the module that runs the configuration's
kind (``bench/systems/<kind>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).

A run: turns on JAX's persistent compilation cache (``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another; every program cached,
however quickly it compiled); makes the weights and
inputs from ``--seed``; warms every shape the traffic can draw; then runs
whole units of work (an offline batch, or one fleet call) back to back
until ``--seconds`` have passed, the unit in flight finishing and counting.
``--trace 1`` runs a short window of its own (at least one unit), traces
a slice of it (the whole window, or the decode steps the mix names under
``trace_steps``) and reports the per-layer metrics and a breakdown instead
of the end-to-end ones.  After the window the device's peak memory is
read, the program's state is freed and the window's outputs are checked
against the plain reference.  The compiles counted inside the window are
printed on a line before the result; the numbers compared are printed
with their limits as the last lines on standard error and under
``checks`` in the result.

The last line of standard output is the result, one JSON object.  With no
TPU, or fewer chips than the cell asks for, or without the repository's
``src/`` beside ``bench/``, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_SECONDS = 3.0      # the traced run's window (at least one unit)
WINDOW_SPAN = "bench.window"


class CompileClock:
    """Seconds JAX spends before a program runs and how many programs it
    compiles, from ``jax.monitoring``: tracing and lowering, the backend
    compile (a persistent-cache read on a hit) and the cache's hits and
    misses."""

    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace_lower",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "trace_lower",
                 "/jax/core/compile/backend_compile_duration":
                     "backend_compile"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.seen = dict.fromkeys(("trace_lower", "backend_compile",
                                   "compiles", "cache_hits",
                                   "cache_misses"), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.DURATIONS:
            self.seen[self.DURATIONS[event]] += duration
            if event == "/jax/core/compile/backend_compile_duration":
                self.seen["compiles"] += 1

    def _on_event(self, event, **_):
        if event in self.COUNTS:
            self.seen[self.COUNTS[event]] += 1

    def snapshot(self) -> dict:
        return dict(self.seen)


def _load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def _reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, e2e: str):
    """(end-to-end entries, per-layer entries) this cell reports: a metric
    that lists its cells where this one is listed, any other end-to-end
    metric, and any other per-layer metric that moves ``e2e``."""
    def listed(m, otherwise: bool) -> bool:
        return workload in m["workloads"] if "workloads" in m else otherwise

    ends = [m for m in bench["end_to_end"] if listed(m, True)]
    layers = [m for m in bench["per_layer"]
              if listed(m, m["moves"] == e2e)]
    return ends, layers


def run_window(cell, seconds: float):
    """Whole units back to back until ``seconds`` have passed; the unit in
    flight finishes and counts.  Returns (work, elapsed seconds, the
    seconds each unit took)."""
    work, ends = 0.0, []
    t0 = time.perf_counter()
    while True:
        work += cell.run_unit()
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= seconds:
            break
    return work, ends[-1], [b - a for a, b in zip([0.0] + ends, ends)]


def load_cell(bench: dict, wl: dict):
    """(configuration, traffic mix, limits) of a workload entry."""
    from bench import traffic_gen

    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return (_load_json(ROOT / entry["file"]),
            traffic_gen.load_mix(wl["traffic"]),
            _load_json(BENCH / "limits" / f"{wl['name']}.json"))


def run(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, trace_dir: Optional[str] = None,
        out=sys.stdout, load=load_cell, peaks: Optional[dict] = None) -> int:
    """One run of ``workload``; returns the exit code.  ``require_tpu``,
    ``load`` and ``peaks`` let the tests drive a run on the CPU at a small
    size."""
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        print(f"run_cell: no workload {workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    config, mix, limits = load(bench, wl)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"run_cell: the repository's src/ is not beside bench/ ({e})",
              file=sys.stderr)
        return 2
    if not Path(compile_cache.__file__).resolve().is_relative_to(ROOT / "src"):
        print(f"run_cell: imported repro from {compile_cache.__file__}, "
              f"not from {ROOT / 'src'}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run_cell: no TPU found (JAX platform: {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < int(wl["chips"]):
        print(f"run_cell: the cell needs {wl['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    if trace and peaks is None:
        table = _load_json(BENCH / "peaks.json")
        peaks = table.get(devices[0].device_kind)
        if peaks is None:
            print(f"run_cell: no peaks for device kind "
                  f"{devices[0].device_kind!r} in bench/peaks.json",
                  file=sys.stderr)
            return 1
    cache_dir = compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = CompileClock()

    system = importlib.import_module(f"bench.systems.{config['kind']}")
    spans = jax.profiler.TraceAnnotation
    cell = system.Cell(config, mix, seed, spans)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    at_setup = clock.snapshot()

    counters0 = cell.counters()
    tracer = None
    if trace:
        tracer = Tracer(trace_dir or tempfile.mkdtemp(prefix="bench-trace-"))
        cell.arm_trace(tracer)
    try:
        work, elapsed, unit_s = run_window(
            cell, min(seconds, TRACE_SECONDS) if trace else seconds)
    finally:
        if tracer is not None:
            tracer.end()
    in_window = {k: v - at_setup[k] for k, v in clock.snapshot().items()}
    counters = {k: v - counters0.get(k, 0.0)
                for k, v in cell.counters().items()}
    print(json.dumps({"workload": workload, "seed": seed,
                      "compile_cache": cache_dir,
                      "setup_compile": at_setup,
                      "window_compiles": in_window["compiles"],
                      "window_cache_misses": in_window["cache_misses"],
                      "window_units": len(unit_s), "window_s": elapsed,
                      "unit_s": unit_s}),
          file=out, flush=True)
    print(f"run_cell: {in_window['compiles']} compiles inside the window",
          file=sys.stderr, flush=True)

    dev = devices[: int(wl["chips"])]
    stats = [d.memory_stats() or {} for d in dev]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}

    ends, layers = cell_metrics(bench, workload, cell.e2e_metric)
    metrics, breakdown = {}, None
    if trace:
        from bench import trace_reduce

        if tracer.t0 is None:
            print("run_cell: the traced slice never began", file=sys.stderr)
            return 1
        reduced = trace_reduce.reduce(trace_reduce.load(tracer.directory),
                                      WINDOW_SPAN)
        if trace_dir is None:
            shutil.rmtree(tracer.directory, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        ctx = _Context(trace=reduced, counts=cell.trace_counts(),
                       counters=counters, window_s=tracer.seconds,
                       peaks=peaks)
        for m in layers:
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in ends:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == cell.e2e_metric:
                value = work / elapsed
            else:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    cell.release()
    checks = cell.check(limits)
    correct = (cell.failed == 0
               and all(c["value"] <= c["limit"] for c in checks))
    result = {"correct": correct, "attempted": cell.attempted,
              "failed": cell.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0


class Tracer:
    """The profiler over one slice of the window.  ``begin`` starts it and
    opens the window span, ``end`` closes both; each acts once.  A cell
    calls them where its slice starts and ends (between two program
    steps), or the harness calls them around the whole window."""

    def __init__(self, directory: str):
        self.directory = directory
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._span = None

    @property
    def active(self) -> bool:
        return self.t0 is not None and self.t1 is None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def begin(self) -> None:
        import jax

        if self.t0 is not None:
            return
        jax.profiler.start_trace(self.directory)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def end(self) -> None:
        import jax

        if not self.active:
            return
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


class _Context:
    """What a per-layer metric's reader gets: the reduced trace, the work
    the traced slice needed (``counts``), the program's counters over the
    whole window, the slice's host seconds and the device's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace here (for reading it by hand)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    bench = _load_json(ROOT / "BENCHMARK.json")
    try:
        return run(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), trace_dir=args.trace_dir)
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
