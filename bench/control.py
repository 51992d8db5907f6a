#!/usr/bin/env python3
"""Read a cell's correctness numbers for the program and for its control,
seed by seed, in one process, to set the cell's limit from.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--units 1]

For each seed: set the cell up from that seed, run ``--units`` units of
its traffic through the program (a short window at the cell's own load and
sizes), and read the number the run compares (``program``) and the same
number for the control put in the program's place, on the same sample: the
``control(cell)`` of the configuration kind's module under
``bench/systems/``.

Prints one JSON line per seed.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run_cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config, mix, limits = run_cell.load_cell(bench, wl)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    system = importlib.import_module(f"bench.systems.{config['kind']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = system.Cell(config, mix, seed, jax.profiler.TraceAnnotation)
        cell.setup()
        for _ in range(args.units):
            cell.run_unit()
        cell.release()
        line = {"workload": args.workload, "seed": seed,
                "failed": cell.failed}
        for c in cell.check(limits):
            line["program"] = c["value"]
            line["limit"] = c["limit"]
        line.update(system.control(cell))
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
