"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV: ``us_per_call`` is the benchmark's
own wall time per simulated query/cell (µs) where meaningful, ``derived`` is
the table's headline quantity (cost, volume ratio, roofline term, …).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json [PATH]]

``--json`` additionally writes every row (plus run metadata) to
``BENCH_fsi.json`` — per-backend µs/query for the FSI channel and SpMM
roofline benches — so subsequent PRs have a perf trajectory to diff against.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def _emit(rows, sink=None):
    for row in rows:
        if sink is not None:
            sink.append(dict(row))
        row = dict(row)
        name = row.pop("name")
        us = row.pop("per_sample_ms", None)
        us = us * 1e3 if us is not None else row.pop("us_per_call", "")
        derived = ";".join(f"{k}={v}" for k, v in row.items())
        print(f"{name},{us},{derived}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller configs (CI-sized)")
    ap.add_argument("--paper-scale", action="store_true",
                    help="add the P=64, N=65536 GraphChallenge sharded sweep "
                         "(vmap baseline + fused megakernel rows, with a "
                         "wall-clock budget recorded in the row)")
    ap.add_argument("--json", nargs="?", const="BENCH_fsi.json", default=None,
                    metavar="PATH",
                    help="also write all rows to PATH (default BENCH_fsi.json)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        bench_cost_model,
        bench_fsi_channels,
        bench_launch,
        bench_partitioning,
        bench_roofline,
        bench_sporadic,
    )

    sink = [] if args.json else None
    print("name,us_per_call,derived")
    t0 = time.time()
    if args.quick:
        _emit(bench_fsi_channels.run(neurons=256, layers=12, batch=32,
                                     workers=(2, 4, 8),
                                     sharded_cases=((64, 1024, 4, 16),),
                                     paper_scale=args.paper_scale),
              sink)
        _emit(bench_partitioning.run(neurons=512, layers=12, batch=16, P=8), sink)
        _emit(bench_cost_model.run(neurons=256, layers=12, batch=32, P=4), sink)
        _emit(bench_sporadic.run(neurons=256, layers=12, batch=32), sink)
        _emit(bench_roofline.run(neurons=256, batch=32), sink)
    else:
        _emit(bench_fsi_channels.run(paper_scale=args.paper_scale), sink)
        _emit(bench_partitioning.run(), sink)
        _emit(bench_cost_model.run(), sink)
        _emit(bench_sporadic.run(), sink)
        _emit(bench_roofline.run(), sink)
    _emit(bench_launch.run(), sink)
    wall = time.time() - t0
    if args.json:
        from benchmarks.check_schema import SCHEMA_VERSION

        payload = {
            "meta": {
                "schema_version": SCHEMA_VERSION,
                "quick": args.quick,
                "paper_scale": args.paper_scale,
                "wall_s": round(wall, 2),
                "python": platform.python_version(),
                "machine": platform.machine(),
            },
            "rows": sink,
        }
        with open(args.json, "w") as f:
            # numpy scalars → native JSON numbers (not strings), so future
            # PRs can diff the trajectory numerically
            json.dump(payload, f, indent=1,
                      default=lambda o: o.item() if hasattr(o, "item") else str(o))
        print(f"# wrote {len(sink)} rows to {args.json}", file=sys.stderr)
    print(f"# total benchmark wall time: {wall:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
