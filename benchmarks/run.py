"""Benchmark harness: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke] [--json F]

Prints ``name,us_per_call,derived`` CSV rows (plus '#' commentary lines).
Exits nonzero if ANY bench raises (each failure still prints its traceback
and an ERROR row, so one rotten bench cannot hide behind the others).

``--smoke``: fast verbose-off mode for CI — sets REPRO_BENCH_SMOKE=1
(benchmarks.common trims timing repeats) and implies --quiet.  Smoke
numbers are NOT representative timings; the mode exists so every scenario
bench is executed on every push and cannot silently rot.

``--json FILE``: additionally persist every row as
``{"rows": {name: {"us_per_call": ..., "derived": ...}}, "failed": [...]}``
— CI's bench-smoke job uploads this as an artifact and gates it against the
committed baseline via ``tools/perf_compare.py`` (the perf trajectory).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

BENCHES = [
    "bench_pareto",            # Fig 2
    "bench_cost_savings",      # Fig 3
    "bench_edge_cloud",        # Fig 4a
    "bench_gpu_rental",        # Fig 4b + Tables 4/5
    "bench_api_cost",          # Fig 5 + Table 1
    "bench_threshold",         # Fig 6 (App B)
    "bench_selection_rate",    # Fig 7 (App C)
    "bench_parallelization",   # Fig 8 (App E.1)
    "bench_kernels",           # kernels micro-bench
    "bench_serving",           # live cascade serving (Table 5 counterpart)
]


def parse_rows(block: str) -> dict:
    """``name,us_per_call,derived`` lines -> {name: {us_per_call, derived}}
    ('#' commentary lines and malformed rows are skipped; derived keeps any
    embedded commas intact via maxsplit)."""
    rows = {}
    for line in str(block).splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split(",", 2)
        if len(parts) < 2:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows[parts[0]] = {
            "us_per_call": us,
            "derived": parts[2] if len(parts) > 2 else "",
        }
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="persist rows to FILE for the perf_compare gate")
    args = ap.parse_args()
    if args.smoke:
        # must land in the environment BEFORE bench modules import
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        args.quiet = True

    names = [b for b in BENCHES if args.only is None or args.only in b]
    print("name,us_per_call,derived")
    failed = []
    results = {}
    for name in names:
        try:
            # import inside the guard: an import-time failure is just as
            # much a rotten bench as a run()-time one
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            row = mod.run(verbose=not args.quiet)
            print(row, flush=True)
            results.update(parse_rows(row))
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},nan,ERROR", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": results, "failed": failed}, f, indent=2, sort_keys=True)
            f.write("\n")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
