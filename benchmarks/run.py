"""Benchmark harness: one function per paper table/figure + kernel micro-
benches + the roofline table from the dry-run artifacts.

Prints ``name,us_per_call,derived`` CSV (per the repo contract), one
machine-readable ``# summary {json}`` line per bench, and persists JSON
payloads under experiments/results/ for EXPERIMENTS.md and the CI
regression gate (``benchmarks.check_regression``).

Exits nonzero if ANY selected benchmark raises — a failing bench used to
pass silently in CI (the error only went to stderr), letting regressions
ship behind a green check.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--quick] [--only substr]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


BENCHES = [
    ("table2", "benchmarks.paper_experiments", "bench_table2"),
    ("window", "benchmarks.paper_experiments", "bench_window_effect"),
    (
        "acquisition",
        "benchmarks.paper_experiments",
        "bench_acquisition_strategies",
    ),
    ("massive", "benchmarks.paper_experiments", "bench_massive_cascade"),
    ("kernels", "benchmarks.kernel_bench", "bench_kernels"),
    ("edge_loop", "benchmarks.edge_loop_bench", "bench_edge_loop"),
    ("massive_fleet", "benchmarks.edge_loop_bench", "bench_massive_fleet"),
    ("comms", "benchmarks.edge_loop_bench", "bench_comms_sweep"),
    ("hetero", "benchmarks.bench_hetero", "bench_hetero"),
    ("async", "benchmarks.bench_async", "bench_async"),
    ("faults", "benchmarks.bench_faults", "bench_faults"),
    ("topology", "benchmarks.bench_topology", "bench_topology"),
    ("stream", "benchmarks.bench_stream", "bench_stream"),
    ("lm", "benchmarks.bench_lm", "bench_lm"),
    ("fused_agg", "benchmarks.bench_fused_agg", "bench_fused_agg"),
    ("roofline", "benchmarks.roofline", "bench_roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--quick", action="store_true", help="reduced repeats/sizes (CI-sized run)"
    )
    ap.add_argument("--only", default=None, help="substring filter on bench name")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs("experiments/results", exist_ok=True)
    failed = []
    print("name,us_per_call,derived")
    for name, mod_name, fn_name in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        summary = {"bench": name, "status": "ok"}
        try:
            import importlib
            fn = getattr(importlib.import_module(mod_name), fn_name)
            rows, payload = fn(quick=args.quick)
            with open(f"experiments/results/{name}.json", "w") as f:
                json.dump(payload, f, indent=2, default=str)
            for rname, us, derived in rows:
                print(f"{rname},{us:.1f},{derived}")
            summary["rows"] = len(rows)
        except Exception as e:  # noqa: BLE001 — report, continue with the rest
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", file=sys.stderr)
            print(f"{name}/ERROR,0.0,{type(e).__name__}")
            summary.update(status="error", error=f"{type(e).__name__}: {e}")
        summary["seconds"] = round(time.time() - t0, 1)
        print(f"# summary {json.dumps(summary)}", flush=True)
    if failed:
        print(f"# FAILED benches: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
