"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows on stdout and writes the same
rows as a machine-readable perf record to ``BENCH_results.json`` (override
the path with ``BENCH_JSON=...``) — the artifact CI uploads so the bench
trajectory is tracked across commits.

    PYTHONPATH=src python -m benchmarks.run            # full
    BENCH_FAST=1 PYTHONPATH=src python -m benchmarks.run   # reduced domains
"""
from __future__ import annotations

import json
import os
import platform
import sys
import traceback


def main() -> None:
    print("name,us_per_call,derived")
    from . import (
        bench_dispatch,
        bench_emit_space,
        bench_fig11_loop_exchange,
        bench_fig12_degree_switch,
        bench_fig13_14_combined,
        bench_fleet_service,
        bench_fleet_tune,
        bench_obs_overhead,
        bench_roofline,
        bench_serve_overload,
        bench_serve_stream,
        bench_serve_traffic,
        bench_train_step,
        bench_tune_throughput,
        common,
    )
    from repro.launch import use_compile_cache  # common put src on the path

    use_compile_cache()

    failures = []
    for mod in (
        bench_fig11_loop_exchange,
        bench_fig12_degree_switch,
        bench_fig13_14_combined,
        bench_roofline,
        bench_serve_traffic,
        bench_serve_stream,
        bench_serve_overload,
        bench_tune_throughput,
        bench_emit_space,
        bench_fleet_tune,
        bench_fleet_service,
        bench_train_step,
        bench_dispatch,
        bench_obs_overhead,
    ):
        try:
            mod.run()
        except Exception as e:  # a failing table must not hide the others
            failures.append(f"{mod.__name__}: {type(e).__name__}: {e}")
            print(f"{mod.__name__},0.0,ERROR={type(e).__name__}:{e}")
            traceback.print_exc()

    import jax

    record = {
        "schema_version": 1,
        "fast": common.FAST,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "rows": common.RESULTS,
        "failures": failures,
    }
    path = os.environ.get("BENCH_JSON", "BENCH_results.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {len(common.RESULTS)} rows to {path}", file=sys.stderr)
    if failures or not common.RESULTS:
        # the perf record exists but the trajectory is broken — fail CI
        print(f"{len(failures)} benchmark module(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
