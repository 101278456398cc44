"""Benchmark aggregator: ``PYTHONPATH=src python -m benchmarks.run``.

One harness per paper table/figure (DESIGN.md §8) + the roofline analysis.
``--quick`` shrinks row counts ~4x for CI-speed runs.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args(argv)
    q = args.quick

    from repro import compile_cache
    print(f"compile cache: {compile_cache.configure()}")

    from benchmarks import (bench_and_design, bench_bi, bench_compress,
                            bench_compression_quality, bench_groupby,
                            bench_memory, bench_orderby, bench_outofcore,
                            bench_primitives, bench_production,
                            bench_roofline, bench_serving, bench_skew,
                            bench_stream, bench_tpch)

    benches = {
        "groupby": lambda: bench_groupby.run(n=300_000 if q else 10_000_000),
        "orderby": lambda: bench_orderby.run(n=300_000 if q else 10_000_000),
        "compress": lambda: bench_compress.run(n=300_000 if q else 2_000_000),
        "stream": lambda: bench_stream.run(n=300_000 if q else 2_000_000),
        "faults": lambda: bench_stream.chaos(n=300_000 if q else 1_000_000),
        "serving": lambda: bench_serving.run(n=300_000 if q else 2_000_000),
        "primitives": lambda: bench_primitives.run(
            sizes=(10_000, 100_000, 500_000) if q else
            (10_000, 100_000, 1_000_000, 4_000_000)),
        "and_design": lambda: bench_and_design.run(n=500_000 if q else 2_000_000),
        "tpch": lambda: bench_tpch.run(n=500_000 if q else 2_000_000),
        "outofcore": lambda: bench_outofcore.run(n=500_000 if q else 2_000_000),
        "compression_quality": lambda: bench_compression_quality.run(
            n=500_000 if q else 2_000_000),
        "production": lambda: bench_production.run(n=800_000 if q else 3_000_000),
        "bi": lambda: bench_bi.run(n=300_000 if q else 1_000_000),
        "skew": lambda: bench_skew.run(n=500_000 if q else 2_000_000),
        "memory": lambda: bench_memory.run(n=500_000 if q else 2_000_000),
        "roofline": lambda: bench_roofline.run("single"),
    }
    only = set(args.only.split(",")) if args.only else None
    failures = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        print(f"\n=== {name} ===")
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"  FAILED: {e!r}")
        print(f"  ({time.perf_counter() - t0:.1f}s)")
    if failures:
        print("\nFAILED BENCHES:", failures)
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
