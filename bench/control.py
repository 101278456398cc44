#!/usr/bin/env python3
"""Read a cell's control at the cell's own size: the float64 reference
recomputed with every input and intermediate rounded to bfloat16, put in
the program's place for the requests of the cell's window, and judged by
the comparison that decides ``correct``. It has to read ``correct``
false.

    python3 bench/control.py --workload tpch-stream-scan --seeds 1,2,3

Numpy only; it prints one JSON line per seed: the window's checks, the
verdict, and each template's ``max_rel_err`` on its own.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def window_templates(cell, seed: int, seconds: float):
    """The templates of the window's requests, in its order: a closed
    loop's first whole cycle, or an open loop's schedule."""
    from traffic import make_order, make_schedule

    names = list(cell.config["templates"])
    if cell.mix["executor"] == "stream":
        return make_order(names, cell.mix, seed)[:len(names)]
    return [n for _, n in make_schedule(names, cell.mix, seconds, seed)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import check
    import harness

    bench = harness.load_benchmark()
    cell = harness.resolve(bench, args.workload)
    limits = cell.config["check"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        data = cell.module.generate(seed, cell.config)
        templates = window_templates(cell, seed, bench["run_seconds"])
        checks = check.control(cell.module, data, templates, limits)
        per_template = {
            t: check.control(cell.module, data, [t], limits)
            ["max_rel_err"]["value"] for t in sorted(set(templates))}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": check.verdict(checks),
                          "per_template": per_template,
                          "seconds": time.perf_counter() - t0,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
