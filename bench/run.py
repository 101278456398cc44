#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are looked up by name
in ``BENCHMARK.json``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number the comparison with the reference made, beside its
limit. The same numbers are the last lines on standard error. Without an
accelerator, or with fewer chips than the cell needs, it prints no result
and exits with code 3.
"""
import os
import sys
import time

# Python salts the hash of every str anew in each process, and the engine
# builds some of its traced programs in the iteration order of sets of
# column names (core/plan.py, the group-by's and the aggregate's
# ``needed``). Each process would then trace other programs than the last
# and miss the compile cache. The run starts again, as the same process,
# with the hash seed fixed: every run traces the same programs.
_T0_VAR = "BENCH_SETUP_T0"
if os.environ.get("PYTHONHASHSEED") != "0":
    os.environ["PYTHONHASHSEED"] = "0"
    os.environ[_T0_VAR] = repr(time.perf_counter())  # CLOCK_MONOTONIC
    sys.stdout.flush()
    sys.stderr.flush()
    os.execv(sys.executable, [sys.executable] + sys.argv)
T_START = float(os.environ.pop(_T0_VAR, time.perf_counter()))

import argparse  # noqa: E402
import json  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
# the TPU runtime otherwise writes its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import NoChip, run_cell

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    except NoChip as exc:
        print(f"bench: {exc}; nothing was measured", file=sys.stderr,
              flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
