#!/usr/bin/env python3
"""Find the knee of an open-loop served cell: the highest offered rate at
which the backlog does not grow over the window and no request fails.

    python3 bench/sweep.py --config ssb-sf2 --traffic serve-open-zipf \\
        --seed <n> --seconds 20 --rates 5,10,15,20

One process: set-up once, then one window per rate on the same server.
For each rate it prints the median and 95th-percentile latency of the
first and the last third of the requests; a backlog that grows shows as a
last third far slower than the first. The mix keeps the rate it finds,
times 0.8, as a number; this tool is not part of a run.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    import harness
    from traffic import make_schedule

    config, module = harness.load_config(
        os.path.join("bench", "configs", args.config + ".json"))
    cell = harness.Cell(name=f"{args.config}.{args.traffic}", chips=1,
                        config=config, module=module,
                        mix=harness.load_mix(args.traffic), end_to_end=[],
                        per_layer=[])
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    from repro.core.partition import PartitionedQuery
    from repro.core.serve import QueryServer

    data = cell.module.generate(args.seed, cell.config)
    table, dims = harness.ingest(data, cell.config)
    stages = cell.module.templates(harness.engine_api())
    names = list(cell.config["templates"])

    def stage(name):
        return stages[name](PartitionedQuery(table), dims)

    server = QueryServer(table, budget_bytes=table.nbytes())
    for n in names:
        server.result(server.submit(stage(n)))
    for rate in [float(r) for r in args.rates.split(",")]:
        run = harness.Run(cell=cell, table=table, dims=dims)
        sched = make_schedule(names, dict(cell.mix, rate_qps=rate),
                              args.seconds, args.seed)
        t0 = time.perf_counter()
        harness.serve_window(run, server, stage, sched,
                             lambda name: harness.NO_SPAN)
        lat = np.asarray(sorted((r["due"], r["latency_ms"])
                                for r in run.records))[:, 1]
        third = max(len(lat) // 3, 1)
        first, last = lat[:third], lat[-third:]
        print(json.dumps({
            "rate_qps": rate, "requests": len(lat), "failed": run.failed,
            "p50_first_ms": float(np.median(first)),
            "p95_first_ms": float(np.percentile(first, 95)),
            "p50_last_ms": float(np.median(last)),
            "p95_last_ms": float(np.percentile(last, 95)),
            "wall_s": time.perf_counter() - t0,
            "notes": run.notes[-1:]}), flush=True)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
