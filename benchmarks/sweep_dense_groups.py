#!/usr/bin/env python3
"""Dense small-table reductions against XLA's scatter and gather, on the
chip this process finds: where ``dispatch.G_DENSE`` comes from.

    PYTHONPATH=src python3 benchmarks/sweep_dense_groups.py [--rows 1048576]

For each table size G it times, at ``--rows`` rows, two row-sized
scatters of a group-by in both forms: an int32 segment sum (COUNT,
``unique_bounded``'s presence count) and an int32 segment min (the
representative row). Times
are device times of each program, from a profiler trace, median of
``--reps`` calls. Prints one JSON object per line; exits 3 without an
accelerator.
"""
import argparse
import glob
import json
import os
import sys
import tempfile

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _forms(g):
    import jax.numpy as jnp

    from repro.kernels import dispatch

    big = jnp.iinfo(jnp.int32).max
    return {
        "sum.scatter": lambda v, ids: jnp.zeros((g,), jnp.int32).at[
            ids].add(v, mode="drop"),
        "sum.dense": lambda v, ids: dispatch._dense_segment_reduce(
            "sum", v, ids, g, 0),
        "min.scatter": lambda v, ids: jnp.full((g,), big, jnp.int32).at[
            ids].min(v, mode="drop"),
        "min.dense": lambda v, ids: dispatch._dense_segment_reduce(
            "min", v, ids, g, big),
    }


def _module_times(trace_dir):
    """Device time of each jitted program, in ms, by module name."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    name = ev.name.split("(")[0]
                    out.setdefault(name, []).append(ev.duration_ns / 1e6)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--groups", default="8,64,512,1024,2048,4096")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        print("sweep: no accelerator; nothing was measured", file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    progs = {}
    for g in (int(x) for x in args.groups.split(",")):
        # ids in [0, G]: G is the drop slot padding rows carry
        ids = jnp.asarray(rng.integers(0, g + 1, args.rows, dtype=np.int32))
        v = jnp.arange(args.rows, dtype=jnp.int32)
        for form, fn in _forms(g).items():
            name = f"{form.replace('.', '_')}_g{g}"
            fn.__name__ = name
            f = jax.jit(fn)
            f(v, ids).block_until_ready()  # compile outside the trace
            progs[name] = (form, g, f, (v, ids))
        # the two forms agree exactly
        for op in ("sum", "min"):
            a, b = [np.asarray(f(*xs)) for n, (fo, gg, f, xs) in progs.items()
                    if gg == g and fo.startswith(op + ".")]
            assert np.array_equal(a, b), (op, g)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for form, g, f, xs in progs.values():
            for _ in range(args.reps):
                f(*xs).block_until_ready()
        jax.profiler.stop_trace()
        times = _module_times(d)
    dev = jax.devices()[0]
    for name, (form, g, f, xs) in progs.items():
        ms = times.get(f"jit_{name}", [])
        print(json.dumps({"form": form, "groups": g, "rows": args.rows,
                          "device_ms_median": float(np.median(ms)) if ms
                          else None, "calls": len(ms),
                          "device_kind": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
