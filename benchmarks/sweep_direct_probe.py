#!/usr/bin/env python3
"""The PK-FK probe's two routes, on the chip this process finds: the
direct-address map against the sorted build side's binary search, where
``join.DIRECT_MAP_MAX_SLOTS`` is checked.

    PYTHONPATH=src python3 benchmarks/sweep_direct_probe.py [--rows 1048576]

Each case is a fact-key domain of ``slots`` keys, of which ``keys``
survive on the dimension side (SSB's four sides at SF 2, then half of
2^19 to 2^22 slots). It times ``join.pk_fk_join`` over a Plain column of
``--rows`` keys drawn from the domain, gathering one int32 payload, on
both routes. Times are device times of each program, from a profiler
trace, median of ``--reps`` calls. Prints one JSON object per line;
exits 3 without an accelerator.
"""
import argparse
import json
import os
import sys
import tempfile

import numpy as np

from sweep_dense_groups import _module_times

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# (name, first key, slots, surviving keys): supplier, customer of one
# region, date (yyyymmdd, unfiltered), part of MFGR#1 or #2
SSB = [("supplier", 1, 4000, 4000), ("customer", 1, 60000, 12000),
       ("date", 19920101, 60702, 2556), ("part", 1, 400000, 160000)]


def _cases(extra):
    return SSB + [(f"half_{s}", 0, s, s // 2) for s in extra]


def _sides(rng, lo, slots, n_keys):
    """Both build sides over the same surviving keys and payload."""
    import jax.numpy as jnp

    from repro.core import compress, join as join_mod

    keys = np.sort(rng.choice(slots, n_keys, replace=False)).astype(
        np.int32) + lo
    cap = compress.next_pow2(n_keys + 1, 8)
    pay = np.zeros((cap,), np.int32)
    pay[:n_keys] = rng.integers(0, 1 << 30, n_keys)
    rows = np.full((slots,), -1, np.int32)
    rows[keys - lo] = np.arange(n_keys, dtype=np.int32)
    direct = join_mod.DirectMap(rows=jnp.asarray(rows),
                                lo=jnp.asarray(lo, jnp.int32),
                                hi=jnp.asarray(lo + slots - 1, jnp.int32))
    ordered = jnp.asarray(np.concatenate(
        [keys, np.full((cap - n_keys,), np.iinfo(np.int32).max, np.int32)]))
    n = jnp.asarray(n_keys, jnp.int32)
    return {"direct": (direct, n, {"p": jnp.asarray(pay)}),
            "sorted": (ordered, n, {"p": jnp.asarray(pay)})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--extra", default="524288,1048576,2097152,4194304")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from repro.core import join as join_mod
    from repro.core.encodings import PlainColumn

    if jax.devices()[0].platform == "cpu":
        print("sweep: no accelerator; nothing was measured", file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    extra = [int(x) for x in args.extra.split(",") if x]
    progs = {}
    for case, lo, slots, n_keys in _cases(extra):
        fk = PlainColumn(values=jnp.asarray(
            rng.integers(0, slots, args.rows).astype(np.int32) + lo),
            nrows=args.rows)
        outs = {}
        for route, side in _sides(rng, lo, slots, n_keys).items():
            def fn(c, s):
                return join_mod.pk_fk_join(c, *s)

            name = f"{route}_{case}"
            fn.__name__ = name
            f = jax.jit(fn)
            outs[route] = jax.block_until_ready(f(fk, side))  # compile
            progs[name] = (route, case, slots, n_keys, f, (fk, side))
        # both routes give the same mask and payload, bit for bit
        a, b = (jax.tree_util.tree_leaves(outs[r]) for r in outs)
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b)), case
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for *_, f, xs in progs.values():
            for _ in range(args.reps):
                jax.block_until_ready(f(*xs))
        jax.profiler.stop_trace()
        times = _module_times(d)
    dev = jax.devices()[0]
    for name, (route, case, slots, n_keys, f, xs) in progs.items():
        ms = times.get(f"jit_{name}", [])
        print(json.dumps({"route": route, "case": case, "slots": slots,
                          "keys": n_keys, "rows": args.rows,
                          "device_ms_median": float(np.median(ms)) if ms
                          else None, "calls": len(ms),
                          "device_kind": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
