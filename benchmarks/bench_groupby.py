"""Group-by grouping-path benchmark: sort-free scatter vs argsort unique.

The paper's §7 observation is that the unique/sort dominates a group-by;
DESIGN.md §5's sort-free path removes the sort entirely when the key is a
dictionary code (dense bounded domain). This harness measures both the
isolated grouping stage and the end-to-end query on a dictionary-keyed
table, for the row-level (high-entropy Plain codes) and run-level (sorted
RLE codes) paths, and emits a machine-readable
``artifacts/bench/BENCH_groupby.json`` so the perf trajectory is tracked
PR over PR.
"""
from __future__ import annotations

import json
import os

import numpy as np
import jax

from repro.core import compress
from repro.core import groupby as G
from repro.core.plan import Query, col
from repro.core.table import Table
from repro.kernels import dispatch
from benchmarks.common import ART_DIR, device_info, time_fn

N_KEYS = 1000  # dictionary cardinality
NUM_GROUPS_CAP = 1024


def _tables(rng, n):
    """Dictionary-keyed tables: codes over a N_KEYS-entry string dictionary
    (pre-encoded, as partitioned ingest would hand them over)."""
    vocab = np.array([f"key_{i:04d}" for i in range(N_KEYS)])
    cfg = compress.CompressionConfig(plain_threshold=1000)
    v = rng.random(n).astype(np.float32)
    out = {}
    # high-entropy codes -> Plain encoding, row-level grouping path
    codes = rng.integers(0, N_KEYS, n).astype(np.int32)
    out["dict-plain"] = Table.from_arrays(
        {"k": codes, "v": v}, cfg=cfg, dictionaries={"k": vocab})
    # sorted codes -> RLE encoding, run-level (hybrid) grouping path
    out["dict-rle"] = Table.from_arrays(
        {"k": np.sort(codes), "v": v}, cfg=cfg, dictionaries={"k": vocab})
    return out


def _grouping_only(table, use_domains: bool):
    """Jitted align+grouping stage (no aggregation), per path."""
    doms = dict(table.domains) if use_domains else None

    @jax.jit
    def fn(columns):
        view = G.align_columns({"k": columns["k"]})
        gid, num_groups, _ = G.grouping(view, ["k"], NUM_GROUPS_CAP,
                                        key_domains=doms)
        return gid, num_groups
    return lambda: fn(table.columns)


def _query(table):
    return (Query(table)
            .filter(col("v") > 0.25)
            .groupby(["k"], {"s": ("sum", "v"), "c": ("count", None)},
                     num_groups_cap=NUM_GROUPS_CAP))


def run(n=10_000_000, out_name="BENCH_groupby.json"):
    rng = np.random.default_rng(7)
    tables = _tables(rng, n)
    entries = []
    results = {}
    for enc, t in tables.items():
        assert t.domains["k"] == (0, N_KEYS)
        for path, sort_free in (("sort_free", True), ("argsort", False)):
            with dispatch.overrides(enable_sort_free=sort_free):
                ms_group = time_fn(_grouping_only(t, use_domains=sort_free),
                                   warmup=1, iters=5) * 1e3
                q = _query(t)
                ms_query = time_fn(lambda: q.run(), warmup=1, iters=3) * 1e3
            for stage, ms in (("grouping", ms_group), ("query", ms_query)):
                entries.append({"rows": n, "encoding": enc, "path": path,
                                "stage": stage, "median_ms": round(ms, 3)})
                results[(enc, path, stage)] = ms
            print(f"  {enc:>10s} | {path:>9s} | grouping {ms_group:9.2f} ms"
                  f" | query {ms_query:9.2f} ms")

    def speedup(enc, stage):
        return results[(enc, "argsort", stage)] / results[(enc, "sort_free",
                                                           stage)]

    report = {
        "bench": "groupby_sortfree",
        **device_info(),
        "rows": n,
        "dict_cardinality": N_KEYS,
        "num_groups_cap": NUM_GROUPS_CAP,
        "entries": entries,
        "speedup_sort_free_grouping": round(speedup("dict-plain", "grouping"), 3),
        "speedup_sort_free_query": round(speedup("dict-plain", "query"), 3),
        "speedup_sort_free_grouping_rle": round(
            speedup("dict-rle", "grouping"), 3),
    }
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, out_name)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"[bench_groupby] sort-free grouping speedup "
          f"{report['speedup_sort_free_grouping']:.2f}x (row-level), "
          f"{report['speedup_sort_free_grouping_rle']:.2f}x (run-level)"
          f" -> {path}")
    return report


if __name__ == "__main__":
    run()
