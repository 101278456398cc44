"""Ordering-subsystem benchmark: entry-level vs row-level top-k, and the
ranked zone-map pruning transfer win (DESIGN.md §10).

Two measurements, both emitted to a machine-readable JSON so the perf
trajectory is tracked PR over PR (like bench_groupby):

  1. **run-level vs row-level top-k** on an RLE dictionary-domain key at
     ``n`` rows: the entry paths (bounded histogram ranks / entry sort)
     rank O(runs) entries, the forced row-level baseline ranks all ``n``
     rows through ``dispatch.topk`` — the compressed-domain ordering claim
     in one number (``speedup_run_level_topk``).
  2. **partitioned ranked transfer counts** with and without ranked
     zone-map pruning on a clustered key: once k candidate rows are held,
     partitions whose key zone map cannot beat the k-th bound are never
     transferred (``transfers_pruned`` vs ``transfers_unpruned``).
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro.core import compress
from repro.core import partition as P
from repro.core.partition import PartitionedQuery, PartitionedTable
from repro.core.plan import Query
from repro.core.table import Table
from repro.kernels import dispatch
from benchmarks.common import ART_DIR, device_info, rle_friendly, time_fn

N_KEYS = 1000  # dictionary cardinality of the order key
LIMIT = 10
MEAN_RUN = 64


def _rle_table(rng, n):
    """Sorted dict-code key -> RLE encoding + ingest (0, N_KEYS) domain."""
    vocab = np.array([f"key_{i:04d}" for i in range(N_KEYS)])
    cfg = compress.CompressionConfig(plain_threshold=1000)
    codes = rle_friendly(rng, n, N_KEYS, MEAN_RUN).astype(np.int32)
    vals = rng.random(n).astype(np.float32)
    return Table.from_arrays({"k": codes, "v": vals}, cfg=cfg,
                             dictionaries={"k": vocab})


def _time_topk(table, **overrides):
    with dispatch.overrides(**overrides):
        q = Query(table).order_by("k", descending=True, limit=LIMIT,
                                  cols=["v"])
        return time_fn(lambda: q.run(), warmup=1, iters=5) * 1e3


def _transfer_counts(rng, n, num_partitions=16):
    data = {"k": np.sort(rng.integers(0, N_KEYS, n)).astype(np.int32),
            "v": rng.random(n).astype(np.float32)}
    cfg = compress.CompressionConfig(plain_threshold=1000)
    pt = PartitionedTable.from_arrays(data, cfg=cfg,
                                      num_partitions=num_partitions)
    counts = {}
    real_put = P.device_put
    try:
        for label, prune in (("pruned", True), ("unpruned", False)):
            calls = []
            P.device_put = lambda tree: (calls.append(1), real_put(tree))[1]
            q = PartitionedQuery(pt).order_by("k", descending=True,
                                              limit=LIMIT)
            q.ranked_pruning = prune
            q.run()
            counts[label] = len(calls)
    finally:
        P.device_put = real_put
    return counts, num_partitions


def run(n=10_000_000, out_name="BENCH_orderby.json"):
    rng = np.random.default_rng(11)
    t = _rle_table(rng, n)
    assert t.domains["k"] == (0, N_KEYS)

    entries = []
    results = {}
    for path, ov in (
            ("bounded", {}),  # histogram ranks (dict domain available)
            ("entry_sort", {"sort_free_max_domain": 0}),  # argsort on runs
            ("row_level", {"enable_entry_order": False})):  # dense topk
        ms = _time_topk(t, **ov)
        results[path] = ms
        entries.append({"rows": n, "path": path, "stage": "topk",
                        "limit": LIMIT, "median_ms": round(ms, 3)})
        print(f"  top-{LIMIT:<3d} | {path:>10s} | {ms:9.2f} ms")

    counts, nparts = _transfer_counts(rng, max(n // 8, 100_000))
    print(f"  ranked transfers: {counts['pruned']}/{nparts} pruned vs "
          f"{counts['unpruned']}/{nparts} unpruned")

    report = {
        "bench": "orderby",
        **device_info(),
        "rows": n,
        "dict_cardinality": N_KEYS,
        "limit": LIMIT,
        "mean_run": MEAN_RUN,
        "entries": entries,
        "speedup_run_level_topk": round(
            results["row_level"] / results["bounded"], 3),
        "speedup_entry_sort_topk": round(
            results["row_level"] / results["entry_sort"], 3),
        "partitions": nparts,
        "transfers_pruned": counts["pruned"],
        "transfers_unpruned": counts["unpruned"],
    }
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, out_name)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"[bench_orderby] run-level top-k speedup "
          f"{report['speedup_run_level_topk']:.2f}x (bounded), "
          f"{report['speedup_entry_sort_topk']:.2f}x (entry sort); "
          f"transfers {counts['pruned']} vs {counts['unpruned']} -> {path}")
    return report


if __name__ == "__main__":
    run()
