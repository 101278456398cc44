"""Paper Fig. 3: primitive microbenchmarks across input sizes.

The paper compares CPU vs GPU; this harness times one backend, the one JAX
runs on (``write_csv`` names it), so the reported axis is *scaling with
input size* for the four fundamental primitives plus the conversion
kernels. The crossover story of Fig. 3 (fixed launch overhead vs linear
work) shows up as near-flat time below ~100K.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import primitives as P
from benchmarks.common import time_fn, write_csv


def _runs(rng, n_rows, mean_run):
    n_runs = max(n_rows // mean_run, 1)
    bounds = np.sort(rng.choice(n_rows, 2 * n_runs, replace=False))
    starts, ends = bounds[0::2].astype(np.int32), (bounds[1::2] - 1).astype(np.int32)
    keep = starts <= ends
    return jnp.asarray(starts[keep]), jnp.asarray(ends[keep])


def run(sizes=(10_000, 100_000, 1_000_000, 4_000_000)):
    rng = np.random.default_rng(0)
    rows = []
    for n in sizes:
        s1, e1 = _runs(rng, n, 32)
        s2, e2 = _runs(rng, n, 48)
        n1 = jnp.asarray(s1.shape[0], jnp.int32)
        n2 = jnp.asarray(s2.shape[0], jnp.int32)
        cap = s1.shape[0] + s2.shape[0]
        pos = jnp.asarray(np.sort(rng.choice(n, min(n // 16, 200_000),
                                             replace=False)).astype(np.int32))
        npos = jnp.asarray(pos.shape[0], jnp.int32)

        fns = {
            "range_intersect": jax.jit(lambda: P.range_intersect(
                s1, e1, n1, s2, e2, n2, n, cap)),
            "range_union": jax.jit(lambda: P.range_union(
                s1, e1, n1, s2, e2, n2, n, cap)),
            "idx_in_rle": jax.jit(lambda: P.idx_in_rle(
                pos, npos, s1, e1, n1, n, pos.shape[0])),
            "rle_contain_idx": jax.jit(lambda: P.rle_contain_idx(
                pos, npos, s1, e1, n1, n, pos.shape[0] + s1.shape[0])),
            "merge_sorted_idx": jax.jit(lambda: P.merge_sorted_idx(
                pos, npos, pos, npos, n, 2 * pos.shape[0])),
            "rle_to_plain": jax.jit(lambda: P.rle_to_plain(
                jnp.ones_like(s1), s1, e1, n1, n)),
        }
        row = {"rows": n, "runs": int(s1.shape[0])}
        for name, f in fns.items():
            row[name + "_ms"] = time_fn(f) * 1e3
        rows.append(row)
    print("[bench_primitives] paper Fig. 3")
    write_csv("primitives.csv", rows)
    return rows


if __name__ == "__main__":
    run()
