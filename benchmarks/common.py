"""Shared benchmark utilities: timing, CSV output, transfer counting,
data generators.

These harnesses each mirror one paper table/figure at reduced row counts
(DESIGN.md §9 deviation 5). They time whatever backend JAX runs on, and
every result names it (``device_info``): a time taken on the CPU backend
says how fast XLA:CPU or the Pallas interpreter is, never the TPU, and is
not comparable to the paper's A100 numbers.
"""
from __future__ import annotations

import contextlib
import csv
import os
import time
from typing import Callable, Dict, List

import numpy as np
import jax

ART_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "artifacts", "bench")


def device_info() -> Dict[str, object]:
    """What a result was measured on, recorded beside every number."""
    dev = jax.devices()[0]
    return {"backend": jax.default_backend(), "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time of a jitted callable (seconds)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def time_interleaved(fns: Dict[str, Callable], rounds: int = 5,
                     warmup: int = 1) -> Dict[str, float]:
    """Best wall time per labelled callable (seconds), sampled interleaved.

    A/B timing comparisons (packed vs unpacked, prefetch-depth sweeps)
    measured as sequential blocks confound the comparison with machine
    drift — on shared-host CI runners the noise between two blocks can
    exceed the effect under test. Each round times every callable once,
    so all labels sample the same drift epochs, and the per-label MIN is
    reported: scheduling noise is one-sided additive, so the minimum
    estimates the true cost, and a ratio of minima is stable where a
    ratio of one-shot medians flips sign run to run.
    """
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    best: Dict[str, float] = {k: float("inf") for k in fns}
    for _ in range(rounds):
        for label, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[label] = min(best[label], time.perf_counter() - t0)
    return best


@contextlib.contextmanager
def count_h2d(into: List[int]):
    """Count bytes crossing the partition executor's ``device_put``
    boundary (DESIGN.md §11) — the ONE shared implementation used by
    bench_compress, bench_outofcore and tests/test_packed.py, so the
    CI-gated transfer metric and the test assertions cannot diverge.

    Since the telemetry registry (core/telemetry.py, DESIGN.md §14)
    became the single source of truth for H2D accounting, this is a thin
    shim over ``telemetry.h2d_listener`` — the byte counts come from the
    same ``record_h2d`` call that feeds the always-on ``h2d_bytes``
    counter and the per-query traces, instead of a monkeypatched
    ``device_put``."""
    from repro.core import telemetry

    with telemetry.h2d_listener(lambda nbytes, tree: into.append(int(nbytes))):
        yield into


def write_csv(name: str, rows: List[Dict], print_table: bool = True):
    os.makedirs(ART_DIR, exist_ok=True)
    path = os.path.join(ART_DIR, name)
    if rows:
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    if print_table and rows:
        cols = list(rows[0])
        print("  " + " | ".join(f"{c:>14s}" for c in cols))
        for r in rows:
            print("  " + " | ".join(
                f"{(f'{v:.4g}' if isinstance(v, float) else str(v)):>14s}"
                for v in r.values()))
    dev = device_info()
    print(f"  -> {path} (measured on {dev['platform']} "
          f"{dev['device_kind']} x{dev['device_count']})")
    return path


def rle_friendly(rng, n: int, n_vals: int, mean_run: int) -> np.ndarray:
    """Values with geometric run lengths averaging ``mean_run``."""
    n_runs = max(n // mean_run, 1)
    lens = rng.geometric(1.0 / mean_run, n_runs)
    vals = rng.integers(0, n_vals, n_runs)
    out = np.repeat(vals, lens)[:n]
    if len(out) < n:
        out = np.concatenate([out, np.full(n - len(out), vals[-1])])
    return out.astype(np.int32)
