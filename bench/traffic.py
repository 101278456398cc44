"""The one traffic generator: turns a mix file's parameters into the
order of a closed loop or the schedule of an open loop.

A closed loop runs whole cycles of the templates, each cycle in an order
drawn from the seed, so every seed does the same work. An open loop gives
every seed the same set of gaps between arrivals and the same number of
requests per popularity rank; the seed draws which template holds each
rank, and the order of the gaps and of the requests.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

ORDER_CYCLES = 100_000  # a closed loop never runs out of requests


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), salt])


def make_order(names: Sequence[str], mix: dict, seed: int) -> List[str]:
    """Closed loop: the templates in turn, each cycle in a seeded order,
    every template with equal weight (``mix["weights"] == "uniform"``)."""
    if mix.get("weights", "uniform") != "uniform":
        raise ValueError(f"unknown weights {mix['weights']!r}")
    rng = _rng(seed, 1)
    names = list(names)
    out: List[str] = []
    for _ in range(ORDER_CYCLES):
        out.extend(names[i] for i in rng.permutation(len(names)))
    return out


def popularity(names: Sequence[str], mix: dict) -> np.ndarray:
    """Share of requests per popularity rank: Zipf over the ranks, or
    uniform."""
    k = np.arange(1, len(names) + 1, dtype=np.float64)
    if mix.get("popularity") == "zipf":
        w = k ** -float(mix["zipf_exponent"])
    else:
        w = np.ones_like(k)
    return w / w.sum()


def _counts(shares: np.ndarray, n: int) -> np.ndarray:
    """Largest-remainder rounding of ``shares * n`` to whole requests."""
    raw = shares * n
    counts = np.floor(raw).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(raw - counts), kind="stable")[:rest]] += 1
    return counts


def make_schedule(names: Sequence[str], mix: dict, seconds: float,
                  seed: int) -> List[Tuple[float, str]]:
    """Open loop: ``(offset_s, template)`` pairs at ``mix["rate_qps"]``.

    Poisson arrivals: the gaps are the quantiles of an exponential
    distribution at the rate, in an order drawn from the seed. The seed
    ranks the templates; each rank gets its share of ``popularity``, and
    the requests come in an order drawn from the seed too."""
    rate = float(mix["rate_qps"])
    n = max(int(round(rate * seconds)), 1)
    rng = _rng(seed, 2)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps[rng.permutation(n)]
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    ranking = rng.permutation(len(names))
    counts = _counts(popularity(names, mix), n)
    picks = np.repeat(ranking, counts)[rng.permutation(n)]
    return [(float(o), names[i]) for o, i in zip(offsets, picks)]
