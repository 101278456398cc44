"""The comparison that decides ``correct``.

Each answer the measured window produced is compared with the float64
numpy reference of its template over the same generated data:

* ``missing``: requests that failed or never answered (limit 0);
* ``exact_mismatch``: answers whose set of group keys or a COUNT differs
  from the reference's, or whose rows are out of their ORDER BY (limit 0:
  SQL is exact there);
* ``max_rel_err``: the widest relative gap of any SUM or AVG, over every
  group of every answer (its limit is the configuration's
  ``check.max_rel_err``; ``PERF.md`` gives the readings it was set from).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


def bfloat16(x):
    """Round to bfloat16 and back: the control's arithmetic."""
    import ml_dtypes

    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _sort_key(keys, order, key, aggs):
    """The ORDER BY key of one row: group keys exact, aggregates as given,
    a descending part wrapped in ``_Desc``."""
    out = []
    for name, desc in order:
        v = key[keys.index(name)] if name in keys else aggs[name]
        out.append(_Desc(v) if desc else v)
    return tuple(out)


class _Desc:
    """Reverses the order of a value inside a sort key."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return self.v > other.v

    def __le__(self, other):
        return self.v >= other.v

    def __eq__(self, other):
        return self.v == other.v


def diff(answer: dict, ref: dict) -> Tuple[bool, float]:
    """(exact parts differ?, widest relative gap of the float aggregates).

    Exact: the set of group keys, every COUNT, and the ORDER BY: the
    answer's rows must be in order by its own values (group keys are exact;
    two sums that float32 cannot tell apart may come in either order, and
    each sum is held to the reference by the relative gap)."""
    want = dict(ref["rows"])
    got_keys = [k for k, _ in answer["rows"]]
    if len(set(got_keys)) != len(got_keys) or set(got_keys) != set(want):
        return True, 0.0
    order = ref.get("order", [])
    sort_keys = [_sort_key(ref["keys"], order, k, a) for k, a in answer["rows"]]
    exact_bad = any(b < a for a, b in zip(sort_keys, sort_keys[1:]))
    worst = 0.0
    for key, got in answer["rows"]:
        if set(got) != set(want[key]):
            return True, 0.0
        for name, w in want[key].items():
            g = got[name]
            if name.startswith("count"):
                exact_bad |= g != w
                continue
            gap = abs(g - w) / abs(w) if w != 0 else abs(g)
            worst = max(worst, float(gap))
    return exact_bad, worst


def compare(module, data, answers: List[Tuple[str, Optional[dict]]],
            limits: Dict[str, float], missing: int = 0, num=None) -> dict:
    """Compare every answer with its template's reference. ``num`` rounds
    the reference's arithmetic (None: float64)."""
    refs: Dict[str, dict] = {}
    exact_bad = 0
    worst = 0.0
    for name, ans in answers:
        if ans is None:
            continue
        if name not in refs:
            refs[name] = (module.reference(name, data) if num is None
                          else module.reference(name, data, num))
        bad, gap = diff(ans, refs[name])
        exact_bad += int(bad)
        worst = max(worst, gap)
    return {"missing": {"value": int(missing), "limit": 0},
            "exact_mismatch": {"value": exact_bad, "limit": 0},
            "max_rel_err": {"value": worst,
                            "limit": float(limits["max_rel_err"])}}


def verdict(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def control(module, data, templates: List[str],
            limits: Dict[str, float]) -> dict:
    """The control: in the program's place, the reference computed in
    bfloat16 answers each of ``templates`` (a window's requests, in its
    order), and ``compare`` judges those answers as it judges a run's.
    ``verdict`` of what it returns has to be false."""
    low: Dict[str, dict] = {}
    for name in templates:
        if name not in low:
            low[name] = module.reference(name, data, bfloat16)
    return compare(module, data, [(n, low[n]) for n in templates], limits)
