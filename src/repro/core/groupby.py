"""Group-by aggregation on encoded columns (paper §7 + Appendix A.2).

Two phases: *Grouping* (inverse index over unique group-key tuples) and
*Aggregating* (segment reductions). The challenge the paper highlights —
heterogeneous encodings across group-by / aggregate columns — is solved by the
Alignment step (§6): all participating columns are brought onto a common
segmentation first.

Run-aware aggregation rewrites (paper §7.2):
  COUNT = Σ run_lengths           (never expands runs)
  SUM   = Σ value · run_length
  MIN/MAX = over value tensor only
  AVG/STD/VAR = post-processing over SUM / COUNT / SUM-of-squares
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import primitives as prim
from repro.kernels import dispatch
from repro.core.encodings import (
    POS_DTYPE,
    IndexColumn,
    IndexMask,
    PlainColumn,
    PlainIndexColumn,
    PlainMask,
    RLEColumn,
    RLEIndexColumn,
    RLEIndexMask,
    RLEMask,
    coverage,
    decode_column,
    decode_mask,
    unpack_values,
    valid_slots,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SegmentView:
    """Aligned view: per-segment values for every column + segment lengths.

    ``starts``/``ends`` are the row ranges of the segments (run-level path)
    or per-row unit ranges (row-level fallback) — the hybrid group-by path
    uses them to scatter Plain aggregate rows onto run-level group ids."""

    values: Dict[str, jax.Array]
    lengths: jax.Array  # rows per segment
    valid: jax.Array  # bool per segment
    n: jax.Array  # number of valid segments
    starts: jax.Array
    ends: jax.Array


def _is_position_explicit(c) -> bool:
    return isinstance(c, (RLEColumn, IndexColumn))


def _as_runs(c):
    """(values, starts, ends, n) — Index columns become unit-length runs.
    Bit-packed buffers unpack here, at the consumer (DESIGN.md §11): the
    group-by key path then fuses the shift+mask into its key scatter."""
    if isinstance(c, RLEColumn):
        return (unpack_values(c.values), unpack_values(c.starts),
                unpack_values(c.ends), c.n)
    if isinstance(c, IndexColumn):
        pos = unpack_values(c.positions)
        return unpack_values(c.values), pos, pos, c.n
    raise TypeError(type(c))


def _mask_as_runs(m, nrows):
    if isinstance(m, RLEMask):
        return m.starts, m.ends, m.n
    if isinstance(m, IndexMask):
        return m.positions, m.positions, m.n
    raise TypeError(type(m))


def align_columns(cols: Dict[str, object], mask=None) -> SegmentView:
    """Bring heterogeneously encoded columns onto one segmentation (§6).

    Fast path (the paper's headline case): all columns position-explicit
    (RLE / Index) -> chained ``range_intersect`` keeps the result run-level —
    segment count is O(Σ runs), never O(rows). Any Plain participant forces
    row-level segmentation (lengths == 1), matching the paper's observation
    that Plain columns dictate per-row processing.
    """
    items = list(cols.items())
    run_ok = all(_is_position_explicit(c) for _, c in items) and (
        mask is None or isinstance(mask, (RLEMask, IndexMask)))
    nrows = items[0][1].nrows

    if run_ok:
        src_vals = {name: _as_runs(c)[0] for name, c in items}
        run_lists = [_as_runs(c)[1:] for _, c in items]
        if mask is not None:
            run_lists.append(_mask_as_runs(mask, nrows))
        if len(run_lists) == 1:
            # single position-explicit column, no mask: its runs ARE the
            # segmentation (identity indices, no sweep needed).
            name0, c0 = items[0]
            _, s, e, n = _as_runs(c0)
            valid = valid_slots(n, c0.capacity)
            lengths = jnp.where(valid, e - s + 1, 0)
            values = {name0: jnp.where(valid, src_vals[name0], 0)}
            return SegmentView(values=values, lengths=lengths, valid=valid,
                               n=n, starts=s, ends=e)
        # k-way fused sweep (one event sort) instead of chained pairwise
        # intersects whose intermediate capacities grow additively.
        cap_total = sum(c.capacity for _, c in items)
        if mask is not None:
            cap_total += mask.capacity
        s, e, idxs, n = prim.range_intersect_multi(run_lists, nrows, cap_total)
        valid = valid_slots(n, cap_total)
        lengths = jnp.where(valid, e - s + 1, 0)
        values = {name: jnp.where(valid, src_vals[name][idxs[j]], 0)
                  for j, (name, _) in enumerate(items)}
        return SegmentView(values=values, lengths=lengths, valid=valid,
                           n=n, starts=s, ends=e)

    # Row-level fallback: any Plain participant (or Plain mask).
    live = jnp.ones((nrows,), jnp.bool_)
    values = {}
    for name, c in items:
        values[name] = decode_column(c)
        if not isinstance(c, (PlainColumn, PlainIndexColumn)):
            live = live & coverage(c)
    if mask is not None:
        live = live & decode_mask(mask)
    lengths = jnp.where(live, 1, 0)
    rows = jnp.arange(nrows, dtype=POS_DTYPE)
    return SegmentView(values=values, lengths=lengths, valid=live,
                       n=jnp.sum(lengths).astype(jnp.int32),
                       starts=rows, ends=rows)


# ---------------------------------------------------------------------------
# Grouping phase (paper §7.1)
# ---------------------------------------------------------------------------


def _bounded_key_domain(view: SegmentView, group_names: Sequence[str],
                        key_domains) -> Optional[int]:
    """Mixed-radix product domain size when the sort-free path may fire:
    every group key integer-valued with ingest-recorded (lo, size) domain
    metadata, and the exact product domain small enough to scatter over
    (DESIGN.md §5). None -> argsort path."""
    pol = dispatch.policy()
    if not pol.enable_sort_free or not key_domains:
        return None
    i32 = jnp.iinfo(jnp.int32)
    total = 1
    for name in group_names:
        dom = key_domains.get(name)
        if dom is None or not jnp.issubdtype(view.values[name].dtype,
                                             jnp.integer):
            return None
        lo, size = int(dom[0]), int(dom[1])
        # the code arithmetic is int32: a domain whose bounds fall outside
        # int32 (e.g. uint32 keys past 2^31) must take the argsort path
        if lo < i32.min or lo + size - 1 > i32.max:
            return None
        total *= size
        if total > pol.sort_free_max_domain:
            return None
    return total if total > 0 else None


def grouping(view: SegmentView, group_names: Sequence[str], num_groups_cap: int,
             key_domains: Optional[Dict[str, Tuple[int, int]]] = None):
    """Inverse index per segment over unique group-key tuples.

    **Sort-free fast path**: when every group key has a bounded dense
    domain (dictionary codes, centered int8/int16 — ``key_domains`` maps
    name -> (lo, size) from ingest), the multi-column key is composed by
    mixed-radix arithmetic over the EXACT domain sizes and grouped by one
    ``unique_bounded`` scatter — no argsort anywhere. Group ids come out
    in the same lexicographic key order as the argsort path, so results
    are identical.

    **Argsort fallback**: keys are combined iteratively
    (id' = id * cap + inv); the combined key gets a final unique pass for
    dense ids.

    Returns (gid[segments], num_groups, rep_index[num_groups_cap]).
    """
    bounded = _bounded_key_domain(view, group_names, key_domains)
    if bounded is not None:
        combined = None
        for name in group_names:
            lo, size = key_domains[name]
            code = view.values[name].astype(jnp.int32) - jnp.asarray(
                lo, jnp.int32)
            combined = code if combined is None else combined * size + code
        _, gid, num_groups = prim.unique_bounded(
            combined, view.valid, bounded, cap_groups=num_groups_cap)
    else:
        combined = None
        for name in group_names:
            vals = view.values[name]
            if jnp.issubdtype(vals.dtype, jnp.integer) and vals.dtype != jnp.int32:
                # centered narrow columns (int8/int16) widen for key
                # arithmetic; also keeps the sentinel (int32 max)
                # collision-free
                vals = vals.astype(jnp.int32)
            _, inv, _ = prim.unique_with_inverse(
                vals, view.valid, num_groups_cap)
            # combined-key arithmetic is int32:
            # requires num_groups_cap**n_cols < 2**31
            inv32 = inv.astype(jnp.int32)
            combined = (inv32 if combined is None
                        else combined * num_groups_cap + inv32)
        _, gid, num_groups = prim.unique_with_inverse(
            combined, view.valid, num_groups_cap)
    # representative segment per group (first occurrence) for key recovery;
    # empty slots read the int32 maximum
    seg_ids = jnp.arange(gid.shape[0], dtype=POS_DTYPE)
    gid_safe = jnp.where(view.valid, gid, num_groups_cap)
    rep = dispatch.segment_min(seg_ids, gid_safe, num_groups_cap)
    return gid_safe, num_groups, rep


# ---------------------------------------------------------------------------
# Aggregating phase (paper §7.2 + A.2)
# ---------------------------------------------------------------------------


def _segsum(values, gid, cap):
    # dispatch-routed (DESIGN.md §5): integers over a small table reduce
    # densely on the TPU; float32 takes the MXU one-hot matmul kernel when
    # the policy allows and cap fits a VMEM block; XLA scatter-add otherwise.
    return dispatch.segment_sum(values, gid, cap)


def aggregate(view: SegmentView, gid: jax.Array, specs, num_groups_cap: int):
    """specs: list of (out_name, agg, col_name). agg in
    sum|count|min|max|avg|var|std. Returns dict out_name -> array[cap]."""
    out = {}
    lengths = view.lengths
    f32 = jnp.float32
    for out_name, agg, col_name in specs:
        if agg == "count":
            out[out_name] = _segsum(lengths.astype(jnp.int32), gid, num_groups_cap)
            continue
        v = view.values[col_name]
        if agg == "sum":
            # RLE-aware: value × run length (paper's v·l rewrite)
            out[out_name] = _segsum(
                v.astype(f32) * lengths.astype(f32), gid, num_groups_cap)
        elif agg == "min":
            vv = jnp.where(view.valid, v.astype(f32), jnp.inf)
            out[out_name] = dispatch.segment_min(vv, gid, num_groups_cap)
        elif agg == "max":
            vv = jnp.where(view.valid, v.astype(f32), -jnp.inf)
            out[out_name] = dispatch.segment_max(vv, gid, num_groups_cap)
        elif agg in ("avg", "var", "std"):
            s = _segsum(v.astype(f32) * lengths.astype(f32), gid, num_groups_cap)
            c = _segsum(lengths.astype(f32), gid, num_groups_cap)
            mean = s / jnp.maximum(c, 1)
            if agg == "avg":
                out[out_name] = mean
            else:
                sq = _segsum((v.astype(f32) ** 2) * lengths.astype(f32), gid,
                             num_groups_cap)
                var = sq / jnp.maximum(c, 1) - mean ** 2
                out[out_name] = var if agg == "var" else jnp.sqrt(jnp.maximum(var, 0))
        else:
            raise ValueError(f"unknown agg {agg}")
    return out


def _gid_per_row(view: SegmentView, gid: jax.Array, nrows: int) -> jax.Array:
    """Group id of the segment covering or preceding each row (0 before the
    first). Segments are disjoint and in row order, so this is the prefix
    sum of the gid steps placed at the segment starts: one O(segments)
    scatter and one O(rows) cumsum, the sweep of ``_run_id_per_row``, in
    place of a row-sized gather from the segments' gids. Integer, so
    exact."""
    prev = jnp.concatenate([jnp.zeros((1,), gid.dtype), gid[:-1]])
    step = jnp.where(view.valid, gid - prev, 0)
    delta = jnp.zeros((nrows + 1,), gid.dtype).at[view.starts].add(
        step, mode="drop")
    return jnp.cumsum(delta[:nrows])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GroupByResult:
    keys: Dict[str, jax.Array]  # group key values per group slot
    aggs: Dict[str, jax.Array]
    num_groups: jax.Array
    valid: jax.Array  # [num_groups_cap]


def groupby_aggregate(
    cols: Dict[str, object],
    group_names: Sequence[str],
    specs: Sequence[Tuple[str, str, Optional[str]]],
    num_groups_cap: int,
    mask=None,
    key_domains: Optional[Dict[str, Tuple[int, int]]] = None,
) -> GroupByResult:
    """End-to-end §7: align -> group -> aggregate.

    ``cols`` must contain every group and aggregate column. ``specs`` entries
    are (out_name, agg, col_name) with col_name None for COUNT.
    ``key_domains`` (name -> (lo, size), from ``Table.domains``) enables
    the sort-free grouping path — see ``grouping``.

    **Hybrid path** (the paper's §7/A.2 flow): when every GROUP column is
    position-explicit but some AGGREGATE columns are Plain, grouping runs at
    run level (unique over O(runs) segments — never the row-level sort) and
    Plain aggregate rows are scattered straight onto group ids through the
    O(n) row->segment sweep. This is where the paper's Q1-style wins
    come from: the expensive part of a group-by is the unique/sort, and
    compression shrinks it by the run-length factor."""
    pe = {k: c for k, c in cols.items() if _is_position_explicit(c)}
    plain = {k: c for k, c in cols.items() if not _is_position_explicit(c)}
    mask_pe = mask is None or isinstance(mask, (RLEMask, IndexMask))
    hybrid = plain and mask_pe and all(g in pe for g in group_names)

    if not hybrid:
        view = align_columns(dict(cols), mask=mask)
        gid, num_groups, rep = grouping(view, group_names, num_groups_cap,
                                        key_domains=key_domains)
        out = aggregate(view, gid, [(o, a, c) for o, a, c in specs],
                        num_groups_cap)
    else:
        from repro.core.encodings import decode_rle_coverage
        nrows = next(iter(cols.values())).nrows
        view = align_columns(pe, mask=mask)  # run-level segments
        gid, num_groups, rep = grouping(view, group_names, num_groups_cap,
                                        key_domains=key_domains)
        run_specs = [(o, a, c) for o, a, c in specs
                     if c is None or c in view.values]
        out = aggregate(view, gid, run_specs, num_groups_cap)
        # row -> group scatter for Plain aggregate columns
        cov = decode_rle_coverage(view.starts, view.ends, view.n, nrows)
        gid_row = jnp.where(cov, _gid_per_row(view, gid, nrows),
                            num_groups_cap)  # drop slot
        f32 = jnp.float32
        counts = None
        for o, a, c in specs:
            if c is None or c in view.values:
                continue
            v = decode_column(plain[c]).astype(f32)
            if a in ("sum", "avg", "var", "std"):
                ssum = _segsum(jnp.where(cov, v, 0.0), gid_row, num_groups_cap)
            if a == "sum":
                out[o] = ssum
            elif a == "min":
                out[o] = dispatch.segment_min(
                    jnp.where(cov, v, jnp.inf), gid_row, num_groups_cap)
            elif a == "max":
                out[o] = dispatch.segment_max(
                    jnp.where(cov, v, -jnp.inf), gid_row, num_groups_cap)
            elif a in ("avg", "var", "std"):
                if counts is None:
                    counts = _segsum(view.lengths.astype(f32), gid,
                                     num_groups_cap)
                mean = ssum / jnp.maximum(counts, 1)
                if a == "avg":
                    out[o] = mean
                else:
                    sq = _segsum(jnp.where(cov, v * v, 0.0), gid_row,
                                 num_groups_cap)
                    var = sq / jnp.maximum(counts, 1) - mean ** 2
                    out[o] = var if a == "var" else jnp.sqrt(
                        jnp.maximum(var, 0))
            else:
                raise ValueError(a)

    rep_safe = jnp.clip(rep, 0, gid.shape[0] - 1)
    gvalid = valid_slots(num_groups, num_groups_cap)
    keys = {
        name: jnp.where(gvalid, view.values[name][rep_safe], 0)
        for name in group_names
    }
    return GroupByResult(keys=keys, aggs=out, num_groups=num_groups, valid=gvalid)


# ---------------------------------------------------------------------------
# Cross-partition merge (partitioned execution, DESIGN.md §4)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MergedGroupBy:
    """Host-side merged group-by result: exact-size numpy arrays, groups in
    lexicographic key order (np.unique)."""

    keys: Dict[str, np.ndarray]
    aggs: Dict[str, np.ndarray]
    num_groups: int


def _reduce_into_groups(vals: np.ndarray, inv: np.ndarray, ng: int,
                        agg: str) -> np.ndarray:
    """Reduce concatenated per-group values under one combine rule."""
    if agg in ("sum", "count"):
        acc = np.zeros((ng,), vals.dtype)
        np.add.at(acc, inv, vals)
        return acc
    if agg == "min":
        acc = np.full((ng,), np.inf, np.float64)
        np.minimum.at(acc, inv, vals)
        return acc.astype(vals.dtype)
    acc = np.full((ng,), -np.inf, np.float64)  # max
    np.maximum.at(acc, inv, vals)
    return acc.astype(vals.dtype)


def fold_groupby_partial(acc, r: GroupByResult, group_names: Sequence[str],
                         partial_specs):
    """Fold ONE partition's GroupByResult partial into the running merged
    state (host side) — the incremental half of ``merge_groupby_partials``
    for the streamed executor (``core/stream.py``): partial ``i`` merges
    here while partitions ``i+1..i+k`` transfer and compute.

    ``acc`` is ``None`` or ``{"keys": uniq2d, "aggs": {out: vals},
    "key_dtypes": [...]}`` with groups in lexicographic key order (both a
    partition's GroupByResult slots and ``np.unique`` are lexicographic).
    The ``np.asarray`` calls are where the host blocks on device values.
    Folding in partition order is bit-identical to the batch merge: each
    group's contributions accumulate left-to-right in both formulations.
    """
    ng = int(r.num_groups)
    if ng == 0:
        return acc
    cols = [np.asarray(r.keys[g])[:ng] for g in group_names]
    block_keys = np.stack(cols, axis=1)
    block_aggs = {o: np.asarray(r.aggs[o])[:ng] for o, _, _ in partial_specs}
    if acc is None:
        return {"keys": block_keys, "aggs": block_aggs,
                "key_dtypes": [c.dtype for c in cols]}
    all_keys = np.concatenate([acc["keys"], block_keys], axis=0)
    if all_keys.shape[1] == 1:
        # np.unique(axis=0) routes through a void-dtype view + lexsort —
        # an order of magnitude slower than the 1-D path for the common
        # single-key group-by, and this fold sits on the streamed
        # executor's critical path once per partition
        u1, inv = np.unique(all_keys[:, 0], return_inverse=True)
        uniq = u1[:, None]
    else:
        uniq, inv = np.unique(all_keys, axis=0, return_inverse=True)
    ng2 = uniq.shape[0]
    merged = {o: _reduce_into_groups(
        np.concatenate([acc["aggs"][o], block_aggs[o]]), inv, ng2, agg)
        for o, agg, _ in partial_specs}
    return {"keys": uniq, "aggs": merged, "key_dtypes": acc["key_dtypes"]}


def finalize_groupby_partials(acc, group_names: Sequence[str],
                              specs: Sequence[Tuple[str, str, Optional[str]]]
                              ) -> MergedGroupBy:
    """Finalize a folded group-by state (avg = sum / count, key dtype
    restoration); ``acc=None`` (every partition skipped or empty) yields
    the empty result."""
    from repro.core import plan as plan_mod

    _, finalize = plan_mod.decompose_specs(specs)
    if acc is None:
        keys = {g: np.zeros((0,), np.int32) for g in group_names}
        aggs = {name: np.zeros((0,), np.float32) for name, _, _ in finalize}
        return MergedGroupBy(keys=keys, aggs=aggs, num_groups=0)
    aggs = plan_mod._apply_finalize(acc["aggs"], finalize)
    keys = {g: acc["keys"][:, i].astype(acc["key_dtypes"][i])
            for i, g in enumerate(group_names)}
    return MergedGroupBy(keys=keys, aggs=aggs,
                         num_groups=acc["keys"].shape[0])


def merge_groupby_partials(results: Sequence[GroupByResult],
                           group_names: Sequence[str],
                           specs: Sequence[Tuple[str, str, Optional[str]]]):
    """Re-aggregate per-partition GroupByResult partials on the host.

    ``results`` come from ``Query.build(partial=True)`` programs (one per
    non-skipped partition); ``specs`` are the ORIGINAL agg specs — the same
    decomposition applied per-partition is recomputed here so each partial
    output merges under its combine rule (sum/count add, min/max extremes)
    and avg finalizes as merged-sum / merged-count. Batch wrapper over
    ``fold_groupby_partial`` + ``finalize_groupby_partials``; the streamed
    executor calls the incremental pair directly.
    """
    from repro.core import plan as plan_mod

    partial_specs, _ = plan_mod.decompose_specs(specs)
    acc = None
    for r in results:
        acc = fold_groupby_partial(acc, r, group_names, partial_specs)
    return finalize_groupby_partials(acc, group_names, specs)
