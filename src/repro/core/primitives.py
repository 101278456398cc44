"""Fundamental parallel primitives on encoded data (paper §4, Table 1).

All primitives are loop-free / branch-free jnp programs (the paper's central
implementation requirement for GPU efficiency, equally necessary for TPU), and
static-shape under the capacity model (DESIGN.md §3):

  * inputs are fixed-capacity buffers + dynamic counts, sentinel-padded,
  * each primitive takes/derives a static output capacity and returns
    (buffers, count) with the sentinel invariant restored.

``torch.bucketize(x, b, right=False)`` == ``jnp.searchsorted(b, x, "left")``;
``right=True`` == ``side="right"`` — the transcription used throughout.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.encodings import (
    POS_DTYPE,
    IndexColumn,
    IndexMask,
    RLEColumn,
    RLEMask,
    pad_positions,
    unpack_values,
    valid_slots,
)
from repro.kernels import dispatch

# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def compact(flags: jax.Array, arrays, caps: int, fills) -> Tuple[tuple, jax.Array]:
    """Stable compaction: keep slots where ``flags``; scatter into cap buffers.

    arrays: tuple of 1-D arrays (same length as flags); fills: per-array fill.
    Returns (tuple of compacted arrays of length ``caps``, count scalar).
    """
    idx = jnp.cumsum(flags) - 1  # target slot for kept entries
    tgt = jnp.where(flags, idx, caps)  # out-of-range -> dropped
    outs = []
    for a, fill in zip(arrays, fills):
        out = jnp.full((caps,), fill, a.dtype)
        outs.append(out.at[tgt].set(a, mode="drop"))
    count = jnp.sum(flags).astype(jnp.int32)
    return tuple(outs), count


def repeat_interleave_capped(repeats: jax.Array, cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """torch.repeat_interleave(arange(len(repeats)), repeats) with static cap.

    Returns (src_index[cap], valid[cap], total). For output slot i the source
    entry is ``searchsorted(cumsum(repeats), i, 'right')`` — binary-search
    expansion, the TPU-native replacement for scatter-style interleave.
    """
    offsets = jnp.cumsum(repeats)  # inclusive prefix sums
    total = offsets[-1] if repeats.shape[0] > 0 else jnp.asarray(0, repeats.dtype)
    i = jnp.arange(cap, dtype=offsets.dtype)
    src = dispatch.bucketize(offsets, i, right=True).astype(POS_DTYPE)
    valid = i < total
    src = jnp.where(valid, src, 0)
    return src, valid, total.astype(jnp.int32)


def range_arange_capped(starts: jax.Array, lengths: jax.Array, cap: int):
    """Algorithm 2 (range_arange) with static output capacity.

    Concatenates [starts[k], starts[k]+1, ..., starts[k]+lengths[k]-1] for all
    k. Returns (result[cap], src[cap], valid[cap], total).
    """
    src, valid, total = repeat_interleave_capped(lengths, cap)
    offsets = jnp.cumsum(lengths)
    prev = jnp.concatenate([jnp.zeros((1,), offsets.dtype), offsets[:-1]])
    i = jnp.arange(cap, dtype=offsets.dtype)
    result = starts[src].astype(offsets.dtype) + (i - prev[src])
    result = jnp.where(valid, result, 0)
    return result.astype(POS_DTYPE), src, valid, total


def unique_with_inverse(values: jax.Array, valid: jax.Array, cap_groups: int):
    """torch.unique(return_inverse=True) under the capacity model.

    Invalid slots get group id cap_groups-1-safe garbage but are flagged off.
    Returns (uniques[cap_groups], inverse[len(values)], num_groups).
    """
    # sentinel = own-dtype max (int8-centered group keys exist: paper §3.2)
    big = (jnp.asarray(jnp.iinfo(values.dtype).max, values.dtype)
           if jnp.issubdtype(values.dtype, jnp.integer)
           else jnp.asarray(jnp.inf, values.dtype))
    key = jnp.where(valid, values, big)
    order = jnp.argsort(key)
    sv = key[order]
    valid_sorted = valid[order]
    newgrp = valid_sorted & ((jnp.arange(sv.shape[0]) == 0) | (sv != jnp.roll(sv, 1)))
    gid_sorted = jnp.cumsum(newgrp) - 1
    inverse = jnp.zeros_like(gid_sorted).at[order].set(gid_sorted)
    (uniques,), num_groups = compact(newgrp, (sv,), cap_groups, (0,))
    return uniques, inverse.astype(POS_DTYPE), num_groups


def unique_bounded(values: jax.Array, valid: jax.Array, domain_size: int,
                   cap_groups: int | None = None):
    """Sort-free unique+inverse for values in the dense domain [0, domain_size).

    The torch.unique/argsort in ``unique_with_inverse`` is the expensive
    part of every grouping (paper §7); when the key is a dictionary code or
    a centered narrow integer its domain is a small dense range known at
    ingest, and unique reduces to a presence count + cumsum renumbering —
    O(n + domain) work, no O(n log n) sort (grouping directly on codes, the
    Lin et al. companion-work trick). Over at most ``dispatch.G_DENSE``
    codes the count is a dense compare; above, a scatter.

    ``valid`` masks slots out; out-of-domain values are dropped (callers
    guarantee in-domain via the ingest domain metadata, DESIGN.md §5).
    Returns (uniques[cap_groups or domain_size] — the present domain values
    ascending, inverse[len(values)], num_groups). Group ids are assigned in
    ascending value order, exactly matching ``unique_with_inverse``.
    """
    cap_groups = domain_size if cap_groups is None else cap_groups
    v = jnp.where(valid, values.astype(jnp.int32), domain_size)
    # dispatch-routed (DESIGN.md §5): over a small domain the count is a
    # dense compare, not a row-sized scatter
    counts = dispatch.segment_sum(jnp.ones_like(v), v, domain_size)
    present = counts > 0
    rank = (jnp.cumsum(present) - 1).astype(POS_DTYPE)
    num_groups = jnp.sum(present).astype(jnp.int32)
    inverse = rank[jnp.clip(v, 0, domain_size - 1)]
    inverse = jnp.where(valid, inverse, 0).astype(POS_DTYPE)
    (uniques,), _ = compact(present,
                            (jnp.arange(domain_size, dtype=jnp.int32),),
                            cap_groups, (0,))
    return uniques, inverse, num_groups


def rank_select_bounded(codes: jax.Array, lengths: jax.Array, valid: jax.Array,
                        domain_size: int, limit: int):
    """Sort-free top-``limit`` ROW selection over entries with bounded rank
    codes (the ordering subsystem's dense-domain trick, DESIGN.md §10).

    ``codes`` are int32 per-entry rank keys in ``[0, domain_size)`` with
    SMALLER = better (direction flips are the caller's job); ``lengths`` is
    rows per entry (run lengths — 1 for points/rows), ``valid`` masks
    entries out. The comparison sort of a row-level top-k is replaced by

      1. a presence histogram of live row counts per code (one scatter-add
         of run lengths — O(E + D)),
      2. a cumulative sum over the domain: ``rows_with_code_below[c]``,
      3. the boundary code c* = the ``limit``-th best row's code (one
         searchsorted into the cumsum), and
      4. ONE O(E) prefix sum over the boundary code's entries to split the
         quota left at c* among them in position (stable) order.

    Returns ``(take, total)``: ``take[i]`` rows of entry ``i`` belong to
    the top-``limit`` (its first ``take[i]`` rows, since same-code entries
    rank in position order), ``total = min(limit, live rows)``. Entries
    with code < c* always have ``take == length``, so
    ``sum(take) == total`` and at most ``total`` entries have a nonzero
    take — a compaction to ``next_pow2(limit)`` slots can never overflow.
    """
    lens = jnp.where(valid, lengths, 0).astype(jnp.int32)
    v = jnp.where(valid & (lens > 0), codes.astype(jnp.int32), domain_size)
    hist = jnp.zeros((domain_size,), jnp.int32).at[v].add(lens, mode="drop")
    csum = jnp.cumsum(hist)  # inclusive: rows with code <= c
    total = jnp.minimum(jnp.asarray(limit, jnp.int32), csum[domain_size - 1])
    cstar = jnp.searchsorted(csum, total, side="left").astype(jnp.int32)
    excl = csum - hist  # rows with code < c
    rows_before_code = excl[jnp.clip(v, 0, domain_size - 1)]
    at_boundary = v == cstar
    b_lens = jnp.where(at_boundary, lens, 0)
    within = jnp.cumsum(b_lens) - b_lens  # boundary rows before this entry
    quota = total - rows_before_code - within
    take = jnp.where(v < cstar, lens,
                     jnp.where(at_boundary, jnp.clip(quota, 0, lens), 0))
    return take, total


# ---------------------------------------------------------------------------
# range_intersect (Algorithm 1) — the workhorse
# ---------------------------------------------------------------------------


def range_intersect(
    s1: jax.Array, e1: jax.Array, n1: jax.Array,
    s2: jax.Array, e2: jax.Array, n2: jax.Array,
    nrows: int, cap_out: int,
):
    """Intersect two sorted non-overlapping run lists (paper Alg. 1).

    Returns (s[cap_out], e[cap_out], idx1[cap_out], idx2[cap_out], n_out).
    idx1/idx2 are per-output-run source indices into each input — used by the
    §6 alignment step to duplicate split-run values.

    |intersection| <= n1 + n2 - 1, so cap_out = cap1 + cap2 is always safe.
    """
    cap1 = s1.shape[0]
    # Step 1/2: bucketize starts & ends (paper lines 1-2).
    bin_s = dispatch.bucketize(e2, s1, right=False)
    bin_e = dispatch.bucketize(s2, e1, right=True)
    # Step 3: overlap counts; zero for invalid input slots. Valid runs of c1
    # never see sentinel slots of c2 (sentinel start == nrows > any valid end),
    # but invalid runs of c1 would count c2's sentinel region -> mask them.
    cnt = jnp.where(valid_slots(n1, cap1), bin_e - bin_s, 0)
    cnt = jnp.maximum(cnt, 0)
    # Also clamp to the valid region of c2 (defensive; no-op when invariant holds).
    cnt = jnp.minimum(cnt, jnp.maximum(n2 - bin_s, 0))
    # Steps 4-6: index tensors via repeat_interleave / range_arange.
    idx2, idx1, valid, n_out = range_arange_capped(bin_s.astype(POS_DTYPE), cnt, cap_out)
    # Step 7: intersection endpoints.
    s = jnp.maximum(s1[idx1], s2[idx2])
    e = jnp.minimum(e1[idx1], e2[idx2])
    sentinel = jnp.asarray(nrows, POS_DTYPE)
    s = jnp.where(valid, s, sentinel)
    e = jnp.where(valid, e, sentinel)
    idx1 = jnp.where(valid, idx1, 0)
    idx2 = jnp.where(valid, idx2, 0)
    return s, e, idx1, idx2, n_out


def range_intersect_masks(m1: RLEMask, m2: RLEMask, cap_out: int | None = None) -> RLEMask:
    """AND of two RLE masks (paper §5.1). Smaller input first is a perf
    heuristic in the paper; for static shapes we order by capacity."""
    if m2.capacity < m1.capacity:
        m1, m2 = m2, m1
    cap_out = cap_out or (m1.capacity + m2.capacity)
    s, e, _, _, n = range_intersect(
        m1.starts, m1.ends, m1.n, m2.starts, m2.ends, m2.n, m1.nrows, cap_out
    )
    return RLEMask(starts=s, ends=e, n=n, nrows=m1.nrows)


def range_intersect_multi(lists: Sequence[tuple], nrows: int, cap_out: int):
    """Intersect k sorted non-overlapping run lists in ONE fused sweep.

    Replaces k-1 chained pairwise ``range_intersect`` calls (whose
    intermediate capacities grow additively and whose bucketize work
    repeats at every stage) with a single coverage sweep: concatenate all
    run boundary events, sort once, and emit maximal intervals where the
    coverage count equals k.

    End events sort BEFORE start events at equal positions, so two
    adjacent runs of one list (a value change at row p) always produce a
    segment boundary — exactly matching the pairwise chain, which splits
    output runs at every source-run boundary. Alignment (§6) depends on
    this: segments must never span a run whose value changes.

    ``lists``: sequence of (starts, ends, n) with the sentinel invariant.
    Returns (s[cap_out], e[cap_out], idxs, n_out) where idxs[j][i] is the
    source run of list j covering output run i (0 where invalid).
    """
    k = len(lists)
    caps = [s.shape[0] for s, _, _ in lists]
    valids = [valid_slots(n, cap) for (_, _, n), cap in zip(lists, caps)]
    sentinel_pos = jnp.asarray(nrows + 1, POS_DTYPE)
    # end events first in the concat => stable argsort keeps them before
    # start events at equal positions (run boundaries split, never merge).
    pos = jnp.concatenate(
        [e + 1 for _, e, _ in lists] + [s for s, _, _ in lists]
    ).astype(POS_DTYPE)
    delta = jnp.concatenate(
        [jnp.where(v, -1, 0) for v in valids]
        + [jnp.where(v, 1, 0) for v in valids])
    pos = jnp.where(delta == 0, sentinel_pos, pos)
    order = jnp.argsort(pos, stable=True)
    pos_s, delta_s = pos[order], delta[order]
    cov = jnp.cumsum(delta_s)
    prev_cov = jnp.concatenate([jnp.zeros((1,), cov.dtype), cov[:-1]])
    # cov touches k only when every list covers; with ends-first ordering a
    # region opened at position p cannot close before p+1, so the i-th
    # start always pairs with the i-th end and no degenerate runs arise.
    start_flag = (cov == k) & (prev_cov < k) & (delta_s != 0)
    end_flag = (cov < k) & (prev_cov == k) & (delta_s != 0)
    (starts_out,), n_out = compact(start_flag, (pos_s,), cap_out, (nrows,))
    (ends_out,), _ = compact(end_flag, (pos_s - 1,), cap_out, (nrows,))
    valid = valid_slots(n_out, cap_out)
    sentinel = jnp.asarray(nrows, POS_DTYPE)
    s_out = jnp.where(valid, starts_out, sentinel).astype(POS_DTYPE)
    e_out = jnp.where(valid, ends_out, sentinel).astype(POS_DTYPE)
    # source run per output run and list: the run containing s_out.
    idxs = []
    for (s_j, _, n_j), cap_j in zip(lists, caps):
        sp = pad_positions(s_j, n_j, nrows)
        b = dispatch.bucketize(sp, s_out, right=True) - 1
        b = jnp.clip(b, 0, cap_j - 1)
        idxs.append(jnp.where(valid, b, 0).astype(POS_DTYPE))
    return s_out, e_out, idxs, n_out


# ---------------------------------------------------------------------------
# range_union (paper §5.2, RLE OR RLE) — vectorized sweep line
# ---------------------------------------------------------------------------


def range_union(
    s1: jax.Array, e1: jax.Array, n1: jax.Array,
    s2: jax.Array, e2: jax.Array, n2: jax.Array,
    nrows: int, cap_out: int,
):
    """Union of two sorted run lists. Returns (s, e, n_out).

    Sweep line over +1/-1 coverage deltas at run starts / (ends+1). Start
    events must land before end events at equal positions so adjacent runs
    merge maximally; that ordering comes from the concat layout (starts
    first) + a STABLE argsort on the position alone. (The previous
    ``pos * 2 + (delta < 0)`` composite key overflowed int32 for tables
    past 2^30 rows — sentinel positions sorted to the front and the union
    collapsed; positions stay un-doubled now, so any nrows <= 2^31 - 2 is
    safe.)
    """
    cap1, cap2 = s1.shape[0], s2.shape[0]
    v1, v2 = valid_slots(n1, cap1), valid_slots(n2, cap2)
    pos = jnp.concatenate([s1, s2, e1 + 1, e2 + 1]).astype(jnp.int32)
    delta = jnp.concatenate([
        jnp.where(v1, 1, 0), jnp.where(v2, 1, 0),
        jnp.where(v1, -1, 0), jnp.where(v2, -1, 0),
    ])
    # sentinel events (invalid slots) -> past-the-end position with delta 0
    pos = jnp.where(delta == 0, jnp.asarray(nrows + 1, jnp.int32), pos)
    order = jnp.argsort(pos, stable=True)
    pos_s, delta_s = pos[order], delta[order]
    cov = jnp.cumsum(delta_s)
    prev_cov = jnp.concatenate([jnp.zeros((1,), cov.dtype), cov[:-1]])
    # A union run starts at an event where coverage goes 0 -> >0 and ends at
    # the event where it returns to 0 (end position = event position - 1).
    start_flag = (cov > 0) & (prev_cov == 0) & (delta_s != 0)
    end_flag = (cov == 0) & (prev_cov > 0) & (delta_s != 0)
    (starts_out,), n_a = compact(start_flag, (pos_s,), cap_out, (nrows,))
    (ends_out,), n_b = compact(end_flag, (pos_s - 1,), cap_out, (nrows,))
    n_out = n_a  # == n_b by construction
    sentinel = jnp.asarray(nrows, POS_DTYPE)
    valid = valid_slots(n_out, cap_out)
    starts_out = jnp.where(valid, starts_out, sentinel).astype(POS_DTYPE)
    ends_out = jnp.where(valid, ends_out, sentinel).astype(POS_DTYPE)
    return starts_out, ends_out, n_out


# ---------------------------------------------------------------------------
# Index/RLE intersections (Algorithms 3-5)
# ---------------------------------------------------------------------------


def idx_in_rle_mask(
    pos: jax.Array, n_idx: jax.Array,
    rs: jax.Array, re: jax.Array, n_rle: jax.Array,
):
    """Algorithm 3 core: boolean mask over index slots + covering run id.

    Returns (mask[cap_idx], run_id[cap_idx]). mask[i] is True iff pos[i] falls
    inside some RLE run; run_id[i] is that run (0 where invalid).
    """
    cap_idx = pos.shape[0]
    bin_ = dispatch.bucketize(rs, pos, right=True) - 1
    ok = (bin_ >= 0) & (bin_ < n_rle)
    bin_c = jnp.clip(bin_, 0, rs.shape[0] - 1)
    mask = ok & (pos <= re[bin_c]) & valid_slots(n_idx, cap_idx)
    return mask, jnp.where(mask, bin_c, 0).astype(POS_DTYPE)


def idx_in_rle(c_idx_pos, n_idx, rs, re, n_rle, nrows: int, cap_out: int):
    """Algorithm 3: positions of an Index list falling inside RLE runs."""
    mask, run_id = idx_in_rle_mask(c_idx_pos, n_idx, rs, re, n_rle)
    (pos_out, run_out, src_out), n_out = compact(
        mask, (c_idx_pos, run_id, jnp.arange(c_idx_pos.shape[0], dtype=POS_DTYPE)),
        cap_out, (nrows, 0, 0),
    )
    return pos_out, run_out, src_out, n_out


def rle_contain_idx(c_idx_pos, n_idx, rs, re, n_rle, nrows: int, cap_out: int):
    """Algorithm 5: same result as Alg. 3, bucketizing the other way.

    Preferred when |idx| >> |rle| (paper §4.2). Returns
    (pos_out, run_out, src_out, n_out) matching idx_in_rle's contract.
    """
    cap_rle = rs.shape[0]
    bin_s = dispatch.bucketize(c_idx_pos, rs, right=False)
    bin_e = dispatch.bucketize(c_idx_pos, re, right=True) - 1
    ok = (bin_s <= bin_e) & valid_slots(n_rle, cap_rle)
    # clamp to the valid region of the index list
    bin_e = jnp.minimum(bin_e, n_idx - 1)
    lengths = jnp.where(ok, bin_e - bin_s + 1, 0)
    flat, run_src, valid, n_out = range_arange_capped(bin_s.astype(POS_DTYPE), lengths, cap_out)
    pos_out = jnp.where(valid, c_idx_pos[flat], jnp.asarray(nrows, POS_DTYPE))
    run_out = jnp.where(valid, run_src, 0).astype(POS_DTYPE)
    src_out = jnp.where(valid, flat, 0).astype(POS_DTYPE)
    return pos_out, run_out, src_out, n_out


def idx_in_idx(p1, n1, p2, n2, nrows: int, cap_out: int):
    """Algorithm 4: intersection of two sorted Index position lists.

    Returns (pos_out, src1_out, src2_out, n_out).
    """
    cap1 = p1.shape[0]
    bin_ = dispatch.bucketize(p2, p1, right=True) - 1
    ok = (bin_ >= 0) & (bin_ < n2) & valid_slots(n1, cap1)
    bin_c = jnp.clip(bin_, 0, p2.shape[0] - 1)
    mask = ok & (p1 == p2[bin_c])
    (pos_out, s1, s2), n_out = compact(
        mask, (p1, jnp.arange(cap1, dtype=POS_DTYPE), bin_c.astype(POS_DTYPE)),
        cap_out, (nrows, 0, 0),
    )
    return pos_out, s1, s2, n_out


def merge_sorted_idx(p1, n1, p2, n2, nrows: int, cap_out: int):
    """Union-merge two sorted unique position lists (paper §5.2 Index OR Index).

    concat + sort + dedup (the paper's concat_sort variant, which is the
    XLA-friendly one: a single bitonic sort beats data-dependent merging).
    Returns (pos_out, n_out).
    """
    sentinel = jnp.asarray(nrows, POS_DTYPE)
    q1 = pad_positions(p1, n1, nrows)
    q2 = pad_positions(p2, n2, nrows)
    allp = jnp.sort(jnp.concatenate([q1, q2]))
    first = (allp < sentinel) & ((jnp.arange(allp.shape[0]) == 0) | (allp != jnp.roll(allp, 1)))
    (pos_out,), n_out = compact(first, (allp,), cap_out, (nrows,))
    return pos_out, n_out


# ---------------------------------------------------------------------------
# Complements (Algorithms 6-7)
# ---------------------------------------------------------------------------


def complement_rle(rs, re, n, nrows: int):
    """Algorithm 6 (not_rle). Output capacity = cap + 1.

    Exploits the sentinel invariant: starts[n] == nrows already, so the final
    gap's end (= nrows - 1) falls out of the same vectorized expression.
    """
    cap = rs.shape[0]
    s = jnp.concatenate([jnp.full((1,), -1, POS_DTYPE), re]) + 1
    e = jnp.concatenate([rs, jnp.full((1,), nrows, POS_DTYPE)]) - 1
    keep = (s <= e) & (jnp.arange(cap + 1) <= n)
    (s_out, e_out), n_out = compact(keep, (s, e), cap + 1, (nrows, nrows))
    return s_out, e_out, n_out


def complement_index(pos, n, nrows: int):
    """Algorithm 7 (not_index): gaps between index points, RLE output."""
    cap = pos.shape[0]
    s = jnp.concatenate([jnp.full((1,), -1, POS_DTYPE), pos]) + 1
    e = jnp.concatenate([pos, jnp.full((1,), nrows, POS_DTYPE)]) - 1
    keep = (s <= e) & (s < nrows) & (e >= 0) & (jnp.arange(cap + 1) <= n)
    (s_out, e_out), n_out = compact(keep, (s, e), cap + 1, (nrows, nrows))
    return s_out, e_out, n_out


# ---------------------------------------------------------------------------
# Compaction of gapped encodings (Table 1: compact_rle, compact_rle+index)
# ---------------------------------------------------------------------------


def compact_rle(rs, re, n, nrows: int):
    """Renumber rows to remove gaps between runs (Table 1 compact_rle).

    After filtering, runs may have gaps; compaction maps them onto a dense
    0..total-1 row space (keeping run boundaries). Returns (s', e', n, new_nrows_count).
    """
    cap = rs.shape[0]
    valid = valid_slots(n, cap)
    lengths = jnp.where(valid, re - rs + 1, 0)
    ends_new = jnp.cumsum(lengths) - 1
    starts_new = ends_new - lengths + 1
    sentinel = jnp.asarray(nrows, POS_DTYPE)
    s_out = jnp.where(valid, starts_new.astype(POS_DTYPE), sentinel)
    e_out = jnp.where(valid, ends_new.astype(POS_DTYPE), sentinel)
    total = jnp.sum(lengths).astype(jnp.int32)
    return s_out, e_out, n, total


# ---------------------------------------------------------------------------
# Conversions (Table 1)
# ---------------------------------------------------------------------------


def rle_to_index(values, rs, re, n, nrows: int, cap_out: int):
    """Expand runs to individual (value, position) pairs."""
    rs, re = unpack_values(rs), unpack_values(re)
    cap = rs.shape[0]
    lengths = jnp.where(valid_slots(n, cap), re - rs + 1, 0)
    pos, src, valid, n_out = range_arange_capped(rs, lengths, cap_out)
    pos = jnp.where(valid, pos, jnp.asarray(nrows, POS_DTYPE))
    vals = (jnp.where(valid, unpack_values(values)[src], 0)
            if values is not None else None)
    return vals, pos, n_out


def rle_to_plain(values, rs, re, n, nrows: int, fill=0):
    """Expand RLE to a dense [nrows] array: the O(n) scatter+cumsum sweep
    (see encodings._run_id_per_row for why not binary search per row; the
    Pallas ``rle_decode`` kernel is off the route,
    ``dispatch.OFF_TPU_ROUTE``)."""
    from repro.core.encodings import _run_id_per_row, decode_rle_coverage
    rs, re = unpack_values(rs), unpack_values(re)
    if values is None:
        return decode_rle_coverage(rs, re, n, nrows)
    covered = decode_rle_coverage(rs, re, n, nrows)
    run = jnp.clip(_run_id_per_row(rs, n, nrows), 0, rs.shape[0] - 1)
    values = unpack_values(values)
    return jnp.where(covered, values[run], jnp.asarray(fill, values.dtype))


def plain_to_rle(values, cap_out: int, nrows: int | None = None):
    """Detect runs of equal consecutive values (Table 1 plain_to_rle)."""
    nrows = nrows or values.shape[0]
    i = jnp.arange(values.shape[0])
    newrun = (i == 0) | (values != jnp.roll(values, 1))
    (v_out, s_out), n_out = compact(newrun, (values, i.astype(POS_DTYPE)), cap_out, (0, nrows))
    # ends: next start - 1; last run ends at nrows-1. Sentinel slots hold
    # nrows so the shifted array gives nrows-1 for the last valid run.
    e_out = jnp.concatenate([s_out[1:], jnp.full((1,), nrows, POS_DTYPE)]) - 1
    e_out = jnp.where(valid_slots(n_out, cap_out), e_out, jnp.asarray(nrows, POS_DTYPE))
    return v_out, s_out, e_out, n_out


def plain_mask_to_rle(mask_values: jax.Array, cap_out: int):
    """Runs of True in a plain boolean mask."""
    nrows = mask_values.shape[0]
    i = jnp.arange(nrows)
    prev = jnp.roll(mask_values, 1).at[0].set(False)
    nxt = jnp.roll(mask_values, -1).at[-1].set(False)
    start_flag = mask_values & ~prev
    end_flag = mask_values & ~nxt
    (s_out,), n_s = compact(start_flag, (i.astype(POS_DTYPE),), cap_out, (nrows,))
    (e_out,), _ = compact(end_flag, (i.astype(POS_DTYPE),), cap_out, (nrows,))
    return s_out, e_out, n_s


def plain_mask_to_index(mask_values: jax.Array, cap_out: int):
    """Positions of True values."""
    nrows = mask_values.shape[0]
    i = jnp.arange(nrows, dtype=POS_DTYPE)
    (pos_out,), n_out = compact(mask_values, (i,), cap_out, (nrows,))
    return pos_out, n_out


def plain_to_plain_index(values, lo, hi, narrow_dtype, cap_outliers: int):
    """Bit-width reduction with outlier separation + centering (paper §3.2).

    Values in [lo, hi] go to the narrow base tensor, centered at the inlier
    mid-range; the rest become Index-encoded outliers.
    Returns (base_narrow, offset, out_positions, out_values, n_outliers).
    """
    nrows = values.shape[0]
    inlier = (values >= lo) & (values <= hi)
    center = (lo + hi) // 2 if jnp.issubdtype(values.dtype, jnp.integer) else (lo + hi) / 2
    base = jnp.where(inlier, values - center, 0).astype(narrow_dtype)
    i = jnp.arange(nrows, dtype=POS_DTYPE)
    (pos_out, val_out), n_out = compact(~inlier, (i, values), cap_outliers, (nrows, 0))
    return base, center, pos_out, val_out, n_out


def plain_to_rle_index(values, min_run: int, cap_runs: int, cap_idx: int, nrows: int | None = None):
    """Composite RLE+Index split (paper §3.2): runs >= min_run stay RLE,
    shorter 'impure' segments go to Index. Returns
    (rv, rs, re, rn, iv, ip, in_)."""
    nrows = nrows or values.shape[0]
    v, s, e, n = plain_to_rle(values, cap_out=values.shape[0], nrows=nrows)
    lengths = jnp.where(valid_slots(n, v.shape[0]), e - s + 1, 0)
    long_run = lengths >= min_run
    (rv, rs, re), rn = compact(long_run, (v, s, e), cap_runs, (0, nrows, nrows))
    # short runs -> index points
    short = (~long_run) & (lengths > 0)
    short_lengths = jnp.where(short, lengths, 0)
    pos, src, validx, in_ = range_arange_capped(s, short_lengths, cap_idx)
    pos = jnp.where(validx, pos, jnp.asarray(nrows, POS_DTYPE))
    iv = jnp.where(validx, v[src], 0)
    return rv, rs, re, rn, iv, pos, in_
