"""Join operations on encoded columns (paper §8 + Appendix A.3).

TPU adaptation (DESIGN.md §3): the paper's GPU hash join becomes a
*sort-merge* join — the build side is sorted by key (once), probes are two
``searchsorted`` calls giving per-probe match ranges, and expansion reuses the
``range_arange`` machinery (Alg. 2). Semantics, including Table 6 Join-Index
encodings and run-length expansion for one-to-many / many-to-many matches,
follow the paper.

Join entries operate at the *encoding granularity* (runs for RLE, points for
Index, rows for Plain): a matching RLE run pair contributes len_l × len_r
row pairs without being expanded until/unless a consumer needs rows — the
paper's "treat each run like a single row in the hash table" (§8.1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import primitives as prim
from repro.core.encodings import (
    POS_DTYPE,
    IndexColumn,
    IndexMask,
    PlainColumn,
    PlainIndexColumn,
    PlainMask,
    RLEColumn,
    RLEIndexColumn,
    RLEMask,
    decode_column,
    unpack_values,
    valid_slots,
)
from repro.kernels import dispatch


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class JoinEntries:
    """Encoding-granular view of a join column."""

    keys: jax.Array  # key value per entry
    row_start: jax.Array  # first row covered by the entry
    length: jax.Array  # rows covered (1 for Plain/Index)
    n: jax.Array  # valid entries


def join_entries(col) -> JoinEntries:
    if isinstance(col, PlainColumn):
        nr = col.capacity
        return JoinEntries(
            keys=col.decode(),
            row_start=jnp.arange(nr, dtype=POS_DTYPE),
            length=jnp.ones((nr,), POS_DTYPE),
            n=jnp.asarray(nr, jnp.int32),
        )
    if isinstance(col, RLEColumn):
        return JoinEntries(keys=unpack_values(col.values),
                           row_start=unpack_values(col.starts),
                           length=col.lengths.astype(POS_DTYPE), n=col.n)
    if isinstance(col, IndexColumn):
        valid = valid_slots(col.n, col.capacity)
        return JoinEntries(keys=unpack_values(col.values),
                           row_start=unpack_values(col.positions),
                           length=jnp.where(valid, 1, 0).astype(POS_DTYPE), n=col.n)
    raise TypeError(type(col))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class JoinIndex:
    """Compressed (entry-level) join index: one slot per matching entry pair.

    ``multiplicity`` = len_l × len_r row pairs per slot; the pair expands to
    rows only on demand (expand_pairs_to_rows). This is the paper's
    RLE-encoded Join Index (Table 6) in capacity form.
    """

    left_entry: jax.Array
    right_entry: jax.Array
    left_start: jax.Array
    left_len: jax.Array
    right_start: jax.Array
    right_len: jax.Array
    n: jax.Array  # valid pair count
    total_rows: jax.Array  # Σ multiplicity


def join_index(left, right, cap_pairs: int) -> JoinIndex:
    """Get Join Index (paper §8.1) via sort-merge probe.

    ``right`` is the build side (sorted by key inside); ``left`` probes.
    """
    le = join_entries(left)
    re_ = join_entries(right)
    capL, capR = le.keys.shape[0], re_.keys.shape[0]
    # sort build side by key; sentinel-key invalid entries to the top
    big = _big_for(re_.keys.dtype)
    rkey = jnp.where(valid_slots(re_.n, capR), re_.keys, big)
    order = jnp.argsort(rkey)
    rk = rkey[order]
    # probe: match range per left entry (dispatch-routed binary search —
    # the bucketize kernel on TPU, XLA searchsorted otherwise)
    lkey = jnp.where(valid_slots(le.n, capL), le.keys, big)
    lo = dispatch.bucketize(rk, lkey, right=False)
    hi = dispatch.bucketize(rk, lkey, right=True)
    cnt = jnp.where(valid_slots(le.n, capL) & (lkey != big), hi - lo, 0)
    # expand (left_entry, right_sorted_slot) pairs
    slot, l_ent, valid, n_pairs = prim.range_arange_capped(
        lo.astype(POS_DTYPE), cnt, cap_pairs)
    r_ent = order[slot].astype(POS_DTYPE)
    l_ent = jnp.where(valid, l_ent, 0).astype(POS_DTYPE)
    r_ent = jnp.where(valid, r_ent, 0)
    l_len = jnp.where(valid, le.length[l_ent], 0)
    r_len = jnp.where(valid, re_.length[r_ent], 0)
    mult = l_len.astype(jnp.int32) * r_len.astype(jnp.int32)
    return JoinIndex(
        left_entry=l_ent, right_entry=r_ent,
        left_start=le.row_start[l_ent], left_len=l_len,
        right_start=re_.row_start[r_ent], right_len=r_len,
        n=n_pairs, total_rows=jnp.sum(mult).astype(jnp.int32),
    )


def expand_pairs_to_rows(ji: JoinIndex, cap_rows: int):
    """Apply Join Index at row granularity (paper §8.2, A.3 steps 2-3).

    Each pair yields len_l × len_r (left_row, right_row) combinations:
    left varies slowest (matches the paper's run-major duplication order).
    Returns (left_rows, right_rows, valid, total).
    """
    mult = (ji.left_len * ji.right_len).astype(jnp.int32)
    pair, valid, total = prim.repeat_interleave_capped(mult, cap_rows)
    offsets = jnp.cumsum(mult)
    prev = jnp.concatenate([jnp.zeros((1,), offsets.dtype), offsets[:-1]])
    t = jnp.arange(cap_rows, dtype=offsets.dtype) - prev[pair]
    rl = jnp.maximum(ji.right_len[pair], 1).astype(t.dtype)
    l_rows = ji.left_start[pair] + (t // rl).astype(POS_DTYPE)
    r_rows = ji.right_start[pair] + (t % rl).astype(POS_DTYPE)
    l_rows = jnp.where(valid, l_rows, 0)
    r_rows = jnp.where(valid, r_rows, 0)
    return l_rows, r_rows, valid, total


def gather_rows(col, rows: jax.Array, valid: jax.Array):
    """Apply Join Index to a payload column: fetch values at (unsorted,
    possibly duplicated) row ids — Table 2's Unsorted-Index extension.

    For RLE payload columns the fetch is a binary search per row (run id ->
    value), i.e. the column is never decompressed (paper §8.2).
    """
    if isinstance(col, PlainColumn):
        vals = col.decode()[rows]
    elif isinstance(col, RLEColumn):
        starts, ends = unpack_values(col.starts), unpack_values(col.ends)
        run = dispatch.bucketize(ends, rows, right=False).astype(POS_DTYPE)
        run = jnp.minimum(run, col.capacity - 1)
        inside = (rows >= starts[run]) & (rows <= ends[run]) & (run < col.n)
        vals = jnp.where(inside, unpack_values(col.values)[run], 0)
    elif isinstance(col, IndexColumn):
        positions = unpack_values(col.positions)
        slot = dispatch.bucketize(positions, rows,
                                  right=False).astype(POS_DTYPE)
        slot = jnp.minimum(slot, col.capacity - 1)
        hit = (positions[slot] == rows) & (slot < col.n)
        vals = jnp.where(hit, unpack_values(col.values)[slot], 0)
    else:
        raise TypeError(type(col))
    return jnp.where(valid, vals, 0)


# ---------------------------------------------------------------------------
# PK-FK star-schema join (paper §8.1 + Table 6): probe a unique-key
# dimension side at ENCODING granularity — one lookup per run/point/row,
# never a run expansion (a PK match is at most one dimension row, so the
# Join Index degenerates to a gather and stays in the fact encoding).
# ---------------------------------------------------------------------------

# Largest fact-FK domain, in slots, the direct-address probe maps. The map
# is built on the host, shipped and held on the device once per execution:
# 2^22 int32 slots are 16 MiB, less than one streamed 2^20-row partition
# of a wide fact table ships (SSB lineorder: about 29 MiB packed), so the
# map never costs more transfer or HBM than the partitions it serves.
# Wider domains keep the sorted build side and its binary search.
DIRECT_MAP_MAX_SLOTS = 1 << 22


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DirectMap:
    """Direct-address build side of a PK-FK join over the fact FK's
    ingest domain ``[lo, hi]``: ``rows[k - lo]`` is the dimension row
    (index into the payload arrays) of the surviving key ``k``, -1 where no
    row survives. One row-sized gather replaces the binary search."""

    rows: jax.Array  # (hi - lo + 1,) int32
    lo: jax.Array  # int32 scalar: the domain's first key
    hi: jax.Array  # int32 scalar: the domain's last key


def pk_fk_join(fact_key_col, dim_keys, n_dim: jax.Array,
               payloads: dict, fill=0):
    """PK-FK probe: returns ``(mask, gathered)``.

    ``dim_keys`` is the build side the plan layer prepares once per query:
    a ``DirectMap`` over the fact FK's domain (one gather per probe), or
    the surviving dimension PK values in the fact key's value space,
    sorted, sentinel-padded past ``n_dim`` (a binary search per probe).
    ``payloads`` maps names to dense per-dimension-row value arrays in the
    build side's row order.

    ``mask`` is the inner-join membership mask in the fact column's own
    encoding (whole RLE runs pass/fail together — §8.1's "treat each run
    like a single row"); ``gathered`` maps each payload name to a column in
    the fact key's encoding carrying the matched dimension attribute (the
    Table 6 Join-Index output applied to the payload, without expansion).
    Composite fact encodings (Plain+Index / RLE+Index) probe their decoded
    row-level form.
    """
    if isinstance(fact_key_col, (PlainIndexColumn, RLEIndexColumn)):
        fact_key_col = PlainColumn(values=decode_column(fact_key_col),
                                   nrows=fact_key_col.nrows)

    def probe(keys, kvalid):
        if isinstance(dim_keys, DirectMap):
            # keys outside [lo, hi] never match; the offset is taken only
            # inside it, where it cannot wrap
            k = unpack_values(keys)
            inside = (k >= dim_keys.lo) & (k <= dim_keys.hi)
            off = jnp.where(inside, k - dim_keys.lo, 0)
            row = dispatch.direct_lookup(dim_keys.rows, off)
            return jnp.maximum(row, 0), kvalid & inside & (row >= 0)
        # packed fact keys route to the fused unpack->bisect kernel; the
        # membership equality reads the (lazily) unpacked codes, which XLA
        # CSEs with any other consumer of the same extraction
        slot = dispatch.bucketize(dim_keys, keys, right=False)
        slot_c = jnp.minimum(slot, dim_keys.shape[0] - 1)
        hit = kvalid & (slot < n_dim) & (dim_keys[slot_c] == unpack_values(keys))
        return slot_c, hit

    def gathered_values(p, slot, hit):
        return jnp.where(hit, p[slot], jnp.asarray(fill, p.dtype))

    if isinstance(fact_key_col, PlainColumn):
        slot, hit = probe(fact_key_col.decode(), True)
        mask = PlainMask(values=hit, nrows=fact_key_col.nrows)
        gathered = {
            name: PlainColumn(values=gathered_values(p, slot, hit),
                              nrows=fact_key_col.nrows)
            for name, p in payloads.items()}
        return mask, gathered

    if isinstance(fact_key_col, RLEColumn):
        c = fact_key_col
        slot, hit = probe(c.values, valid_slots(c.n, c.capacity))
        (s, e), n = prim.compact(
            hit, (unpack_values(c.starts), unpack_values(c.ends)), c.capacity,
            (c.nrows, c.nrows))
        mask = RLEMask(starts=s, ends=e, n=n, nrows=c.nrows)
        # gathered columns keep the fact key's FULL run structure (misses
        # hold ``fill`` and are excluded by the mask), so later alignment
        # sees the same segmentation as the key column itself.
        gathered = {
            name: RLEColumn(values=gathered_values(p, slot, hit),
                            starts=c.starts, ends=c.ends, n=c.n, nrows=c.nrows)
            for name, p in payloads.items()}
        return mask, gathered

    if isinstance(fact_key_col, IndexColumn):
        c = fact_key_col
        slot, hit = probe(c.values, valid_slots(c.n, c.capacity))
        (pos,), n = prim.compact(hit, (unpack_values(c.positions),),
                                 c.capacity, (c.nrows,))
        mask = IndexMask(positions=pos, n=n, nrows=c.nrows)
        gathered = {
            name: IndexColumn(values=gathered_values(p, slot, hit),
                              positions=c.positions, n=c.n, nrows=c.nrows)
            for name, p in payloads.items()}
        return mask, gathered

    raise TypeError(type(fact_key_col))


# ---------------------------------------------------------------------------
# Semi-join (the production-workload workhorse: 7-10 semi-joins per query)
# ---------------------------------------------------------------------------


def semi_join_mask(left, right_keys: jax.Array, n_right: jax.Array):
    """LEFT SEMI JOIN membership mask, in the left column's own encoding.

    ``right_keys`` must be sorted with invalid slots at the top (sentinel).
    For an RLE left column, membership is decided once per *run* — whole runs
    pass/fail together (App. D's 'early filtering of entire runs').
    """
    def member(keys, kvalid):
        # packed left keys hit the fused unpack->bisect kernel (DESIGN §11)
        lo = dispatch.bucketize(right_keys, keys, right=False)
        lo_c = jnp.minimum(lo, right_keys.shape[0] - 1)
        return kvalid & (lo < n_right) & (right_keys[lo_c] == unpack_values(keys))

    if isinstance(left, PlainColumn):
        return PlainMask(values=member(left.decode(), True), nrows=left.nrows)
    if isinstance(left, RLEColumn):
        keep = member(left.values, valid_slots(left.n, left.capacity))
        (s, e), n = prim.compact(
            keep, (unpack_values(left.starts), unpack_values(left.ends)),
            left.capacity, (left.nrows, left.nrows))
        return RLEMask(starts=s, ends=e, n=n, nrows=left.nrows)
    if isinstance(left, IndexColumn):
        keep = member(left.values, valid_slots(left.n, left.capacity))
        (p,), n = prim.compact(keep, (unpack_values(left.positions),),
                               left.capacity, (left.nrows,))
        return IndexMask(positions=p, n=n, nrows=left.nrows)
    raise TypeError(type(left))


def sorted_unique_keys(values: jax.Array, valid: jax.Array, cap: int):
    """Helper to build the right-side key set for semi_join_mask."""
    uniq, _, n = prim.unique_with_inverse(values, valid, cap)
    big = _big_for(uniq.dtype)
    uniq = jnp.where(valid_slots(n, cap), uniq, big)
    return uniq, n


def _big_for(dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    return jnp.asarray(jnp.inf, dtype)
