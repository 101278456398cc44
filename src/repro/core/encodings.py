"""Encoded column/mask representations (paper §3).

Every encoding is a JAX pytree (registered dataclass) with:
  * static metadata: ``nrows`` (logical row count of the column), ``capacity``
    (max number of runs / index points the buffers can hold),
  * array leaves: fixed-``capacity`` buffers plus a dynamic scalar ``n`` count.

Padding convention (the *sentinel invariant*): slots at positions >= n hold
``starts = ends = nrows`` (RLE) or ``positions = nrows`` (Index) and
``values = 0``.  Because every valid position is < nrows, the sentinel keeps
the buffers sorted, which lets ``searchsorted``-based primitives operate on the
whole fixed-size buffer without masking the tail first.

The paper's PyTorch implementation uses dynamically sized tensors; the
capacity+count scheme is the TPU/XLA adaptation (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Positions use int32 by default: TPU has no native int64 ALU path and all
# target columns have nrows < 2**31 (DESIGN.md §3).
POS_DTYPE = jnp.int32


def _register(cls):
    """Register a dataclass as a pytree with static/dynamic field split."""
    data_fields = [f.name for f in dataclasses.fields(cls) if f.metadata.get("pytree", True)]
    meta_fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("pytree", True)]
    return jax.tree_util.register_dataclass(cls, data_fields=data_fields, meta_fields=meta_fields)


def static(**kw):
    return dataclasses.field(metadata={"pytree": False}, **kw)


# ---------------------------------------------------------------------------
# Data columns
# ---------------------------------------------------------------------------


@_register
@dataclasses.dataclass(frozen=True)
class PackedColumn:
    """Bit-packed integer buffer leaf (paper §3.2 taken sub-byte; §11).

    Stands in for a ``jax.Array`` in the buffer slots of the other
    encodings (plain values / dictionary codes, RLE values/starts/ends,
    index values/positions): unsigned ``bit_width``-bit codes densely
    packed into uint32 lanes, logical value = code + ``offset`` (int32,
    wrap-add — width-32 passthrough is exact by modular arithmetic).
    Packing is computed host-side at ingest (compress.pack_array) from the
    column's exact ``(lo, hi)`` domain, so a 9-bit dictionary code ships 9
    bits over PCIe instead of the 16/32 a whole-dtype narrowing would.

    Unpacking is LAZY and on-device: consumers call ``unpack_values`` /
    ``.unpack()``, which routes through ``dispatch.unpack`` (Pallas
    shift+mask kernel on TPU, inline XLA expression elsewhere) at TRACE
    time — inside the one jitted query program, where XLA fuses the
    extraction into the consumer instead of materializing a full-width
    copy in HBM. ``offset`` is a traced data leaf (like
    ``PlainColumn.offset``) so per-partition domains never retrace;
    ``bit_width``/``nrows`` are static because buffer shapes derive from
    them.

    ``nrows`` is the logical element count of the packed vector — rows
    for a plain payload, capacity for run/point buffers.
    """

    words: jax.Array  # uint32[ceil(nrows * bit_width / 32)]
    nrows: int = static(default=0)
    bit_width: int = static(default=32)
    offset: Any = 0

    # array-metadata duck-typing: capacity/shape probes on encodings whose
    # buffers are packed keep working without unpacking
    @property
    def shape(self):
        return (self.nrows,)

    @property
    def size(self) -> int:
        return self.nrows

    @property
    def dtype(self):
        return jnp.int32  # logical (unpacked) dtype

    def unpack(self) -> jax.Array:
        from repro.kernels import dispatch
        return dispatch.unpack(self)


def unpack_values(x):
    """Materialize a buffer slot: identity for arrays, routed lazy unpack
    for ``PackedColumn`` leaves. The single choke point every buffer READ
    goes through — under jit the unpack traces inline at the consumer, so
    XLA fuses (and CSEs) the shift+mask with whatever reads the values."""
    return x.unpack() if isinstance(x, PackedColumn) else x


@_register
@dataclasses.dataclass(frozen=True)
class PlainColumn:
    """Plain (uncompressed) column: 1:1 row-to-slot mapping (paper §3.1).

    ``offset`` implements the paper's §3.2 *centering* for bit-width reduction:
    logical value = values.astype(wide) + offset. offset == 0 for uncentered.
    It is a *data* leaf (traced, like the ``n`` counts), not static metadata:
    partitioned execution re-centers every partition independently, and a
    static center would retrace the query program once per partition
    (DESIGN.md §4).
    """

    values: jax.Array
    nrows: int = static(default=0)
    offset: Any = 0

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    def decode(self) -> jax.Array:
        """Materialize logical values (wide dtype).

        The device value domain is int32 (DESIGN.md §3) — wider integers are
        dictionary-encoded at ingest — so centering always widens to int32.
        """
        v = unpack_values(self.values)  # packed: offset folded into unpack
        if not offset_is_zero(self.offset):
            v = v.astype(jnp.int32 if jnp.issubdtype(v.dtype, jnp.integer) else v.dtype)
            v = v + self.offset
        return v


def offset_is_zero(offset) -> bool:
    """True only for a HOST-side zero offset. A traced/array offset is never
    "known zero": callers must take the general add-the-offset path."""
    return isinstance(offset, (int, float)) and offset == 0


@_register
@dataclasses.dataclass(frozen=True)
class RLEColumn:
    """Run-length encoded column: (values, starts, ends, n) (paper §3.1).

    Runs are sorted by start, non-overlapping; slot i covers rows
    starts[i]..ends[i] inclusive. Gaps are allowed (post-filter columns).
    """

    values: jax.Array
    starts: jax.Array
    ends: jax.Array
    n: jax.Array  # scalar int32: number of valid runs
    nrows: int = static(default=0)

    @property
    def capacity(self) -> int:
        return self.starts.shape[0]

    @property
    def lengths(self) -> jax.Array:
        """Run lengths (0 for padding slots)."""
        valid = jnp.arange(self.capacity) < self.n
        return jnp.where(
            valid, unpack_values(self.ends) - unpack_values(self.starts) + 1,
            0)


@_register
@dataclasses.dataclass(frozen=True)
class IndexColumn:
    """Index-encoded column: (values, positions, n), sorted positions (§3.1)."""

    values: jax.Array
    positions: jax.Array
    n: jax.Array
    nrows: int = static(default=0)

    @property
    def capacity(self) -> int:
        return self.positions.shape[0]


@_register
@dataclasses.dataclass(frozen=True)
class PlainIndexColumn:
    """Composite Plain + Index (paper §3.2): narrow-dtype base + outliers.

    base.values is the narrow tensor (centered via base.offset); outlier rows'
    base slots hold 0 (never read). outliers.values carries the wide values.
    """

    base: PlainColumn
    outliers: IndexColumn
    nrows: int = static(default=0)


@_register
@dataclasses.dataclass(frozen=True)
class RLEIndexColumn:
    """Composite RLE + Index (paper §3.2): pure runs + impure singletons.

    Positions covered by ``rle`` and ``idx`` are disjoint.
    """

    rle: RLEColumn
    idx: IndexColumn
    nrows: int = static(default=0)


# ---------------------------------------------------------------------------
# Mask columns (paper §3.3): value domain {T, F}; position-explicit encodings
# store only T positions and elide the value tensor.
# ---------------------------------------------------------------------------


@_register
@dataclasses.dataclass(frozen=True)
class PlainMask:
    values: jax.Array  # bool[nrows]
    nrows: int = static(default=0)

    @property
    def capacity(self) -> int:
        return self.values.shape[0]


@_register
@dataclasses.dataclass(frozen=True)
class RLEMask:
    starts: jax.Array
    ends: jax.Array
    n: jax.Array
    nrows: int = static(default=0)

    @property
    def capacity(self) -> int:
        return self.starts.shape[0]

    @property
    def lengths(self) -> jax.Array:
        valid = jnp.arange(self.capacity) < self.n
        return jnp.where(valid, self.ends - self.starts + 1, 0)


@_register
@dataclasses.dataclass(frozen=True)
class IndexMask:
    positions: jax.Array
    n: jax.Array
    nrows: int = static(default=0)

    @property
    def capacity(self) -> int:
        return self.positions.shape[0]


@_register
@dataclasses.dataclass(frozen=True)
class RLEIndexMask:
    rle: RLEMask
    idx: IndexMask
    nrows: int = static(default=0)


DataColumn = (PlainColumn, RLEColumn, IndexColumn, PlainIndexColumn, RLEIndexColumn)
MaskColumn = (PlainMask, RLEMask, IndexMask, RLEIndexMask)


# ---------------------------------------------------------------------------
# Constructors
# ---------------------------------------------------------------------------


def _as_pos(x) -> jax.Array:
    return jnp.asarray(x, dtype=POS_DTYPE)


def make_plain(values, nrows: Optional[int] = None, offset=0) -> PlainColumn:
    values = jnp.asarray(values)
    return PlainColumn(values=values, nrows=int(nrows if nrows is not None else values.shape[0]), offset=offset)


def make_rle(values, starts, ends, nrows: int, n=None, capacity: Optional[int] = None) -> RLEColumn:
    """Build an RLEColumn from (possibly unpadded) host/np arrays."""
    values = jnp.asarray(values)
    starts, ends = _as_pos(starts), _as_pos(ends)
    k = starts.shape[0]
    n = jnp.asarray(k if n is None else n, jnp.int32)
    cap = capacity or k
    if cap > k:
        pad = cap - k
        values = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
        starts = jnp.concatenate([starts, jnp.full((pad,), nrows, POS_DTYPE)])
        ends = jnp.concatenate([ends, jnp.full((pad,), nrows, POS_DTYPE)])
    return RLEColumn(values=values, starts=starts, ends=ends, n=n, nrows=nrows)


def make_index(values, positions, nrows: int, n=None, capacity: Optional[int] = None) -> IndexColumn:
    values = jnp.asarray(values)
    positions = _as_pos(positions)
    k = positions.shape[0]
    n = jnp.asarray(k if n is None else n, jnp.int32)
    cap = capacity or k
    if cap > k:
        pad = cap - k
        values = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
        positions = jnp.concatenate([positions, jnp.full((pad,), nrows, POS_DTYPE)])
    return IndexColumn(values=values, positions=positions, n=n, nrows=nrows)


def make_rle_mask(starts, ends, nrows: int, n=None, capacity: Optional[int] = None) -> RLEMask:
    c = make_rle(jnp.zeros((len(starts),), jnp.int8), starts, ends, nrows, n, capacity)
    return RLEMask(starts=c.starts, ends=c.ends, n=c.n, nrows=nrows)


def make_index_mask(positions, nrows: int, n=None, capacity: Optional[int] = None) -> IndexMask:
    c = make_index(jnp.zeros((len(positions),), jnp.int8), positions, nrows, n, capacity)
    return IndexMask(positions=c.positions, n=c.n, nrows=nrows)


def make_plain_mask(values, nrows: Optional[int] = None) -> PlainMask:
    values = jnp.asarray(values, jnp.bool_)
    return PlainMask(values=values, nrows=int(nrows if nrows is not None else values.shape[0]))


# ---------------------------------------------------------------------------
# Padding / slicing helpers used throughout the primitives
# ---------------------------------------------------------------------------


def valid_slots(n: jax.Array, capacity: int) -> jax.Array:
    """Boolean [capacity] mask of valid slots."""
    return jnp.arange(capacity) < n


def pad_positions(pos: jax.Array, n: jax.Array, nrows: int) -> jax.Array:
    """Force sentinel on invalid tail slots (restores sorted invariant)."""
    return jnp.where(valid_slots(n, pos.shape[0]), pos, jnp.asarray(nrows, pos.dtype))


def with_capacity_1d(x: jax.Array, cap: int, fill) -> jax.Array:
    """Pad or truncate a 1-D array to ``cap`` with ``fill``."""
    k = x.shape[0]
    if k == cap:
        return x
    if k > cap:
        return x[:cap]
    return jnp.concatenate([x, jnp.full((cap - k,), fill, x.dtype)])


# ---------------------------------------------------------------------------
# Decoding to plain (reference materialization; used by tests and as the
# rle_to_plain / idx_to_plain conversion primitives' core).
# ---------------------------------------------------------------------------


def _run_id_per_row(starts, n, nrows: int) -> jax.Array:
    """run id covering-or-preceding each row: cumsum of start deltas, O(n).

    The scatter+cumsum formulation replaces one binary search PER ROW with
    two O(runs) scatters + one O(n) prefix sum — ~40x faster on the XLA
    CPU backend and the same asymptotics on TPU (cumsum = efficient
    reduce-window). Sentinel starts (== nrows) drop out of range.
    """
    starts = unpack_values(starts)
    valid = valid_slots(n, starts.shape[0])
    delta = jnp.zeros((nrows + 1,), POS_DTYPE).at[starts].add(
        jnp.where(valid, 1, 0), mode="drop")
    return jnp.cumsum(delta[:nrows]) - 1  # -1 before the first run


def decode_rle_values(col: RLEColumn, fill=0) -> jax.Array:
    """Expand RLE to a dense [nrows] value array (gaps -> fill).

    One cumsum total: coverage is derived from the run id (row <= run end)
    instead of a second delta sweep, so pass count stays at one. The
    Pallas ``rle_decode`` kernels are off the route
    (``dispatch.OFF_TPU_ROUTE``)."""
    starts, ends = unpack_values(col.starts), unpack_values(col.ends)
    run_raw = _run_id_per_row(starts, col.n, col.nrows)
    run = jnp.clip(run_raw, 0, col.capacity - 1).astype(POS_DTYPE)
    rows = jnp.arange(col.nrows, dtype=POS_DTYPE)
    cov = (run_raw >= 0) & (rows <= ends[run]) & (run_raw < col.n)
    vals = unpack_values(col.values)[run]
    return jnp.where(cov, vals, jnp.asarray(fill, vals.dtype))


def decode_rle_coverage(starts, ends, n, nrows: int) -> jax.Array:
    """Boolean [nrows]: true where some run covers the row. O(n) sweep:
    +1 at run starts, -1 after run ends, prefix sum > 0."""
    starts, ends = unpack_values(starts), unpack_values(ends)
    valid = valid_slots(n, starts.shape[0])
    one = jnp.where(valid, 1, 0)
    delta = jnp.zeros((nrows + 1,), POS_DTYPE)
    delta = delta.at[starts].add(one, mode="drop")
    delta = delta.at[ends + 1].add(-one, mode="drop")
    return jnp.cumsum(delta[:nrows]) > 0


def decode_index_values(col: IndexColumn, fill=0) -> jax.Array:
    # Sentinel slots hold positions == nrows, which fall outside the output
    # and are dropped by mode="drop".
    vals = unpack_values(col.values)
    out = jnp.full((col.nrows,), fill, vals.dtype)
    return out.at[unpack_values(col.positions)].set(vals, mode="drop")


def decode_index_coverage(positions, n, nrows: int) -> jax.Array:
    positions = unpack_values(positions)
    out = jnp.zeros((nrows,), jnp.bool_)
    valid = valid_slots(n, positions.shape[0])
    return out.at[positions].set(valid, mode="drop")


def decode_mask(m) -> jax.Array:
    """Materialize any mask to bool[nrows]."""
    if isinstance(m, PlainMask):
        return m.values
    if isinstance(m, RLEMask):
        return decode_rle_coverage(m.starts, m.ends, m.n, m.nrows)
    if isinstance(m, IndexMask):
        return decode_index_coverage(m.positions, m.n, m.nrows)
    if isinstance(m, RLEIndexMask):
        return decode_mask(m.rle) | decode_mask(m.idx)
    raise TypeError(f"not a mask: {type(m)}")


def decode_column(c, fill=0) -> jax.Array:
    """Materialize any data column to dense [nrows] values (gaps -> fill)."""
    if isinstance(c, PlainColumn):
        return c.decode()
    if isinstance(c, RLEColumn):
        return decode_rle_values(c, fill)
    if isinstance(c, IndexColumn):
        return decode_index_values(c, fill)
    if isinstance(c, PlainIndexColumn):
        base = c.base.decode()
        cov = decode_index_coverage(c.outliers.positions, c.outliers.n, c.nrows)
        out_vals = decode_index_values(c.outliers, 0)
        return jnp.where(cov, out_vals.astype(base.dtype), base)
    if isinstance(c, RLEIndexColumn):
        rle_vals = decode_rle_values(c.rle, fill)
        rle_cov = decode_rle_coverage(c.rle.starts, c.rle.ends, c.rle.n, c.nrows)
        idx_cov = decode_index_coverage(c.idx.positions, c.idx.n, c.nrows)
        idx_vals = decode_index_values(c.idx, 0)
        out = jnp.where(rle_cov, rle_vals, jnp.asarray(fill, rle_vals.dtype))
        return jnp.where(idx_cov, idx_vals.astype(out.dtype), out)
    raise TypeError(f"not a data column: {type(c)}")


def coverage(c) -> jax.Array:
    """Boolean [nrows] of rows present in the (possibly gapped) column."""
    if isinstance(c, PlainColumn):
        return jnp.ones((c.nrows,), jnp.bool_)
    if isinstance(c, RLEColumn):
        return decode_rle_coverage(c.starts, c.ends, c.n, c.nrows)
    if isinstance(c, IndexColumn):
        return decode_index_coverage(c.positions, c.n, c.nrows)
    if isinstance(c, PlainIndexColumn):
        return jnp.ones((c.nrows,), jnp.bool_)
    if isinstance(c, RLEIndexColumn):
        return coverage(c.rle) | coverage(c.idx)
    raise TypeError(f"not a data column: {type(c)}")
