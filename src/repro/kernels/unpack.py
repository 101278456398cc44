"""Pallas TPU kernels: fused sub-byte bit-unpacking (DESIGN.md §11).

A bit-packed buffer stores unsigned codes at ``bit_width`` bits, densely
concatenated into uint32 lanes (value ``i`` occupies bit range
``[i*b, i*b + b)`` of the stream, little-endian within each lane). The
logical value is ``code + offset`` in int32 — centering folded into the
layout, exactly the paper's §3.2 bit-width reduction taken below whole
dtypes. Packing happens host-side at ingest (compress.pack_array); these
kernels are the device-side inverse, fused into the hot consumers so the
full-width tensor never lands in HBM:

  * ``unpack_kernel``        — standalone shift+mask expansion (the
    group-by key-scatter path and any ``decode_column`` consumer),
  * ``bucketize_packed_kernel`` — binary search over packed queries: each
    query tile is extracted in-register and fed straight to the bucketize
    bisection loop (the PK-FK probe / range-algorithm core),
  * ``rle_decode_packed_kernel`` — RLE expansion gathering the run value
    from packed words (run id -> word/shift -> value, one fused pass).

``unpack_kernel`` streams word tiles and extracts at static shifts (see
``_unpack_body``). The two fused kernels keep the packed words block
VMEM-resident per grid step (like the boundary block in bucketize.py) and
extract with a per-lane gather: ``w = i*b >> 5`` may straddle two lanes,
so two loads + shift + or + mask. ``i*b`` is computed as
``(i>>5)*b + ((i&31)*b >> 5)`` to stay inside int32 for any capacity the
engine supports. Mosaic lowers no 1-D gather, so dispatch routes neither
fused kernel on the TPU (``dispatch.OFF_TPU_ROUTE``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bucketize import _bsearch

VAL_TILE = 2048


def _extract(words: jax.Array, idx: jax.Array, bit_width: int,
             nwords: int) -> jax.Array:
    """Unsigned codes at positions ``idx`` of a packed uint32 stream.

    Pure jnp — shared by the kernel bodies below and ``ref.ref_unpack``.
    ``idx`` entries past the stream's end read clamped words and return
    garbage; callers mask/slice them away.
    """
    b = bit_width
    # i*b decomposed to avoid int32 overflow past 2**26 values
    w = (idx >> 5) * b + (((idx & 31) * b) >> 5)
    off = ((idx & 31) * b) & 31
    w = jnp.clip(w, 0, nwords - 1)
    w1 = jnp.clip(w + 1, 0, nwords - 1)
    off_u = off.astype(jnp.uint32)
    lo = jax.lax.shift_right_logical(jnp.take(words, w), off_u)
    # the straddle's contribution: zero-filled below (32 - off) bits, so
    # the final mask erases it whenever the value fits one lane; only the
    # off == 0 case needs a guard (shift by 32 is undefined)
    hi = jax.lax.shift_left(jnp.take(words, w1),
                            ((jnp.uint32(32) - off_u) & jnp.uint32(31)))
    hi = jnp.where(off == 0, jnp.uint32(0), hi)
    mask = jnp.uint32(0xFFFFFFFF) if b == 32 else jnp.uint32((1 << b) - 1)
    return (lo | hi) & mask


def _to_signed(codes: jax.Array, offset) -> jax.Array:
    """code + offset in int32. The bitcast (not a value convert) makes the
    width-32 passthrough exact: (v - offset) mod 2**32 stored, wrap-add of
    ``offset`` recovers v for every int32 v."""
    return (jax.lax.bitcast_convert_type(codes, jnp.int32)
            + jnp.asarray(offset, jnp.int32))


# ---------------------------------------------------------------------------
# Standalone unpack
# ---------------------------------------------------------------------------


ROW_VALS = 128  # values per output row: one lane-width
ROW_TILE = 512  # output rows per grid step (65536 values)


def _unpack_body(bit_width: int, w_ref, off_ref, out_ref):
    """A row of 128 values spans exactly ``4*b`` words, so value ``j`` of
    every row reads words ``(j*b)>>5`` (and the next, when it straddles)
    at a shift known at trace time: static sublane slices of the
    transposed ``(4b, ROW_TILE)`` word block, no gather (Mosaic lowers
    none over a 1-D block)."""
    b = bit_width
    w = w_ref[...]
    rows = []
    for j in range(ROW_VALS):
        wj, oj = (j * b) >> 5, (j * b) & 31
        v = jax.lax.shift_right_logical(w[wj:wj + 1], jnp.uint32(oj))
        if oj + b > 32:
            v = v | jax.lax.shift_left(w[wj + 1:wj + 2], jnp.uint32(32 - oj))
        if b < 32:
            v = v & jnp.uint32((1 << b) - 1)
        rows.append(v)
    codes = jnp.concatenate(rows, axis=0)  # (ROW_VALS, ROW_TILE)
    out_ref[...] = _to_signed(codes.T, off_ref[0])


def unpack_kernel(words: jax.Array, bit_width: int, offset, nvals: int,
                  interpret: bool = False) -> jax.Array:
    """Expand a packed stream to int32[nvals]. Words stream through the
    grid in row tiles, so VMEM use is fixed whatever the stream length."""
    b = bit_width
    rows = -(-nvals // ROW_VALS)
    rows_pad = -(-rows // ROW_TILE) * ROW_TILE
    words = jnp.pad(words, (0, rows_pad * 4 * b - words.shape[0]))
    off_arr = jnp.asarray(offset, jnp.int32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_unpack_body, b),
        grid=(rows_pad // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((4 * b, ROW_TILE), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # offset scalar
        ],
        out_specs=pl.BlockSpec((ROW_TILE, ROW_VALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, ROW_VALS), jnp.int32),
        name="unpack_kernel",
        interpret=interpret,
    )(words.reshape(rows_pad, 4 * b).T, off_arr)
    return out.reshape(-1)[:nvals]


# ---------------------------------------------------------------------------
# Fused unpack -> binary search (bucketize over packed queries)
# ---------------------------------------------------------------------------


def _bucketize_packed_body(right: bool, n_b: int, bit_width: int, nwords: int,
                           b_ref, w_ref, o_ref_scalar, out_ref):
    i = pl.program_id(0)
    idx = i * VAL_TILE + jax.lax.iota(jnp.int32, VAL_TILE)
    q = _to_signed(_extract(w_ref[...], idx, bit_width, nwords),
                   o_ref_scalar[0])
    out_ref[...] = _bsearch(b_ref[...], q, n_b, right)


def bucketize_packed_kernel(boundaries: jax.Array, words: jax.Array,
                            bit_width: int, offset, nvals: int,
                            right: bool = True,
                            interpret: bool = False) -> jax.Array:
    """``bucketize(boundaries, unpack(words))`` without materializing the
    unpacked query tensor: extraction feeds the bisection in-register."""
    n_b = boundaries.shape[0]
    nwords = words.shape[0]
    n_pad = -(-nvals // VAL_TILE) * VAL_TILE
    off_arr = jnp.asarray(offset, jnp.int32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_bucketize_packed_body, right, n_b, bit_width,
                          nwords),
        grid=(n_pad // VAL_TILE,),
        in_specs=[
            pl.BlockSpec((n_b,), lambda i: (0,)),  # boundaries resident
            pl.BlockSpec((nwords,), lambda i: (0,)),  # words resident
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((VAL_TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        name="bucketize_packed_kernel",
        interpret=interpret,
    )(boundaries, words, off_arr)
    return out[:nvals]


# ---------------------------------------------------------------------------
# Fused RLE decode with packed run values
# ---------------------------------------------------------------------------


def _rle_decode_packed_body(n_runs_cap: int, bit_width: int, nwords: int,
                            fill, w_ref, s_ref, e_ref, n_ref, o_ref_scalar,
                            out_ref):
    i = pl.program_id(0)
    rows = i * VAL_TILE + jax.lax.iota(jnp.int32, VAL_TILE)
    e = e_ref[...]
    run = _bsearch(e, rows, n_runs_cap, right=False)
    run = jnp.minimum(run, n_runs_cap - 1)
    s = jnp.take(s_ref[...], run)
    n = n_ref[0]
    covered = (rows >= s) & (rows <= jnp.take(e, run)) & (run < n)
    vals = _to_signed(_extract(w_ref[...], run, bit_width, nwords),
                      o_ref_scalar[0])
    out_ref[...] = jnp.where(covered, vals, jnp.asarray(fill, vals.dtype))


def rle_decode_packed_kernel(words: jax.Array, bit_width: int, offset,
                             cap: int, starts: jax.Array, ends: jax.Array,
                             n: jax.Array, nrows: int, fill=0,
                             interpret: bool = False) -> jax.Array:
    """RLE expansion whose run-value gather extracts straight from packed
    words (run id -> lane/shift) — the full-width value buffer is never
    materialized."""
    nwords = words.shape[0]
    rows_pad = -(-nrows // VAL_TILE) * VAL_TILE
    n_arr = jnp.asarray(n, jnp.int32).reshape((1,))
    off_arr = jnp.asarray(offset, jnp.int32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_rle_decode_packed_body, cap, bit_width, nwords,
                          fill),
        grid=(rows_pad // VAL_TILE,),
        in_specs=[
            pl.BlockSpec((nwords,), lambda i: (0,)),  # packed values resident
            pl.BlockSpec((cap,), lambda i: (0,)),  # starts resident
            pl.BlockSpec((cap,), lambda i: (0,)),  # ends resident
            pl.BlockSpec((1,), lambda i: (0,)),  # count scalar
            pl.BlockSpec((1,), lambda i: (0,)),  # offset scalar
        ],
        out_specs=pl.BlockSpec((VAL_TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows_pad,), jnp.int32),
        name="rle_decode_packed_kernel",
        interpret=interpret,
    )(words, starts, ends, n_arr, off_arr)
    return out[:nrows]
