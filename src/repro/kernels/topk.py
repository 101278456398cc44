"""Pallas TPU kernel: partial-bitonic top-k selection.

The ordering subsystem's row-level hot path (DESIGN.md §10) is "find the k
best rows of a value tensor" — the kernel form of ``jax.lax.top_k``. The
classic GPU/TPU formulation is *partial* bitonic: instead of sorting all N
elements (O(N log^2 N) network), each tile keeps only a K-wide candidate
row and halves the candidate set with bitonic merges, so the network depth
is O(log^2 K · log(TILE/K)) per tile and tiles stream through the grid.

Per grid step (one TILE-element slab resident in VMEM as (TILE/K, K)
rows, K = 128 lanes whatever k is):

  1. bitonic-sort every row, the top half of the rows descending and the
     bottom half ascending (the compare-exchange network is unrolled at
     trace time; partners are lane rotations within a row),
  2. log2(TILE/K) merge rounds: pair row ``r`` of the top half with row
     ``r`` of the bottom half and keep the element-wise better — a
     descending row beside an ascending one, so this is the half-cleaner
     of a 2K bitonic merge and keeps the top-K of the union — then clean
     the resulting bitonic rows with a log2(K)-stage merge network, again
     top half descending and bottom half ascending,
  3. emit the surviving (1, K) values + source indices per tile.

Rows pair by halves, not by even/odd, and no row is reversed: Mosaic
lowers neither a strided sublane slice nor a lane reversal.

A final ``lax.top_k`` over the T·K survivors (T = #tiles, ≪ N) picks the
global top-k. The comparator is lexicographic ``(value desc, index asc)``
throughout, so ties resolve to the LOWEST source index — exactly
``lax.top_k``'s contract and pandas' stable descending sort, which the
parity tests assert element-for-element.

Ascending order is the caller's job (flip the rank key — order.py), as is
validity masking (invalid rows carry a worst-rank sentinel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 2048  # slab per grid step: (TILE/128, 128) values + indices resident
K = 128  # candidate row width: one lane-width
MAX_KERNEL_K = K


def _worst(dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).min
    return -jnp.inf


def _better(v, i, pv, pi):
    """Lexicographic (value desc, index asc): is the partner better?"""
    return (pv > v) | ((pv == v) & (pi < i))


def _cmpex(v, i, row_asc, jj: int, kk: int):
    """One compare-exchange stage at lane distance ``jj``. In a row whose
    ``row_asc`` is 0, lanes with ``(lane & kk) == 0`` sort descending
    (``kk=0``: every lane — the merge-network case); rows with 1 mirror
    that. The partner, lane ``l ^ jj``, comes from two lane rotations.
    Directions are 0/1 int32: Mosaic lowers no select between two masks."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    is_low = (lane & jj) == 0
    pv = jnp.where(is_low, pltpu.roll(v, K - jj, 1), pltpu.roll(v, jj, 1))
    pi = jnp.where(is_low, pltpu.roll(i, K - jj, 1), pltpu.roll(i, jj, 1))
    # an element wants the BETTER of the pair iff it is the low slot of a
    # descending block or the high slot of an ascending one
    high = jnp.minimum(lane & jj, 1)
    block_asc = jnp.minimum(lane & kk, 1) ^ row_asc
    p_better = jnp.where(_better(v, i, pv, pi), 1, 0)
    take = (p_better ^ high ^ block_asc) == 1
    return jnp.where(take, pv, v), jnp.where(take, pi, i)


def _halves_asc(rows: int):
    """Row directions for ``rows`` candidate rows about to be paired top
    half with bottom half: 0 (descending) for the top half, 1 for the
    bottom (a single row: descending)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return jnp.minimum(r // max(rows // 2, 1), 1)


def _topk_body(v_ref, ov_ref, oi_ref):
    t = pl.program_id(0)
    v = v_ref[...]
    rows = v.shape[0]
    i = (t * TILE + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) * K
         + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1))
    row_asc = _halves_asc(rows)
    kk = 2
    while kk <= K:  # bitonic sort of every row
        jj = kk // 2
        while jj >= 1:
            v, i = _cmpex(v, i, row_asc, jj, kk)
            jj //= 2
        kk *= 2
    while rows > 1:
        # a descending row beside an ascending one: the element-wise better
        # holds the top-K of their union as a bitonic row (a half-cleaner)
        h = rows // 2
        pb = _better(v[:h], i[:h], v[h:], i[h:])
        v = jnp.where(pb, v[h:], v[:h])
        i = jnp.where(pb, i[h:], i[:h])
        rows = h
        row_asc = _halves_asc(rows)
        jj = K // 2
        while jj >= 1:  # bitonic merge network cleans each row
            v, i = _cmpex(v, i, row_asc, jj, 0)
            jj //= 2
    ov_ref[...] = v
    oi_ref[...] = i


def topk_kernel(values: jax.Array, k: int, interpret: bool = False):
    """Top-k (descending) of a 1-D int32/float32 array.

    Returns ``(vals[k], idx[k])`` with lax.top_k tie semantics (equal
    values -> lowest index first). Padding slots carry the dtype's worst
    sentinel and past-the-end indices, so they lose every comparison a
    real element can win.
    """
    n = values.shape[0]
    if not 1 <= k <= MAX_KERNEL_K:
        raise ValueError(f"topk_kernel: k={k} outside [1, {MAX_KERNEL_K}]")
    pad = max(-(-n // TILE) * TILE, TILE)
    if pad != n:
        values = jnp.pad(values, (0, pad - n),
                         constant_values=_worst(values.dtype))
    n_tiles = pad // TILE
    # survivors leave as (1, K) lane-dense rows of a (1, n_tiles*K) output
    vals, idx = pl.pallas_call(
        _topk_body,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((TILE // K, K), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((1, K), lambda t: (0, t)),
                   pl.BlockSpec((1, K), lambda t: (0, t))],
        out_shape=[jax.ShapeDtypeStruct((1, n_tiles * K), values.dtype),
                   jax.ShapeDtypeStruct((1, n_tiles * K), jnp.int32)],
        name="topk_kernel",
        interpret=interpret,
    )(values.reshape(pad // K, K))
    vals, idx = vals[0], idx[0]
    if n_tiles == 1:
        return vals[:k], idx[:k]
    # Survivor reduction: T·K candidates, already per-tile sorted. Tiles
    # appear in index order and intra-tile ties kept the lowest indices, so
    # a plain value top_k over the candidate list preserves exact stable
    # tie order (first occurrence in the list == lowest source index).
    fv, slot = jax.lax.top_k(vals, k)
    return fv, idx[slot]
