"""Pallas TPU kernel: vectorized ``bucketize`` (binary search).

bucketize is the engine's dominant primitive — it is the computational core
of range_intersect (Alg. 1), idx_in_rle (Alg. 3), idx_in_idx (Alg. 4),
rle_contain_idx (Alg. 5), run expansion and the sort-merge join probe. The
paper leans on torch.bucketize; this is the TPU-native equivalent.

Two variants (chosen by `ops.bucketize` based on boundary size; only the
second compiles for the TPU — ``_bsearch``'s per-lane 1-D gather has no
Mosaic lowering, see ``dispatch.OFF_TPU_ROUTE``):

1. ``bucketize_kernel`` — boundaries staged HBM->VMEM once per grid step
   (they fit VMEM up to ~2M int32 entries); each lane runs a branch-free
   log2(B)-step binary search (fori_loop with static trip count). Query
   tiles stream through the grid. Work O(Q log B), VMEM = B + Q_TILE.

2. ``bucketize_count_kernel`` — 2-D grid over (query tiles × boundary
   tiles); each step adds the per-tile counts #\\{j in tile : b[j] <= q\\}
   into the output block (sequential-grid accumulation). Work
   O(Q·B / lanes); dispatch routes it for boundary lists of one tile.

Both compute counts (== searchsorted indices), matching ref.ref_bucketize.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Q_TILE = 1024
B_TILE = 2048
# VMEM budget for the resident-boundaries variant (int32 words).
MAX_VMEM_BOUNDARIES = 1 << 21  # 2M entries = 8 MiB


def _bsearch(b, q, n_b: int, right: bool):
    """Branch-free vectorized binary search: count boundaries <=/< q."""
    steps = max(1, math.ceil(math.log2(n_b + 1)))
    lo = jnp.zeros(q.shape, jnp.int32)

    def body(k, lo):
        s = jnp.asarray(1 << (steps - 1), jnp.int32) >> k
        cand = lo + s
        ok = cand <= n_b
        v = jnp.take(b, jnp.clip(cand - 1, 0, n_b - 1))
        pred = ok & ((v <= q) if right else (v < q))
        return jnp.where(pred, cand, lo)

    return jax.lax.fori_loop(0, steps, body, lo)


def _bucketize_body(right: bool, n_b: int, b_ref, q_ref, o_ref):
    b = b_ref[...]
    q = q_ref[...]
    o_ref[...] = _bsearch(b, q, n_b, right)


def bucketize_kernel(boundaries: jax.Array, queries: jax.Array, right: bool = True,
                     interpret: bool = False) -> jax.Array:
    """Resident-boundaries variant. boundaries sorted 1-D; queries 1-D."""
    n_b = boundaries.shape[0]
    n_q = queries.shape[0]
    q_pad = -(-n_q // Q_TILE) * Q_TILE
    if q_pad != n_q:
        queries = jnp.pad(queries, (0, q_pad - n_q))
    grid = (q_pad // Q_TILE,)
    out = pl.pallas_call(
        functools.partial(_bucketize_body, right, n_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_b,), lambda i: (0,)),  # boundaries resident
            pl.BlockSpec((Q_TILE,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((Q_TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((q_pad,), jnp.int32),
        name="bucketize_kernel",
        interpret=interpret,
    )(boundaries, queries)
    return out[:n_q]


def _count_body(right: bool, n_b: int, b_ref, q_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    b = b_ref[...]
    q = q_ref[...]
    cmp = (b[None, :] <= q[:, None]) if right else (b[None, :] < q[:, None])
    # padding slots never count, even against a query equal to the pad value
    real = j * B_TILE + jax.lax.iota(jnp.int32, B_TILE) < n_b
    o_ref[...] += jnp.sum(cmp & real[None, :], axis=1).astype(jnp.int32)


def bucketize_count_kernel(boundaries: jax.Array, queries: jax.Array,
                           right: bool = True, interpret: bool = False) -> jax.Array:
    """Tiled-count variant: every query against every boundary tile."""
    n_b = boundaries.shape[0]
    n_q = queries.shape[0]
    q_pad = -(-n_q // Q_TILE) * Q_TILE
    b_pad = -(-n_b // B_TILE) * B_TILE
    if q_pad != n_q:
        queries = jnp.pad(queries, (0, q_pad - n_q))
    if b_pad != n_b:
        boundaries = jnp.pad(boundaries, (0, b_pad - n_b))
    grid = (q_pad // Q_TILE, b_pad // B_TILE)
    out = pl.pallas_call(
        functools.partial(_count_body, right, n_b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B_TILE,), lambda i, j: (j,)),
            pl.BlockSpec((Q_TILE,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((Q_TILE,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((q_pad,), jnp.int32),
        name="bucketize_count_kernel",
        interpret=interpret,
    )(boundaries, queries)
    return out[:n_q]
