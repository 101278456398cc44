"""Pallas TPU kernel: fused RLE decode (binary search + gather).

``rle_to_plain`` / run expansion is the engine's second hot spot: one binary
search over run *ends* per output row, then a gather of the run value, fused
so the run id never round-trips to HBM. This is the TPU-native adaptation of
torch.repeat_interleave-style expansion (DESIGN.md §3).

Run metadata (values/starts/ends) is staged HBM->VMEM once per grid step;
output row tiles stream through the grid. VMEM = 3·R + TILE; work
O(nrows · log R). The run-value fetch and ``_bsearch`` are per-lane 1-D
gathers, which Mosaic refuses, so dispatch never routes this kernel
(``dispatch.OFF_TPU_ROUTE``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bucketize import _bsearch

ROW_TILE = 2048


def _decode_body(n_runs_cap: int, fill, v_ref, s_ref, e_ref, n_ref, o_ref):
    i = pl.program_id(0)
    rows = i * ROW_TILE + jax.lax.iota(jnp.int32, ROW_TILE)
    e = e_ref[...]
    # run = first run whose end >= row  == count of ends < row (side left)
    run = _bsearch(e, rows, n_runs_cap, right=False)
    run = jnp.minimum(run, n_runs_cap - 1)
    s = jnp.take(s_ref[...], run)
    n = n_ref[0]
    covered = (rows >= s) & (rows <= jnp.take(e, run)) & (run < n)
    vals = jnp.take(v_ref[...], run)
    o_ref[...] = jnp.where(covered, vals, jnp.asarray(fill, vals.dtype))


def rle_decode_kernel(values: jax.Array, starts: jax.Array, ends: jax.Array,
                      n: jax.Array, nrows: int, fill=0,
                      interpret: bool = False) -> jax.Array:
    """Decode an RLE column (capacity buffers + count) to dense [nrows]."""
    cap = values.shape[0]
    rows_pad = -(-nrows // ROW_TILE) * ROW_TILE
    n_arr = jnp.asarray(n, jnp.int32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_decode_body, cap, fill),
        grid=(rows_pad // ROW_TILE,),
        in_specs=[
            pl.BlockSpec((cap,), lambda i: (0,)),  # values resident
            pl.BlockSpec((cap,), lambda i: (0,)),  # starts resident
            pl.BlockSpec((cap,), lambda i: (0,)),  # ends resident
            pl.BlockSpec((1,), lambda i: (0,)),  # count scalar
        ],
        out_specs=pl.BlockSpec((ROW_TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows_pad,), values.dtype),
        name="rle_decode_kernel",
        interpret=interpret,
    )(values, starts, ends, n_arr)
    return out[:nrows]
