"""Jit'd public wrappers for the Pallas kernels with XLA fallbacks.

Off the TPU the kernels run in interpret mode (``interpret=True`` executes
the kernel body in Python for correctness validation); on the TPU they
compile natively. ``interpret=None`` takes the dispatch policy's
``interpret_mode()``. ``use_pallas=False`` routes to the pure-jnp reference
implementations so the wrappers work on any backend.

These wrappers are the *explicit-choice* API (tests, microbenches). The
query pipeline itself routes through ``repro.kernels.dispatch``, which
makes the backend/size decision automatically at trace time.

Degenerate shapes (empty boundaries / queries / values, zero rows or
segments) always take the reference path: the kernels assume at least one
grid step and a non-empty resident block.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bucketize import (
    MAX_VMEM_BOUNDARIES,
    bucketize_count_kernel,
    bucketize_kernel,
)
from repro.kernels.dispatch import MAX_MATMUL_SEGMENTS, policy
from repro.kernels.rle_decode import rle_decode_kernel
from repro.kernels.segment_reduce import segment_sum_kernel
from repro.kernels.unpack import unpack_kernel


def _interpret(interpret: bool | None) -> bool:
    return policy().interpret_mode() if interpret is None else interpret


@partial(jax.jit, static_argnames=("right", "use_pallas", "interpret"))
def bucketize(boundaries, queries, right: bool = True, use_pallas: bool = False,
              interpret: bool | None = None):
    if (not use_pallas or boundaries.shape[0] == 0
            or queries.shape[0] == 0):
        return ref.ref_bucketize(boundaries, queries, right)
    interp = _interpret(interpret)
    if boundaries.shape[0] <= MAX_VMEM_BOUNDARIES:
        return bucketize_kernel(boundaries, queries, right, interpret=interp)
    return bucketize_count_kernel(boundaries, queries, right, interpret=interp)


@partial(jax.jit, static_argnames=("nrows", "fill", "use_pallas", "interpret"))
def rle_decode(values, starts, ends, n, nrows: int, fill=0,
               use_pallas: bool = False, interpret: bool | None = None):
    if nrows == 0:
        return jnp.zeros((0,), values.dtype)
    if values.shape[0] == 0:  # no run capacity at all: every row is a gap
        return jnp.full((nrows,), fill, values.dtype)
    if not use_pallas:
        return ref.ref_rle_decode(values, starts, ends, n, nrows, fill)
    interp = _interpret(interpret)
    return rle_decode_kernel(values, starts, ends, n, nrows, fill, interpret=interp)


@partial(jax.jit, static_argnames=("bit_width", "nvals", "use_pallas", "interpret"))
def unpack(words, bit_width: int, offset, nvals: int,
           use_pallas: bool = False, interpret: bool | None = None):
    """Expand a bit-packed uint32 stream to int32[nvals] (DESIGN.md §11)."""
    if nvals == 0 or words.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    if not use_pallas:
        return ref.ref_unpack(words, bit_width, offset, nvals)
    interp = _interpret(interpret)
    return unpack_kernel(words, bit_width, offset, nvals, interpret=interp)


@partial(jax.jit, static_argnames=("num_segments", "reduce", "use_pallas", "interpret"))
def segment_reduce(values, segment_ids, num_segments: int, reduce: str = "sum",
                   use_pallas: bool = False, interpret: bool | None = None):
    if (not use_pallas or reduce != "sum" or num_segments > MAX_MATMUL_SEGMENTS
            or num_segments == 0 or values.shape[0] == 0):
        return ref.ref_segment_reduce(values, segment_ids, num_segments, reduce)
    interp = _interpret(interpret)
    return segment_sum_kernel(values.astype(jnp.float32), segment_ids,
                              num_segments, interpret=interp)
