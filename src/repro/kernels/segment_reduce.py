"""Pallas TPU kernel: segment-sum via one-hot MXU matmul.

Group-by aggregation (paper §7.2) bottoms out in a scatter-reduce
(torch.scatter on GPU). TPUs have no global-memory atomics; the idiomatic
adaptation (DESIGN.md §3) turns the scatter into a matmul:

    partial[g] = Σ_t  onehot(ids[t] == g) · values[t]

Per input tile: build the (TILE × G) one-hot in VREGs, contract on the MXU,
accumulate into the resident (G,) output across the sequential grid. The
one-hot never exists in HBM. Works for any id order (sorted not required).

G (number of groups) must fit a VMEM block — up to ~4096 float32 lanes is
cheap. Larger G falls back to the XLA scatter path in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SEG_TILE = 1024


def _segsum_body(num_segments: int, v_ref, id_ref, o_ref, c_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    vals = v_ref[...].astype(jnp.float32)  # (T,)
    ids = id_ref[...]  # (T,)
    onehot = (ids[:, None] == jax.lax.iota(jnp.int32, num_segments)[None, :])
    # (1,T) @ (T,G) on the MXU. HIGHEST keeps float32 operands exact: the
    # default precision rounds them to bfloat16, which a SUM would show.
    partial = jnp.dot(vals[None, :], onehot.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)[0]
    # Kahan-compensated accumulation across grid steps: a plain running
    # add over the 4096 tiles of a 2^22-row partition drifts ~1.6e-5
    # relative from the exact sum; c_ref carries the lost low-order bits.
    y = partial - c_ref[...]
    acc = o_ref[...]
    t = acc + y
    c_ref[...] = (t - acc) - y
    o_ref[...] = t


def segment_sum_kernel(values: jax.Array, segment_ids: jax.Array,
                       num_segments: int, interpret: bool = False) -> jax.Array:
    """Segment sum; out-of-range ids (e.g. capacity padding) contribute 0."""
    n = values.shape[0]
    n_pad = -(-n // SEG_TILE) * SEG_TILE
    if n_pad != n:
        values = jnp.pad(values, (0, n_pad - n))
        segment_ids = jnp.pad(segment_ids, (0, n_pad - n),
                              constant_values=num_segments)  # dropped
    out = pl.pallas_call(
        functools.partial(_segsum_body, num_segments),
        grid=(n_pad // SEG_TILE,),
        in_specs=[
            pl.BlockSpec((SEG_TILE,), lambda i: (i,)),
            pl.BlockSpec((SEG_TILE,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((num_segments,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((num_segments,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((num_segments,), jnp.float32)],
        name="segment_sum_kernel",
        interpret=interpret,
    )(values, segment_ids)
    return out
