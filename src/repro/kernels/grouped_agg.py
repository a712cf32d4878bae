"""Grouped aggregation via one-hot matmul (Pallas TPU).

The pushed-back form of grouped-aggregation pushdown (paper Table 1).
Hash tables — the CPU storage engine's implementation — do not vectorize
on a systolic array; the TPU-native formulation builds a per-tile one-hot
group matrix and contracts it against the values on the MXU:

    sums_partial (G,)  =  values (1, BLOCK) @ onehot (BLOCK, G)

accumulated across grid steps in the output block (same output block for
every step — a revisited accumulator, the standard Pallas reduction
pattern). G is capped by the tile budget (G <= 4096 comfortably fits VMEM);
larger group counts fall back to partial-agg + host merge, exactly like the
paper's two-phase S3-Select workaround — except one phase here is free.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.predicate_bitmap import resolve_interpret

DEFAULT_BLOCK = 8192


def _kernel(num_groups: int, ids_ref, val_ref, sum_ref, cnt_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    ids = ids_ref[...]                                     # (block,) int32
    vals = val_ref[...].astype(jnp.float32)                # (block,)
    onehot = (ids[:, None] == jnp.arange(num_groups)[None, :]
              ).astype(jnp.float32)                        # (block, G)
    # MXU contraction: (1, block) @ (block, G)
    part = jnp.dot(vals[None, :], onehot,
                   preferred_element_type=jnp.float32)[0]  # (G,)
    ones = jnp.dot(jnp.ones((1, ids.shape[0]), jnp.float32), onehot,
                   preferred_element_type=jnp.float32)[0]
    sum_ref[...] += part
    cnt_ref[...] += ones.astype(jnp.int32)


def grouped_agg(ids: jax.Array, values: jax.Array, num_groups: int,
                block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """ids: (R,) int32 in [0, num_groups); values: (R,).
    Returns (sums (G,) f32, counts (G,) int32). R % block == 0."""
    R = ids.shape[0]
    assert R % block == 0, (R, block)
    grid = (R // block,)
    return pl.pallas_call(
        functools.partial(_kernel, num_groups),
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((num_groups,), lambda i: (0,)),
                   pl.BlockSpec((num_groups,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((num_groups,), jnp.float32),
                   jax.ShapeDtypeStruct((num_groups,), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(ids, values)
