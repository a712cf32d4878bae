"""Shuffle partition function + histogram (Pallas TPU).

The partitioning half of distributed-data-shuffle pushdown (paper §4.2,
Fig 5): assign each row its destination compute node and count rows per
target. Knuth multiplicative hashing runs in uint32 VREG lanes; the
histogram is a one-hot MXU contraction per block, accumulated over the
grid in one revisited (1, P) output block (TPUs have no scatter unit — the
actual reorder is an XLA sort keyed on the partition id, or on the host;
the paper's storage nodes buffer per-target anyway).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.predicate_bitmap import resolve_interpret

DEFAULT_BLOCK = 8192
KNUTH = 2654435761


def _kernel(num_parts: int, keys_ref, pid_ref, hist_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    keys = keys_ref[...].astype(jnp.uint32)
    h = keys * jnp.uint32(KNUTH)                       # wraps mod 2^32
    pid = ((h >> jnp.uint32(16)) % jnp.uint32(num_parts)).astype(jnp.int32)
    pid_ref[...] = pid
    onehot = (pid[:, None] == jnp.arange(num_parts)[None, :]
              ).astype(jnp.float32)
    ones = jnp.dot(jnp.ones((1, pid.shape[0]), jnp.float32), onehot,
                   preferred_element_type=jnp.float32)[0]
    hist_ref[...] += ones.astype(jnp.int32)[None, :]


def hash_partition(keys: jax.Array, num_parts: int,
                   block: int = DEFAULT_BLOCK,
                   interpret: Optional[bool] = None):
    """keys: (R,) int32/uint32, R % block == 0.
    Returns (pids (R,) int32, hist (1, P) int32)."""
    R = keys.shape[0]
    assert R % block == 0, (R, block)
    grid = (R // block,)
    return pl.pallas_call(
        functools.partial(_kernel, num_parts),
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec((1, num_parts), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R,), jnp.int32),
                   jax.ShapeDtypeStruct((1, num_parts), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(keys)
