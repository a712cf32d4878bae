"""Apply a packed selection bitmap to a column (Pallas TPU).

The compute-layer half of selection-bitmap pushdown (paper §4.2, Figs 3/4):
a bitmap shipped across the network filters a *device-cached* column.

TPU adaptation: late materialization — the output keeps the input's shape
with dropped rows zeroed, plus the selected-row count, accumulated over the
grid in one revisited (1, 1) block. Row compaction is a data-dependent
scatter (a sort on TPU) and is deliberately NOT done here; downstream
consumers either work on masked form directly (aggregations) or compact
once on the host. Bits unpack with a per-lane variable shift of the lane's
own word (``predicate_bitmap.unpack_lanes``) — branch-free VREG bit
twiddling over (rows, 128) tiles.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.predicate_bitmap import (LANES, WORDS_PER_ROW, as_tiles,
                                            check_block, resolve_interpret,
                                            unpack_lanes)

DEFAULT_BLOCK = 8192


def _kernel(words_ref, col_ref, out_ref, cnt_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    bits = unpack_lanes(words_ref[...])                     # (rows, 128)
    col = col_ref[...]
    out_ref[...] = jnp.where(bits == 1, col, jnp.zeros((), col.dtype))
    cnt_ref[...] += jnp.sum(jnp.sum(bits, axis=1, keepdims=True), axis=0,
                            keepdims=True)


def bitmap_apply(words: jax.Array, col: jax.Array,
                 block: int = DEFAULT_BLOCK,
                 interpret: Optional[bool] = None):
    """words: (R/32,) uint32; col: (R,). R % block == 0, block a multiple
    of 1024. Returns (masked column (R,), selected-row count (1, 1) int32
    accumulated over the grid in one revisited block)."""
    R = col.shape[0]
    check_block(R, block)
    assert words.shape[0] == R // 32
    rows = block // LANES
    w = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        -1, WORDS_PER_ROW)
    masked, cnt = pl.pallas_call(
        _kernel,
        grid=(R // block,),
        in_specs=[pl.BlockSpec((rows, WORDS_PER_ROW), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R // LANES, LANES), col.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(w, as_tiles(col))
    return masked.reshape(R), cnt
