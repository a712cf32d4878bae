"""Fused predicate -> packed bitmap -> hash partition (Pallas TPU).

The storage-side hot path of a pushed filter + shuffle (or bitmap-exchange)
chain as ONE kernel — the device mirror of the numpy batch executor's aux
emission (``core.executor._emit_aux``). Per row tile:

- the compiled predicate tree evaluates branch-free over VREG-resident
  column tiles (as in ``predicate_bitmap``),
- the boolean row mask packs 32 rows/word with the weighted-sum-over-lanes
  contraction (``predicate_bitmap.pack_lanes``: disjoint powers of two make
  SUM == OR),
- the shuffle key hashes to its target compute node in uint32 lanes (as in
  ``hash_partition``),
- and a mask-gated tile reduction per target counts the *surviving* rows
  per target into one revisited (1, P) block — the per-target output sizes
  the storage node's pull buffers need (§4.2), in the same pass.

Fusion removes the two HBM round-trips the three-kernel pipeline
(``predicate_bitmap`` -> ``bitmap_apply`` -> ``hash_partition``) pays
between predicate, apply, and partition.

A ``valid`` lane (1 real row / 0 padding) rides along with the columns so
padding rows can never set a bitmap bit or count toward a target — the
wrapper needs no tail-word masking and no histogram subtraction.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.predicate_bitmap import (LANES, WORDS_PER_ROW, as_tiles,
                                            check_block, pack_lanes,
                                            resolve_interpret, words_out)

DEFAULT_BLOCK = 8192
KNUTH = 2654435761


def _kernel(pred_fn: Optional[Callable], names: Sequence[str],
            num_parts: int, *refs):
    *col_refs, key_ref, valid_ref, words_ref, pid_ref, hist_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    cols = {n: r[...] for n, r in zip(names, col_refs)}
    keep = (pred_fn(cols) if pred_fn is not None
            else jnp.ones(key_ref.shape, bool))
    keep = keep & (valid_ref[...] > 0)                   # (rows, 128) bool
    # pack: 32 rows/word, little-endian bit order (== np.packbits)
    words_ref[...] = pack_lanes(keep)
    # hash: Knuth multiplicative, wraps mod 2^32 in uint32 lanes
    keys = key_ref[...].astype(jnp.uint32)
    h = keys * jnp.uint32(KNUTH)
    pid = ((h >> jnp.uint32(16)) % jnp.uint32(num_parts)).astype(jnp.int32)
    pid_ref[...] = pid
    # per-target survivor count, accumulated over the grid in one
    # revisited (1, P) block: one masked tile reduction per target
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_parts), 1)
    hist = jnp.zeros((1, num_parts), jnp.int32)
    for p in range(num_parts):
        hit = jnp.where(keep & (pid == p), 1, 0)
        n = jnp.sum(jnp.sum(hit, axis=1, keepdims=True), axis=0,
                    keepdims=True)                       # (1, 1)
        hist = hist + jnp.where(lane == p, n, 0)
    hist_ref[...] += hist


def fused_scan_shuffle(cols, pred_fn: Optional[Callable], keys: jax.Array,
                       valid: jax.Array, num_parts: int,
                       block: int = DEFAULT_BLOCK,
                       interpret: Optional[bool] = None):
    """cols: dict of equal-length 1-D predicate input arrays; keys: (R,)
    shuffle key; valid: (R,) 1/0 row-validity lane. R % block == 0,
    block a multiple of 1024. Returns (packed bitmap (R/32,) uint32,
    pids (R,) int32, surviving-rows-per-target hist (1, P) int32).
    ``pred_fn=None`` means every valid row survives."""
    names = list(cols)
    arrs = [as_tiles(cols[n]) for n in names]
    R = keys.shape[0]
    check_block(R, block)
    rows = block // LANES
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    words, pids, hist = pl.pallas_call(
        functools.partial(_kernel, pred_fn, names, num_parts),
        grid=(R // block,),
        in_specs=[tile] * (len(arrs) + 2),
        out_specs=[pl.BlockSpec((rows, WORDS_PER_ROW), lambda i: (i, 0)),
                   tile,
                   pl.BlockSpec((1, num_parts), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R // LANES, WORDS_PER_ROW),
                                        jnp.int32),
                   jax.ShapeDtypeStruct((R // LANES, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1, num_parts), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(*arrs, as_tiles(keys), as_tiles(valid))
    return words_out(words), pids.reshape(R), hist
