"""Fused predicate evaluation -> packed selection bitmap (Pallas TPU).

TPU adaptation of the paper's §4.2 selection-bitmap operator: instead of a
row-at-a-time branchy filter (the C++ storage engine's form), the predicate
tree is evaluated branch-free over VREG-resident column tiles, and the
resulting boolean lane values are packed 32 rows/word with a
weighted-sum-over-lanes (``pack_lanes``: disjoint powers of two make SUM ==
OR, and int32 wraparound is exactly uint32's).

The predicate arrives as a *traced closure* over the column tile dict —
the same Expr tree that the numpy storage path evaluates is compiled into
the kernel body by ``compile_predicate`` below, so both sides share one
plan representation (the paper ships serialized plans, not SQL).

Block layout: a column of R rows is viewed as (R/128, 128) lane-dense
tiles and processed BLOCK rows at a time, (BLOCK/128, 128) per grid step
(32 KiB of f32 at the default 8192 — a handful of columns fit comfortably
in the ~16 MiB VMEM budget); BLOCK is a multiple of 1024 so each tile is a
whole number of (8, 128) vregs. Packed words come out as (R/128, 4).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.queryproc import expressions as ex

DEFAULT_BLOCK = 8192
LANES = 128
WORDS_PER_ROW = LANES // 32


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` (every kernel's default): compiled by Mosaic on a TPU
    backend, interpreted on the CPU backend the tests run on."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def pack_lanes(mask: jax.Array) -> jax.Array:
    """(rows, 128) bool tile -> (rows, 4) int32 packed words, row-major:
    lane ``l`` of tile row ``i`` is bit ``l % 32`` of word ``4i + l // 32``
    (== np.packbits little-endian over the flattened rows). Mosaic has no
    unsigned reductions and no (rows*128,) -> (rows*4, 32) shape cast, so
    the disjoint bits of each 32-lane group are summed in int32 (two's
    complement wraps exactly like uint32); callers bitcast to uint32."""
    lane = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
    bits = jnp.where(mask, jnp.left_shift(jnp.int32(1), lane % 32),
                     jnp.int32(0))
    return jnp.concatenate(
        [jnp.sum(jnp.where(lane // 32 == k, bits, 0), axis=1, keepdims=True)
         for k in range(WORDS_PER_ROW)], axis=1)


def unpack_lanes(words: jax.Array) -> jax.Array:
    """Inverse of ``pack_lanes``: (rows, 4) int32 words -> (rows, 128)
    int32 0/1 bits, each lane shifting its own word by its own offset."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (words.shape[0], LANES), 1)
    word = words[:, 0:1]
    for k in range(1, WORDS_PER_ROW):
        word = jnp.where(lane // 32 == k, words[:, k:k + 1], word)
    return jax.lax.shift_right_logical(word, lane % 32) & 1


def as_tiles(x: jax.Array) -> jax.Array:
    """(R,) -> (R/128, 128): the lane-dense 2-D layout the kernels tile."""
    return x.reshape(-1, LANES)


def words_out(words: jax.Array) -> jax.Array:
    """(R/128, 4) int32 kernel words -> (R/32,) uint32 packed bitmap."""
    return jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(-1)


def check_block(R: int, block: int) -> None:
    assert R % block == 0 and block % (8 * LANES) == 0, (R, block)


def _kernel(pred_fn: Callable, names: Sequence[str], *refs):
    *col_refs, out_ref = refs
    cols = {n: r[...] for n, r in zip(names, col_refs)}
    out_ref[...] = pack_lanes(pred_fn(cols))      # (rows, 128) -> (rows, 4)


def predicate_bitmap(cols: Dict[str, jax.Array], pred_fn: Callable,
                     block: int = DEFAULT_BLOCK,
                     interpret: Optional[bool] = None) -> jax.Array:
    """cols: dict of equal-length 1-D arrays (R % block == 0, block a
    multiple of 1024). Returns packed (R/32,) uint32 bitmap."""
    names = list(cols)
    arrs = [as_tiles(cols[n]) for n in names]
    R = arrs[0].size
    check_block(R, block)
    rows = block // LANES
    in_specs = [pl.BlockSpec((rows, LANES), lambda i: (i, 0)) for _ in arrs]
    out_spec = pl.BlockSpec((rows, WORDS_PER_ROW), lambda i: (i, 0))
    return words_out(pl.pallas_call(
        functools.partial(_kernel, pred_fn, names),
        grid=(R // block,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((R // LANES, WORDS_PER_ROW),
                                       jnp.int32),
        interpret=resolve_interpret(interpret),
    )(*arrs))


# ---------------------------------------------------------------- compiler
def compile_predicate(expr: ex.Expr) -> Callable:
    """Expr tree -> branch-free jnp closure over a column-tile dict.
    The same tree the numpy storage path evaluates (one plan, two engines)."""
    if isinstance(expr, ex.Cmp):
        op = {"<=": jnp.less_equal, "<": jnp.less, ">=": jnp.greater_equal,
              ">": jnp.greater, "==": jnp.equal}[expr.op]
        name, v = expr.col.name, expr.value
        if isinstance(v, ex.Col):  # column-column compare (e.g. Q4-style)
            rname = v.name
            return lambda cols: op(cols[name], cols[rname])
        return lambda cols: op(cols[name], v)
    if isinstance(expr, ex.In):
        name, vals = expr.col.name, expr.values
        def fn(cols):
            c = cols[name]
            acc = jnp.zeros(c.shape, bool)
            for v in vals:
                acc = acc | (c == v)
            return acc
        return fn
    if isinstance(expr, ex.And):
        l, r = compile_predicate(expr.left), compile_predicate(expr.right)
        return lambda cols: l(cols) & r(cols)
    if isinstance(expr, ex.Or):
        l, r = compile_predicate(expr.left), compile_predicate(expr.right)
        return lambda cols: l(cols) | r(cols)
    raise TypeError(expr)
