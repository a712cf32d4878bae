"""Fused predicate -> bitmap-apply -> grouped partial agg (Pallas TPU).

The storage-side hot path of a pushed ``filter + grouped-agg`` plan as ONE
kernel: per row tile, the compiled predicate tree evaluates branch-free
over VREG-resident column tiles (as in ``predicate_bitmap``), the boolean
row mask gates the values and the one-hot group matrix (as in
``bitmap_apply``'s late materialization — no compacted intermediate is ever
built), and masked sums/counts accumulate on the MXU into revisited output
blocks (as in ``grouped_agg``). Fusion removes the two HBM round-trips the
three-kernel pipeline pays between predicate, apply, and aggregate —
exactly the ISSUE's "no materialized intermediates" requirement, and the
Pallas mirror of the numpy batch executor (``core.executor``).

Masking is arithmetic, not control flow: a failing row multiplies to 0.0 in
both the value vector and the count contraction, so SUM semantics are exact
(0 contribution == filtered out). Padding rows carry a poison group id
(== num_groups) whose one-hot column is sliced off by the wrapper.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.predicate_bitmap import resolve_interpret

DEFAULT_BLOCK = 8192


def _kernel(pred_fn: Callable, names: Sequence[str], num_groups: int, *refs):
    *col_refs, ids_ref, val_ref, sum_ref, cnt_ref = refs
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cols = {n: r[...] for n, r in zip(names, col_refs)}
    keep = (pred_fn(cols) if pred_fn is not None
            else jnp.ones(ids_ref.shape, bool)).astype(jnp.float32)  # (B,)
    ids = ids_ref[...]                                               # (B,)
    vals = val_ref[...].astype(jnp.float32) * keep                   # masked
    onehot = (ids[:, None] == jnp.arange(num_groups)[None, :]
              ).astype(jnp.float32)                                  # (B, G)
    # MXU contractions: (1, B) @ (B, G) — masked sum and masked count
    sums = jnp.dot(vals[None, :], onehot,
                   preferred_element_type=jnp.float32)[0]            # (G,)
    cnts = jnp.dot(keep[None, :], onehot,
                   preferred_element_type=jnp.float32)[0]
    sum_ref[...] += sums
    cnt_ref[...] += cnts.astype(jnp.int32)


def fused_scan_agg(cols, pred_fn: Callable, ids: jax.Array, values: jax.Array,
                   num_groups: int, block: int = DEFAULT_BLOCK,
                   interpret: Optional[bool] = None):
    """cols: dict of equal-length 1-D predicate input arrays; ids: (R,)
    int32 in [0, num_groups); values: (R,). R % block == 0.
    Returns (sums (G,) f32, counts (G,) int32) over rows passing pred_fn.
    ``pred_fn=None`` means all rows pass (plain grouped agg)."""
    names = list(cols)
    arrs = [cols[n] for n in names]
    R = ids.shape[0]
    assert R % block == 0, (R, block)
    grid = (R // block,)
    in_specs = ([pl.BlockSpec((block,), lambda i: (i,)) for _ in arrs]
                + [pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec((block,), lambda i: (i,))])
    return pl.pallas_call(
        functools.partial(_kernel, pred_fn, names, num_groups),
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((num_groups,), lambda i: (0,)),
                   pl.BlockSpec((num_groups,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((num_groups,), jnp.float32),
                   jax.ShapeDtypeStruct((num_groups,), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(*arrs, ids, values)
