"""Public jit'd wrappers for the Pallas kernels.

Handle the unglamorous edges: pad to block multiples (padding rows carry a
poison group id / always-false predicate so results are exact), dtype
guards, and un-padding. ``interpret=None`` (the default) compiles each
kernel with Mosaic on a TPU backend and runs the Pallas interpreter only
on the CPU backend the tests use; ``tests/test_tpu_compile.py`` compiles
every wrapper for a described v5e chip.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitmap_apply as _ba
from repro.kernels import fused_scan_agg as _fsa
from repro.kernels import fused_scan_shuffle as _fss
from repro.kernels import grouped_agg as _ga
from repro.kernels import hash_partition as _hp
from repro.kernels import predicate_bitmap as _pb
from repro.kernels.predicate_bitmap import compile_predicate  # noqa: F401 re-export

DEFAULT_BLOCK = 8192


def _pad_to(x: jax.Array, mult: int, fill=0):
    R = x.shape[0]
    pad = (-R) % mult
    if pad == 0:
        return x, R
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)]), R


def predicate_bitmap(cols: Dict[str, jax.Array], pred_fn: Callable,
                     block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """Packed (ceil(R/32),) uint32 bitmap of pred_fn over the columns.
    Padding rows evaluate through pred_fn but are masked off the result."""
    R = next(iter(cols.values())).shape[0]
    padded = {}
    for k, v in cols.items():
        assert v.shape == (R,), (k, v.shape)
        padded[k], _ = _pad_to(v.astype(jnp.float32) if v.dtype == jnp.float64
                               else v, block)
    words = _pb.predicate_bitmap(padded, pred_fn, block, interpret)
    # mask bits beyond R (padding rows may satisfy the predicate)
    n_words = -(-R // 32)
    words = words[:max(n_words, 1)] if R else words[:0]
    tail_bits = R - 32 * (n_words - 1)
    if R and tail_bits < 32:
        mask = jnp.uint32((1 << tail_bits) - 1)
        words = words.at[-1].set(words[-1] & mask)
    return words


def bitmap_apply(words: jax.Array, col: jax.Array,
                 block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """(masked col (R,), total selected count). Accepts any R."""
    col_p, R = _pad_to(col, block)
    words_p, _ = _pad_to(words, col_p.shape[0] // 32)
    masked, count = _ba.bitmap_apply(words_p, col_p, block, interpret)
    return masked[:R], count[0, 0]


def grouped_agg(ids: jax.Array, values: jax.Array, num_groups: int,
                block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """(sums (G,) f32, counts (G,) int32); padding rows get id == G and an
    extra scratch group that is dropped."""
    ids_p, R = _pad_to(ids.astype(jnp.int32), block, fill=num_groups)
    vals_p, _ = _pad_to(values.astype(jnp.float32), block)
    sums, counts = _ga.grouped_agg(ids_p, vals_p, num_groups + 1, block,
                                   interpret)
    return sums[:num_groups], counts[:num_groups]


def fused_scan_agg(cols: Dict[str, jax.Array], pred_fn: Optional[Callable],
                   ids: jax.Array, values: jax.Array, num_groups: int,
                   block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """Fused predicate -> mask -> grouped agg: (sums (G,) f32, counts (G,)
    int32) over rows passing pred_fn. Padding rows carry the poison group
    id == G (their one-hot column is an extra scratch group, dropped), so
    they cannot contribute even when the padded predicate holds."""
    ids_p, R = _pad_to(ids.astype(jnp.int32), block, fill=num_groups)
    vals_p, _ = _pad_to(values.astype(jnp.float32), block)
    padded = {}
    for k, v in cols.items():
        assert v.shape == (R,), (k, v.shape)
        padded[k], _ = _pad_to(v.astype(jnp.float32) if v.dtype == jnp.float64
                               else v, block)
    sums, counts = _fsa.fused_scan_agg(padded, pred_fn, ids_p, vals_p,
                                       num_groups + 1, block, interpret)
    return sums[:num_groups], counts[:num_groups]


def fused_scan_shuffle(cols: Dict[str, jax.Array], pred_fn: Optional[Callable],
                       keys: jax.Array, num_parts: int,
                       block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """Fused predicate -> packed bitmap -> hash partition: (packed bitmap
    (ceil(R/32),) uint32, pids (R,) int32, surviving-rows-per-target hist
    (P,) int32) in one pass. A validity lane zeroes padding rows inside the
    kernel, so no tail-word masking or histogram subtraction is needed —
    pad rows can neither set a bit nor count toward a target."""
    R = keys.shape[0]
    keys_p, _ = _pad_to(keys, block)
    valid_p, _ = _pad_to(jnp.ones(R, jnp.int32), block)
    padded = {}
    for k, v in cols.items():
        assert v.shape == (R,), (k, v.shape)
        padded[k], _ = _pad_to(v.astype(jnp.float32) if v.dtype == jnp.float64
                               else v, block)
    words, pids, hist = _fss.fused_scan_shuffle(padded, pred_fn, keys_p,
                                                valid_p, num_parts, block,
                                                interpret)
    n_words = -(-R // 32)
    return (words[:n_words] if R else words[:0], pids[:R], hist[0])


def hash_partition(keys: jax.Array, num_parts: int,
                   block: int = DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """(pids (R,) int32, hist (P,) int32). Padding keys hash somewhere but
    are excluded from the histogram by subtraction."""
    keys_p, R = _pad_to(keys, block)
    pids, hist = _hp.hash_partition(keys_p, num_parts, block, interpret)
    hist = hist[0]
    pad = keys_p.shape[0] - R
    if pad:
        pad_pids = pids[R:]
        pad_hist = (pad_pids[:, None] == jnp.arange(num_parts)[None, :]
                    ).sum(axis=0, dtype=jnp.int32)
        hist = hist - pad_hist
    return pids[:R], hist


# ------------------------------------------------------- numpy conveniences
def predicate_bitmap_np(cols: Dict[str, np.ndarray], expr) -> np.ndarray:
    """Expr tree + numpy columns -> packed bitmap as numpy (storage interop)."""
    fn = compile_predicate(expr)
    jcols = {k: jnp.asarray(v.astype(np.float32) if v.dtype == np.float64
                            else v) for k, v in cols.items()}
    return np.asarray(predicate_bitmap(jcols, fn))
