"""Explicit shard_map collectives: the paper's shuffle as an in-mesh
primitive + distributed-optimization tricks.

- ``expert_all_to_all_dispatch``: the in-mesh analogue of distributed-
  data-shuffle pushdown (§4.2). The baseline MoE keeps the (E, C, d)
  buffer sharded over the expert axis and lets GSPMD re-shard; this
  variant hash-routes tokens to expert shards with ONE all_to_all from the
  producer — exactly Fig 5(b)'s "partition at the source, send straight to
  the target" applied to the TP mesh. Used by the §Perf hillclimb.

- ``compressed_psum``: int8 error-feedback gradient all-reduce. Gradients
  quantize to int8 with a per-tensor scale; the quantization error feeds
  back into the next step's gradient (error-feedback keeps SGD unbiased
  in the long run). Cross-pod (DCN) traffic drops 4x for f32 grads.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


# ---------------------------------------------------------- EP dispatch
def expert_all_to_all_dispatch(x_by_expert: jax.Array, mesh: Mesh,
                               axis: str = "model") -> jax.Array:
    """(E, C, d) token buffer, E sharded over ``axis`` at the *producer*
    (each shard scattered its local tokens into all E expert slots) ->
    buffer where shard i holds ONLY its experts' rows from every producer,
    i.e. the post-shuffle layout. One all_to_all; no all-gather.

    Mirrors ops.shuffle_partition: partition at source, route to target."""
    E = x_by_expert.shape[0]
    n = mesh.shape[axis]
    assert E % n == 0, (E, n)

    def body(local):  # local: (E, C_local, d) — producer's slice over C
        # split expert dim into n groups and exchange: group j -> shard j
        return jax.lax.all_to_all(local, axis, split_axis=0, concat_axis=1,
                                  tiled=True)

    return shard_map(body, mesh=mesh,
                     in_specs=P(None, axis, None),
                     out_specs=P(axis, None, None))(x_by_expert)


def expert_all_to_all_combine(y_by_expert: jax.Array, mesh: Mesh,
                              axis: str = "model") -> jax.Array:
    """Inverse of the dispatch (expert results back to producers)."""
    def body(local):  # (E_local, C, d)
        return jax.lax.all_to_all(local, axis, split_axis=1, concat_axis=0,
                                  tiled=True)

    return shard_map(body, mesh=mesh,
                     in_specs=P(axis, None, None),
                     out_specs=P(None, axis, None))(y_by_expert)


# ------------------------------------------------- compressed all-reduce
def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(grad: jax.Array, err: jax.Array, mesh: Mesh,
                    axis: str = "pod") -> Tuple[jax.Array, jax.Array]:
    """Error-feedback int8 all-reduce over ``axis``.

    grad: this shard's gradient contribution (f32), err: carried
    quantization error from the previous step (same shape). Returns
    (reduced gradient estimate, new error). Traffic: 1 byte/elem over the
    cross-pod axis instead of 4 (plus one scalar)."""
    def body(g, e):
        v = g + e
        # agree on a COMMON scale first (one scalar all-reduce) so the
        # integer psum dequantizes exactly; per-element error is then only
        # each shard's own rounding, which the feedback carries forward
        scale = jax.lax.pmax(jnp.maximum(jnp.max(jnp.abs(v)), 1e-30), axis) \
            / 127.0
        q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
        new_err = v - q.astype(jnp.float32) * scale
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        approx = total.astype(jnp.float32) * scale
        return approx, new_err

    n = mesh.shape[axis]
    if n == 1:
        # degenerate mesh: nothing to reduce, but the carried error MUST
        # still fold into the estimate — dropping it here would silently
        # bias error-feedback (the shard_map path returns g+e exactly,
        # since a single shard's common-scale quantization round-trips
        # through its own rounding and new_err absorbs the difference:
        # approx + new_err == g + e). Conservation pinned by
        # tests/test_distributed.py::test_compressed_psum_n1_error_feedback.
        return grad + err, jnp.zeros_like(err)
    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis), P(axis)),
                     out_specs=(P(axis), P(axis)))(grad, err)
