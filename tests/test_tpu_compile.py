"""Compile the device path for a described TPU v5e chip, with no chip.

The TPU compiler is installed beside JAX, and it compiles for a chip that
is described rather than attached: what Mosaic or XLA:TPU would refuse on
the chip is refused here, at no chip time. Nothing runs, so these tests
say nothing about results or speed (``chip_smoke.py`` runs them on the
chip). Covered:

- the six Pallas kernels of ``kernels/ops.py``, compiled (``interpret=
  False``) at 2^20 rows, each with its ``tpu_custom_call`` in the program;
- the jitted residual stages of Q1, Q5 and Q18 at TPC-H SF1 shapes (the
  generator's ``sf=100``), specialized by an observe run on the CPU.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compiler import compile_query_detailed, tensorize
from repro.core import engine
from repro.kernels import ops
from repro.queryproc import tpch
from repro.queryproc.expressions import Col

R = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described device's program is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, dtype, n=R):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)


_PRED = ops.compile_predicate((Col("q") <= 24)
                              & ((Col("d") > 5) | Col("q").eq(7)))

# name -> (wrapper with interpret=False, argument dtypes and lengths)
_KERNELS = {
    "predicate_bitmap": (
        lambda q, d: ops.predicate_bitmap({"q": q, "d": d}, _PRED,
                                          interpret=False),
        ((jnp.float32, R), (jnp.float32, R))),
    "bitmap_apply": (
        lambda w, c: ops.bitmap_apply(w, c, interpret=False),
        ((jnp.uint32, R // 32), (jnp.float32, R))),
    "grouped_agg": (
        lambda i, v: ops.grouped_agg(i, v, 37, interpret=False),
        ((jnp.int32, R), (jnp.float32, R))),
    "hash_partition": (
        lambda k: ops.hash_partition(k, 16, interpret=False),
        ((jnp.int32, R),)),
    "fused_scan_agg": (
        lambda q, d, i, v: ops.fused_scan_agg({"q": q, "d": d}, _PRED, i, v,
                                              37, interpret=False),
        ((jnp.float32, R), (jnp.float32, R), (jnp.int32, R),
         (jnp.float32, R))),
    "fused_scan_shuffle": (
        lambda q, d, k: ops.fused_scan_shuffle({"q": q, "d": d}, _PRED, k,
                                               16, interpret=False),
        ((jnp.float32, R), (jnp.float32, R), (jnp.int32, R))),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _KERNELS[name]
    compiled = jax.jit(fn).lower(
        *[_spec(one_chip, dt, n) for dt, n in args]).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


class _Captured(Exception):
    pass


def _stage_inputs(residual, merged):
    """The first jitted stage's inputs, exactly as ``tensorize.execute``
    pads them: observe once on the CPU, then stop the first jitted run
    at its stage call (these residuals have one stage)."""
    tensorize.execute(residual, merged)                    # observe
    art = tensorize._artifact(residual)
    assert len(art.stages) == 1 and art.jit_fns[0] is not None
    seen = {}

    def capture(inputs):
        seen["inputs"] = inputs
        raise _Captured

    fn = art.jit_fns[0]
    art.jit_fns[0] = capture
    try:
        with pytest.raises(_Captured):
            tensorize.execute(residual, merged)
    finally:
        art.jit_fns[0] = fn
    return fn, seen["inputs"]


@pytest.fixture(scope="module")
def sf1_catalog():
    return tpch.build_catalog(sf=100, num_nodes=2)


@pytest.mark.parametrize("qid", ["Q1", "Q5", "Q18"])
def test_residual_stage_compiles_for_v5e(qid, sf1_catalog, one_chip):
    cq = compile_query_detailed(qid)
    merged = engine.execute_requests(
        engine.plan_requests(cq.query, sf1_catalog))
    fn, inputs = _stage_inputs(cq.residual, merged)
    with jax.enable_x64(True):
        specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                           sharding=one_chip), inputs)
        compiled = fn.lower(specs).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 1024 ** 3, (qid, used)
