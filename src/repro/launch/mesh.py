"""Production mesh definitions (TPU v5e pods).

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS for 512 host devices *before* any jax import; everything else
(smoke tests, benchmarks) sees the single real CPU device.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


# ------------------------------------------------------- hardware constants
@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """TPU v5e (the roofline constants from the task spec)."""
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12      # FLOP/s per chip
    hbm_bw: float = 819e9                # bytes/s per chip
    ici_bw: float = 50e9                 # bytes/s per link (~per axis direction)
    dcn_bw: float = 25e9                 # bytes/s per host across pods
    hbm_bytes: int = 16 * 1024 ** 3      # 16 GiB HBM per chip


V5E = HardwareSpec()


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod pass."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    n = jax.device_count()
    if shape is None:
        shape = (n, 1)
    devs = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return jax.sharding.Mesh(devs, axes)


def mesh_chips(mesh: jax.sharding.Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def mesh_tag(mesh: jax.sharding.Mesh) -> str:
    return "x".join(str(s) for s in mesh.shape.values())
