"""Production meshes.

Target fleet: TPU v5e pods of 256 chips arranged (16, 16); the multi-pod
configuration stacks 2 pods = 512 chips on a leading "pod" axis (data
parallelism over DCI, with gradient compression available for the cross-pod
reduction). Defined as FUNCTIONS so importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: sharding is propagated by the compiler from the logical-axis
    # rules (``distributed.sharding``); jax.make_mesh defaults to Explicit
    # axes, under which a contraction over a sharded dim is a type error.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(num_devices: Optional[int] = None, model_axis: int = None):
    """Small-scale mesh for tests/examples on host platforms."""
    n = num_devices or len(jax.devices())
    m = model_axis or (2 if n % 2 == 0 and n > 1 else 1)
    return _auto_mesh((n // m, m), ("data", "model"))


def make_fleet_mesh(num_devices: Optional[int] = None, *, pods: int = 1):
    """Scene-axis mesh for fleet rollouts / closed-loop eval.

    The rollout tick is data-parallel over scene slots (no tensor
    parallelism — the sim models are small; the scale axis is scenes), so
    the fleet mesh carries only the DP axes ``("pod", "data")`` that
    :class:`repro.runtime.RolloutEngine` shard_maps its lanes over.
    ``num_devices`` defaults to every visible device and may name a
    PREFIX subset (the fleet-bench scaling sweep builds meshes over 1, 2,
    4, ... devices inside one forced-device-count process); ``pods``
    splits a leading cross-pod axis off for multi-pod runs.
    """
    import numpy as np

    devs = jax.devices()[:num_devices] if num_devices else jax.devices()
    n = len(devs)
    if n % max(pods, 1) != 0:
        raise ValueError(f"{n} devices do not split into {pods} pods")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs).reshape(pods, n // pods), ("pod", "data"))


# Hardware constants for the roofline model (TPU v5e).
HW = {
    "name": "tpu_v5e",
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link (~per chip per direction)
    "hbm_bytes": 16 * 1024**3,     # 16 GiB per chip
    "dci_bw": 6.25e9,              # cross-pod per chip (assumed 50 Gbit/s)
}
