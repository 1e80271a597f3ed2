"""Production mesh construction (TPU v5e-class target).

A function (NOT a module-level constant) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod: 2 pods = 512 chips, axes ("pod", "data", "model"); the batch
shards over (pod, data) and params replicate across pods (DP) while the
`model` axis carries tensor/expert parallelism within a pod — matching the
paper's local-device/edge-tier split, where the `pod` axis separates tiers.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh

AxisVal = Union[None, str, Tuple[str, ...]]

#: the serving mesh axis streams shard over (see ``repro.fleet``)
FLEET_AXIS = "shard"


def make_production_mesh(*, multi_pod: bool = False):
    """The full-scale training/serving mesh — 256 chips (single pod) or
    2x256 (multi-pod).

    Degrades gracefully when fewer devices are visible (single-host CPU
    CI): the available devices fold into the ``data`` axis with the other
    axes at size 1, so the axis *names* — and therefore every logical
    sharding rule — stay valid; size-1 axes simply replicate."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n_avail = len(jax.devices())
    if n_avail < int(np.prod(shape)):
        shape = (1, n_avail, 1) if multi_pod else (n_avail, 1)
    return jax.make_mesh(shape, axes)


def make_fleet_mesh(n_shards: Optional[int] = None, *, axis: str = FLEET_AXIS) -> Mesh:
    """A 1-D city-scale *serving* mesh: the first ``n_shards`` of
    ``jax.devices()`` along one ``"shard"`` axis, streams sharded over it
    (see ``repro.fleet.plane``).

    The devices are whatever the backend exposes: the chips of a TPU host
    (four on a v5e 2x2 host), or CPU devices forced with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    ``n_shards=None`` takes every visible device; asking for more shards
    than devices clamps to the available count, so a 1-device run gets a
    1-shard mesh and the sharded data plane degrades to the single-device
    path."""
    devices = jax.devices()
    n = len(devices) if n_shards is None else int(n_shards)
    if n < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n = min(n, len(devices))
    return Mesh(np.array(devices[:n]), (axis,))


def logical_axes(*, multi_pod: bool = False) -> Dict[str, AxisVal]:
    """Logical -> mesh axis mapping used by meshctx.constrain."""
    return {
        "batch": ("pod", "data") if multi_pod else "data",
        "model": "model",
        "expert": "model",  # expert-parallel over the model axis
        "data_only": "data",
    }
