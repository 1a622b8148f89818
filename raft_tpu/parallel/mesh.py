"""Mesh construction and sharding helpers."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.core.error import expects


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axis_names: Tuple[str, ...] = ("data",),
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    expects(int(np.prod(shape)) == len(devs),
            "make_mesh: shape %s != %d devices", shape, len(devs))
    return Mesh(np.asarray(devs).reshape(shape), axis_names=axis_names)


def shard_rows(x, mesh: Mesh, axis: str = "data"):
    """Place an array with rows sharded along a mesh axis; pads rows to a
    multiple of the axis size (pad rows are all-zero — callers that care
    use valid-row masks)."""
    import jax.numpy as jnp
    n = mesh.shape[axis]
    pad = (-x.shape[0]) % n
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    sharding = NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
    return jax.device_put(x, sharding), pad


def replicate(x, mesh: Mesh):
    return jax.device_put(x, NamedSharding(mesh, P()))

