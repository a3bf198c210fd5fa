"""Device mesh helpers.

The TPU analog of the reference's silo ring (ConsistentRingProvider.cs:17):
a 1-D ``jax.sharding.Mesh`` over the axis ``"silo"``. Each mesh coordinate
is one logical silo shard of the vectorized actor tables; cross-shard
messages ride ICI collectives along this axis
(orleans_tpu.parallel.transport).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["SILO_AXIS", "make_mesh", "shard_spec", "replicated_spec"]

SILO_AXIS = "silo"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the silo axis. ``n_devices=None`` uses all local
    devices (the host's TPU chips; 8 virtual CPU devices in tests)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SILO_AXIS,))


def shard_spec(mesh: Mesh, *trailing: None) -> NamedSharding:
    """Sharding for arrays with a leading per-silo shard axis:
    [n_shards, ...] split over the silo axis."""
    return NamedSharding(mesh, P(SILO_AXIS, *trailing))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
