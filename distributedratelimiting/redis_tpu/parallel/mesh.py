"""Mesh construction helpers.

One logical axis, ``"shard"``, carries the key dimension. On real hardware
the axis should follow the physical ICI topology (jax's default device
order does); on CPU it maps over the virtual devices created by
``--xla_force_host_platform_device_count`` (the test/dry-run path replacing
the reference's Orleans-localhost multi-silo trick,
``TestApp/Program.cs:37-104``).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["SHARD_AXIS", "create_mesh", "shard_spec", "replicated_spec",
           "init_sharded"]

SHARD_AXIS = "shard"


def create_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D device mesh over the first ``n_devices`` devices (all by
    default)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} present"
            )
        devices = devices[:n_devices]
    import numpy as np

    return Mesh(np.array(devices), (SHARD_AXIS,))


def shard_spec(mesh: Mesh) -> NamedSharding:
    """First-axis sharding over the key dimension."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def init_sharded(init, n: int, sharding):
    """Build ``init(n)``'s arrays directly in ``sharding``: each device
    materialises only its own shard. (``jax.device_put`` of a freshly
    built array would first hold all of it on one device.)"""
    return jax.jit(init, static_argnums=0, out_shardings=sharding)(n)
