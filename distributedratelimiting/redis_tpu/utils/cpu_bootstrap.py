"""Pin jax to the CPU platform, optionally with N virtual devices.

The test suite and ``__graft_entry__``'s multi-chip dry run both run on
the CPU by design. A child process that needs no chip is started with
``JAX_PLATFORMS=cpu`` in its environment instead (one process per chip:
a child that reaches for the chip its parent holds fails or hangs).
"""

from __future__ import annotations

__all__ = [
    "force_cpu_platform",
    "set_virtual_device_count",
    "XLA_DEVICE_COUNT_FLAG",
]

XLA_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def set_virtual_device_count(env: dict, n_devices: int) -> None:
    """Point ``env`` at an ``n_devices``-device virtual CPU platform.

    Replaces (never appends next to) any inherited device-count flag —
    two occurrences would leave the effective count at XLA's mercy.
    ``XLA_FLAGS`` is read at backend init, so mutating ``os.environ``
    with this before the first array op also works in-process.
    """
    import re

    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        re.sub(rf"{XLA_DEVICE_COUNT_FLAG}=\S+", "", env.get("XLA_FLAGS", ""))
        + f" {XLA_DEVICE_COUNT_FLAG}={n_devices}"
    )


def force_cpu_platform() -> None:
    """Pin jax to the CPU platform. Must run before jax's first backend
    init (jax reads ``JAX_PLATFORMS`` when it is imported, so a process
    that imported jax first is pinned at the config level here)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
