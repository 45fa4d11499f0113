"""JAX platform selection helpers."""

from __future__ import annotations

import os


def force_cpu(host_devices: int = 0) -> None:
    """Pin this process to the CPU backend, with ``host_devices`` virtual
    devices when given — the tests shard over eight of them. Call it before
    the first jax computation."""
    if host_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={host_devices}".strip())
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind if jax.devices() else "none"


def on_tpu() -> bool:
    """True when the default backend is ``tpu`` — the one test kernel
    dispatch uses to pick pallas over the XLA oracle."""
    import jax
    return jax.default_backend() == "tpu"
