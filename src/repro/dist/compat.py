"""Platform pinning for host-mesh dry-runs.

A process that forces host-platform devices (``XLA_FLAGS`` with
``--xla_force_host_platform_device_count``) is by definition a CPU-mesh
dry-run; :func:`install` pins ``JAX_PLATFORMS=cpu`` for it when the caller
left the platform unset.
"""

from __future__ import annotations

import os

import jax


def install() -> None:
    # Without the pin, a host with libtpu installed but no TPU attached
    # stalls for minutes probing the cloud metadata server before falling
    # back to the CPU.
    if ("xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
            and not os.environ.get("JAX_PLATFORMS")):
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
