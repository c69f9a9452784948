"""Pre-jax process bootstrap helpers.

This module must import NOTHING that initializes the jax backend: its whole
point is to mutate ``XLA_FLAGS`` before the first ``import jax`` runs.
"""
from __future__ import annotations

import os


def ensure_host_devices_for_mesh(argv, n: int = 8, flag: str = "--mesh") -> None:
    """On the CPU backend only (``JAX_PLATFORMS=cpu``): if ``flag`` (or
    ``flag=value``) appears in ``argv``, force ``n`` emulated host-platform
    devices unless a device count is already pinned. On an accelerator the
    real devices form the mesh and no emulation flag is set. Call BEFORE
    importing jax — the backend reads XLA_FLAGS exactly once."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    if not any(a == flag or a.startswith(flag + "=") for a in argv):
        return
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        return
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", ""))
