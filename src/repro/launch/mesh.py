"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips/pod ("data", "model"); 2 pods adds a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(launch/dryrun.py sets this)")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(n_devices: int | None = None,
                   axes: tuple[str, str] = ("data", "model"),
                   shape: tuple[int, int] | None = None) -> Mesh:
    """Small mesh over whatever devices exist (for tests on 1..8 CPUs)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if shape is None:
        shape = (n, 1)
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
