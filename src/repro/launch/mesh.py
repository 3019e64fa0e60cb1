"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests, which must
see one CPU device while the dry-run sees 512 placeholders.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run via "
            f"launch/dryrun.py which sets xla_force_host_platform_device_count")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(devices=None):
    """``devices`` (default: whatever exists locally, 1 CPU in tests) as a
    (data, model) mesh."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
