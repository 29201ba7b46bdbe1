"""Production mesh construction.

IMPORTANT: functions, not module-level constants — importing this module
never touches jax device state. The dry-run entrypoint sets
xla_force_host_platform_device_count=512 before any jax import.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model); multi-pod: 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def small_mesh(data: int = 2, model: int = 2):
    """For subprocess tests with xla_force_host_platform_device_count."""
    return make_mesh((data, model), ("data", "model"))


def nomora_ordered_devices(
    host_of_device: Sequence[int],
    latency_to_root: Sequence[float],
    devices: Optional[Sequence] = None,
):
    """Beyond-paper integration: order mesh devices by the NoMora placement.

    Hosts closest (lowest RTT) to the job's root host take the model-
    parallel (innermost, latency-critical) positions; far hosts land on the
    data axis where only gradient reductions cross them. Returns devices
    sorted by (latency_to_root[host_of_device[d]], device_id).
    """
    devices = list(devices or jax.devices())
    lat = np.asarray(latency_to_root, dtype=np.float64)
    order = sorted(
        range(len(devices)), key=lambda d: (lat[host_of_device[d]], d)
    )
    return [devices[i] for i in order]
