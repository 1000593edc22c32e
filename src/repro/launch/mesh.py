"""Production mesh definitions (TPU v5e target).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the 512-device host platform is
forced only inside ``launch/dryrun.py``.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi pod:  2×16×16 = 512 chips, axes (pod, data, model) — the ``pod`` axis
carries the data-parallel/client dimension across pods (DCN-ish boundary).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.launch.sharding import CLIENT_AXIS

__all__ = ["make_production_mesh", "make_client_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_client_mesh(
    num_devices: Optional[int] = None, axis: str = CLIENT_AXIS
) -> jax.sharding.Mesh:
    """1-D mesh carrying the federation's client axis (DESIGN.md §8).

    Uses the first ``num_devices`` visible devices (all of them by default) —
    on CPU hosts scale the axis with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devices)} visible "
                "(set --xla_force_host_platform_device_count on CPU)"
            )
        devices = devices[:num_devices]
    return jax.sharding.Mesh(np.asarray(devices), (axis,))


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""

    PEAK_FLOPS_BF16 = 197e12  # per chip
    HBM_BW = 819e9  # bytes/s per chip
    ICI_BW = 50e9  # bytes/s per link
    HBM_BYTES = 16 * 2**30  # per chip
