"""Production meshes and per-chip peaks.

Meshes are v5e pod-slice shapes (DESIGN.md §5): single pod =
(data=16, model=16) = 256 chips; multi-pod adds a leading pod axis:
(pod=2, data=16, model=16) = 512 chips.

A FUNCTION, not a module constant: importing this module must never
touch jax device state.
"""
from __future__ import annotations

import jax

# Published per-chip peaks, keyed by `jax.Device.device_kind`. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
# HBM, 1,600 Gbit/s inter-chip interconnect = 4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bytes_s_per_link": 50e9},
}


#: the chip the production meshes above are shaped for
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind; a kind not in the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    return _make_mesh((data, model), ("data", "model"))
