"""Production meshes.

Single pod = 16x16 = 256 chips (v5e pod), axes ("data", "model").
Multi-pod  = 2x16x16 = 512 chips, axes ("pod", "data", "model"): "pod" is
pure DP (FP8-compressed gradient hop), "data" is FSDP, "model" is TP/EP.

Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_host_mesh", "ChipPeaks", "PEAKS",
           "peaks", "HW"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh over host (CPU) devices for tests."""
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks for the roofline."""

    peak_flops_bf16: float  # FLOP/s
    peak_ops_int8: float  # OP/s
    hbm_bw: float  # B/s
    hbm_bytes: int
    ici_bw_per_link: float  # B/s per link (wire bytes already per device)
    source: str


#: Keyed by ``jax.Device.device_kind``. A kind missing here is an error,
#: never a default: a roofline against another chip's peaks is wrong.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        peak_flops_bf16=197e12,
        peak_ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 1024**3,
        ici_bw_per_link=50e9,  # 1,600 Gbit/s per chip over 4 links
        source='Google Cloud documentation, "TPU v5e" (system architecture)',
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of one chip kind, e.g. ``jax.devices()[0].device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to launch.mesh.PEAKS with their source"
        ) from None


#: The chip the production meshes above describe (a v5e pod).
HW = peaks("TPU v5 lite")
