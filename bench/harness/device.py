"""The chip a run is on: its presence, its peaks, its memory.

The peaks are published per chip kind and keyed by ``device_kind``; a kind
missing here is an error, never a default.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 1024 ** 3,
        "source": 'Google Cloud documentation, "TPU v5e" (system architecture)',
    },
}


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}") from None


def require(chips: int):
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _fullest(devs, key: str) -> int | None:
    vals = []
    for d in devs:
        stats = d.memory_stats() or {}
        if key in stats:
            vals.append(int(stats[key]))
    return max(vals) if vals else None


def memory_peak_bytes(devs) -> int | None:
    """The process's peak bytes in use on the fullest chip, where the
    backend reports it."""
    return _fullest(devs, "peak_bytes_in_use")


def memory_in_use_bytes(devs) -> int | None:
    """Bytes in use now on the fullest chip, where the backend reports it."""
    return _fullest(devs, "bytes_in_use")
