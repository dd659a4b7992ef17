"""Closed-form operations and bytes of each kernel, from unpadded shapes.

Each function returns (flops, bytes) of ONE call: the work the algorithm
needs, not what an implementation re-fetches or pads. Operands are counted
at the dtype the call carries them in (float32 activations and results,
1-byte FloatSD8 codes, FloatSD4's half byte plus one exponent byte per 32
rows and column). ``floor_seconds`` is the least time a chip with the given
peaks could take, and which of the two bounds sets it.
"""
from __future__ import annotations


def floor_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
