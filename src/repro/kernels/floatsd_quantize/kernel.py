"""Pallas TPU kernel: FloatSD8 quantization (master weights -> uint8 codes).

Runs after every optimizer step (paper §III-B: 'the master copy weights are
then quantized to FloatSD8 for the next iteration'). Pure VPU work:
nearest-grid-value rounding against the 64 grid midpoints, done as a select
chain over compile-time constants (``mosaic.select_lut``: no searchsorted
and no table gather on TPU) that directly yields the precomputed code of
the winning grid slot, for each sign.

Tiles are [bm, bn] VMEM blocks of the (flattened-2D) weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core import floatsd
from .. import mosaic

__all__ = ["quantize_kernel", "quantize_pallas"]

_GRID = floatsd._GRID_POS.astype(np.float32)  # 65 non-negative values
_MID = ((_GRID[1:] + _GRID[:-1]) / 2).astype(np.float32)  # 64 midpoints
_E = floatsd._GRID_E.astype(np.int32)
_MIDX = floatsd._GRID_MIDX.astype(np.int32)
# per grid slot: the code for +value in the low byte, for -value in the next
# (``core.floatsd.encode``: code = e << 5 | mantissa index, sign folded into
# the index as 30 - idx)
_CODES = ((_E << 5) | _MIDX) | (((_E << 5) | (30 - _MIDX)) << 8)


def quantize_kernel(x_ref, inv_scale_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    # |x| / 2^bias as a product with the exact power of two 2^-bias
    n = jnp.abs(x) * inv_scale_ref[0, 0]
    n = jnp.minimum(n, _GRID[-1])
    # nearest grid slot = #(midpoints below n), resolved to its code pair
    pair = mosaic.select_lut(n, _MID, _CODES)
    code = jnp.where(x < 0, pair >> 8, pair & 0xFF)
    out_ref[...] = code.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def quantize_pallas(x, bias, *, bm: int = 256, bn: int = 256, interpret: bool = False):
    m, n = x.shape
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        quantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint8),
        interpret=interpret,
    )(mosaic.widen(x),
      jnp.reshape(floatsd.exp2i(-floatsd._clamp_bias(bias)), (1, 1)))
