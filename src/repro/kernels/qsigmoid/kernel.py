"""Pallas TPU kernel: two-region FloatSD8 sigmoid (paper Eqs. 7-8).

The standalone version of the sigmoid stage inside the fused LSTM-cell
kernel: sigma(-|x|) lands in (0, 0.5], is rounded to the nearest entry of
the 42-value non-positive-branch LUT by a broadcast compare-count against
the 42 midpoints (the VPU analogue of the paper's reduced-depth LUT), and
the positive region is mirrored as 1 - Q(sigma(-x)). The compare-count runs
as a select chain over compile-time constants (``mosaic.select_lut``). Registered in
``kernels.dispatch`` so gate activations outside the fused cell (e.g. the
RWKV receptance gate) can run the same datapath.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core import qsigmoid as _qs
from .. import mosaic

__all__ = ["qsigmoid_kernel", "qsigmoid_pallas"]

_SIG_GRID = _qs.sigmoid_lut_values().astype(np.float32)  # 43 incl. 0
_SIG_MID = ((_SIG_GRID[1:] + _SIG_GRID[:-1]) / 2).astype(np.float32)


def qsigmoid_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    s_neg = jax.nn.sigmoid(-jnp.abs(x))  # in (0, 0.5]
    q = mosaic.select_lut(s_neg, _SIG_MID, _SIG_GRID)
    out_ref[...] = jnp.where(x > 0, 1.0 - q, q).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def qsigmoid_pallas(x, *, bm: int = 256, bn: int = 256, interpret: bool = False):
    """x: [M, N] -> quantized sigmoid, same shape/dtype."""
    m, n = x.shape
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        qsigmoid_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), mosaic.io_dtype(x.dtype)),
        interpret=interpret,
    )(mosaic.widen(x)).astype(x.dtype)
