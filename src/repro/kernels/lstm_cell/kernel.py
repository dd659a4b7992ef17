"""Pallas TPU kernel: fused LSTM neuron element-wise stage (paper Fig. 9).

After the PE array produces the four gate pre-activations z = [i|f|g|o]
(the FloatSD8 matmuls), the neuron circuit applies: sigmoid LUT (two-region
FloatSD8 quantized, Eqs. 7-8), tanh LUT (FP8 output), the two element-wise
MACs of Eqs. (5)-(6), and the FP16 cell-state write-back. This kernel fuses
all of that into one VMEM pass — one read of z/c_prev, one write of h/c —
instead of the ~10 HBM round-trips the unfused XLA graph makes.

The FloatSD8 quantization of sigma(x) uses the same compare-count + LUT
trick as the quantize kernel, restricted to the 42-value non-positive branch
(paper: 'the depth of the LUT can be reduced'), as a select chain over
compile-time constants (``mosaic.select_lut``).

The cell state keeps the paper's FP16 values, but Mosaic has no f16
vectors: an f16 ``c`` crosses the kernel boundary as f32 (widened before,
narrowed after; both exact) and ``c_t`` is rounded onto the f16 grid inside
the kernel with integer ops (``core.fp8.round_to``), so ``tanh(c_t)``
sees exactly the stored value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core import qsigmoid
from ...core.fp8 import round_to
from .. import mosaic

__all__ = ["lstm_cell_kernel", "lstm_cell_pallas"]

# non-negative representable values at the sigmoid LUT bias, in (0, 0.5]
_SIG_GRID = qsigmoid.sigmoid_lut_values().astype(np.float32)  # 43 incl. 0
_SIG_MID = ((_SIG_GRID[1:] + _SIG_GRID[:-1]) / 2).astype(np.float32)


def _q_sigmoid(x):
    """Two-region FloatSD8 sigmoid via compare-count on the 42-entry LUT."""
    s_neg = jax.nn.sigmoid(-jnp.abs(x))  # in (0, 0.5]
    q = mosaic.select_lut(s_neg, _SIG_MID, _SIG_GRID)
    return jnp.where(x > 0, 1.0 - q, q)


def _q_tanh_fp8(x):
    t = jnp.tanh(x)
    return t.astype(jnp.float8_e5m2).astype(x.dtype)


def lstm_cell_kernel(z_ref, c_ref, h_ref, c_out_ref, *, quantized: bool,
                     c_dtype):
    h = c_ref.shape[-1]
    z = z_ref[...].astype(jnp.float32)
    zi, zf, zg, zo = (z[:, i * h : (i + 1) * h] for i in range(4))
    if quantized:
        i_t, f_t, o_t = _q_sigmoid(zi), _q_sigmoid(zf), _q_sigmoid(zo)
        g_t = _q_tanh_fp8(zg)  # tanh LUT emitting FP8
    else:
        i_t, f_t, o_t = jax.nn.sigmoid(zi), jax.nn.sigmoid(zf), jax.nn.sigmoid(zo)
        g_t = jnp.tanh(zg)
    c_prev = c_ref[...].astype(jnp.float32)
    # Eq. 5 state, on the c_dtype grid
    c_t = round_to(f_t * c_prev + i_t * g_t, c_dtype)
    tc = jnp.tanh(c_t)
    if quantized:
        tc = tc.astype(jnp.float8_e5m2).astype(jnp.float32)
    h_t = o_t * tc  # Eq. 6
    h_ref[...] = h_t.astype(h_ref.dtype)
    c_out_ref[...] = c_t.astype(c_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bb", "bh", "quantized", "c_dtype", "interpret")
)
def lstm_cell_pallas(
    z, c_prev, *, bb: int = 128, bh: int = 512, quantized: bool = True,
    c_dtype=jnp.float16, interpret: bool = False,
):
    """z: [B, 4H], c_prev: [B, H] -> (h [B, H] z.dtype, c [B, H] c_dtype)."""
    b, h4 = z.shape
    h = h4 // 4
    bb, bh = min(bb, b), min(bh, h)
    assert b % bb == 0 and h % bh == 0, (b, h, bb, bh)
    grid = (b // bb, h // bh)

    h_t, c_t = pl.pallas_call(
        functools.partial(lstm_cell_kernel, quantized=quantized,
                          c_dtype=c_dtype),
        grid=grid,
        in_specs=[
            # gate-interleaved columns: each (i,j) tile needs the 4 gate
            # slices of its h-block — index_map picks the j-th h-block of
            # each gate via a strided custom block
            pl.BlockSpec((bb, 4 * bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h), mosaic.io_dtype(z.dtype)),
            jax.ShapeDtypeStruct((b, h), mosaic.io_dtype(c_dtype)),
        ],
        interpret=interpret,
    )(_regroup_gates(mosaic.widen(z), h, bh), mosaic.widen(c_prev))
    return h_t.astype(z.dtype), c_t.astype(c_dtype)


def _regroup_gates(z, h, bh):
    """[B, i|f|g|o] -> blocks where the j-th 4*bh column group holds the
    j-th bh-slice of each gate (so one BlockSpec tile sees all 4 gates)."""
    b = z.shape[0]
    zz = z.reshape(b, 4, h // bh, bh)  # [B, gate, jblock, bh]
    zz = jnp.swapaxes(zz, 1, 2)  # [B, jblock, gate, bh]
    return zz.reshape(b, 4 * h)
