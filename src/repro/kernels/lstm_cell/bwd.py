"""Recompute-gates backward for the fused LSTM element-wise cell.

Residual contract (the memory side of the tentpole): the forward saves ONLY
``(z, c_prev)`` — the pre-activations and the incoming cell state. Everything
autodiff would have stacked per time step (three sigmoid outputs, their
quantized values, g, tanh(c), c_t, the products...) is recomputed here from
z in one fused pass. That cuts BPTT residual memory from ~13 [B,H]-sized
tensors per step to 5 ([B,4H] z + [B,H] c_prev) and turns the backward into
a single VMEM-resident kernel instead of a chain of HBM round-trips.

Gradient semantics match the straight-through estimators of the inline
training math (``nn.lstm.LSTMCell.step``):

  * forward VALUES are the quantized ones (two-region FloatSD8 sigmoid,
    FP8 tanh) — they appear in the product rule terms;
  * derivative FACTORS are the smooth ones (sigma', tanh') — the STE
    wrappers route gradients through the exact nonlinearity.

One recorded deviation from the autodiff oracle: the chain through the
``c_t.astype(c_dtype)`` cast stays f32 here (autodiff rounds the tanh-path
cotangent to fp16 before summing when the cell state is fp16). dz is then
strictly *more* precise than the oracle; the parity tests pin the fp32-cell
policies tight and the fp16-cell policies to the fp16 rounding envelope.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.fp8 import FP8_E5M2, quantize_fp8, round_to
from ...core.qsigmoid import qsigmoid_raw
from .. import mosaic
from .kernel import _q_sigmoid, _regroup_gates

__all__ = ["lstm_cell_bwd_ref", "lstm_cell_bwd_pallas"]


def lstm_cell_bwd_ref(z, c_prev, dh, dc, quantized: bool = True,
                      c_dtype=jnp.float16):
    """z: [B, 4H] (i|f|g|o), c_prev: [B, H], dh: [B, H] (cotangent of h_t),
    dc: [B, H] (cotangent of c_t from the carry). Returns (dz [B,4H] f32,
    dc_prev [B,H] in c_prev.dtype)."""
    h = c_prev.shape[-1]
    z32 = z.astype(jnp.float32)
    zi, zf, zg, zo = jnp.split(z32, 4, axis=-1)
    si, sf, so = jax.nn.sigmoid(zi), jax.nn.sigmoid(zf), jax.nn.sigmoid(zo)
    tg = jnp.tanh(zg)
    if quantized:
        i_t, f_t, o_t = qsigmoid_raw(zi), qsigmoid_raw(zf), qsigmoid_raw(zo)
        g_t = quantize_fp8(tg, FP8_E5M2)
    else:
        i_t, f_t, o_t, g_t = si, sf, so, tg
    c_prev32 = c_prev.astype(jnp.float32)
    # recompute the EXACT forward cell state, including the storage rounding
    c32 = round_to(f_t * c_prev32 + i_t * g_t, c_dtype)
    tanh_c = jnp.tanh(c32)
    tc = quantize_fp8(tanh_c, FP8_E5M2) if quantized else tanh_c

    dh32 = dh.astype(jnp.float32)
    dc32 = dc.astype(jnp.float32)
    dzo = (dh32 * tc) * so * (1.0 - so)
    dct = dc32 + dh32 * o_t * (1.0 - tanh_c * tanh_c)
    dzf = (dct * c_prev32) * sf * (1.0 - sf)
    dzi = (dct * g_t) * si * (1.0 - si)
    dzg = (dct * i_t) * (1.0 - tg * tg)
    dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
    dc_prev = (dct * f_t).astype(c_prev.dtype)
    del h
    return dz, dc_prev


def lstm_cell_bwd_kernel(z_ref, c_ref, dh_ref, dc_ref, dz_ref, dcp_ref, *,
                         quantized: bool, c_dtype):
    h = c_ref.shape[-1]
    z = z_ref[...].astype(jnp.float32)
    zi, zf, zg, zo = (z[:, i * h : (i + 1) * h] for i in range(4))
    si, sf, so = jax.nn.sigmoid(zi), jax.nn.sigmoid(zf), jax.nn.sigmoid(zo)
    tg = jnp.tanh(zg)
    if quantized:
        i_t, f_t, o_t = _q_sigmoid(zi), _q_sigmoid(zf), _q_sigmoid(zo)
        g_t = tg.astype(jnp.float8_e5m2).astype(jnp.float32)
    else:
        i_t, f_t, o_t, g_t = si, sf, so, tg
    c_prev = c_ref[...].astype(jnp.float32)
    c32 = round_to(f_t * c_prev + i_t * g_t, c_dtype)
    tanh_c = jnp.tanh(c32)
    tc = tanh_c.astype(jnp.float8_e5m2).astype(jnp.float32) if quantized else tanh_c

    dh = dh_ref[...].astype(jnp.float32)
    dc = dc_ref[...].astype(jnp.float32)
    dzo = (dh * tc) * so * (1.0 - so)
    dct = dc + dh * o_t * (1.0 - tanh_c * tanh_c)
    dzf = (dct * c_prev) * sf * (1.0 - sf)
    dzi = (dct * g_t) * si * (1.0 - si)
    dzg = (dct * i_t) * (1.0 - tg * tg)
    dz_ref[...] = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1).astype(dz_ref.dtype)
    dcp_ref[...] = (dct * f_t).astype(dcp_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bb", "bh", "quantized", "c_dtype", "interpret")
)
def lstm_cell_bwd_pallas(
    z, c_prev, dh, dc, *, bb: int = 128, bh: int = 512, quantized: bool = True,
    c_dtype=jnp.float16, interpret: bool = False,
):
    """Fused recompute-gates backward. z: [B, 4H], c_prev/dh/dc: [B, H] ->
    (dz [B, 4H] f32, dc_prev [B, H] in c_prev.dtype)."""
    b, h4 = z.shape
    h = h4 // 4
    bb, bh = min(bb, b), min(bh, h)
    assert b % bb == 0 and h % bh == 0, (b, h, bb, bh)
    grid = (b // bb, h // bh)

    dz_g, dcp = pl.pallas_call(
        functools.partial(lstm_cell_bwd_kernel, quantized=quantized,
                          c_dtype=c_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 4 * bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 4 * bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 4 * h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), mosaic.io_dtype(c_prev.dtype)),
        ],
        interpret=interpret,
    )(
        _regroup_gates(mosaic.widen(z), h, bh),
        mosaic.widen(c_prev),
        mosaic.widen(dh),
        mosaic.widen(dc),
    )
    return _ungroup_gates(dz_g, h, bh), dcp.astype(c_prev.dtype)


def _ungroup_gates(zg, h, bh):
    """Inverse of ``kernel._regroup_gates``: blocked (jblock, gate, bh)
    columns back to the contiguous i|f|g|o gate layout."""
    b = zg.shape[0]
    zz = zg.reshape(b, h // bh, 4, bh)  # [B, jblock, gate, bh]
    zz = jnp.swapaxes(zz, 1, 2)  # [B, gate, jblock, bh]
    return zz.reshape(b, 4 * h)
