"""Oracle for the fused LSTM element-wise cell (paper Eqs. 5-6 + q-sigmoid)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.fp8 import quantize_fp8, round_to
from ...core.qsigmoid import qsigmoid_raw

__all__ = ["lstm_cell_ref"]


def lstm_cell_ref(z, c_prev, quantized: bool = True, c_dtype=jnp.float16):
    """z: [B, 4H] pre-activations (i|f|g|o), c_prev: [B, H].

    Returns (h [B,H], c [B,H]) with the paper's quantization (FloatSD8
    two-region sigmoid on gates, FP8 tanh LUT outputs, FP16 cell state).
    ``c_dtype`` is the cell-state storage dtype (f16 per the paper; f32 for
    fp32-master policies, so the dispatched cell matches any policy).
    """
    h4 = z.shape[-1]
    h = h4 // 4
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    if quantized:
        i_t, f_t, o_t = qsigmoid_raw(zi), qsigmoid_raw(zf), qsigmoid_raw(zo)
        g_t = quantize_fp8(jnp.tanh(zg))
    else:
        i_t, f_t, o_t = jax.nn.sigmoid(zi), jax.nn.sigmoid(zf), jax.nn.sigmoid(zo)
        g_t = jnp.tanh(zg)
    c_t = round_to(f_t * c_prev.astype(f_t.dtype) + i_t * g_t,
                   c_dtype).astype(c_dtype)
    tc = quantize_fp8(jnp.tanh(c_t.astype(z.dtype))) if quantized else jnp.tanh(c_t.astype(z.dtype))
    h_t = o_t * tc
    return h_t.astype(z.dtype), c_t
