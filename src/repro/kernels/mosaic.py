"""What every Pallas kernel here needs to stay inside what Mosaic, the TPU
kernel compiler, lowers — shared so each kernel states it once.

Three limits of Mosaic on TPU v5e shape the kernels (each found by
compiling for a described v5e chip, ``tests/test_tpu_compile.py``):

  * **no 1-D table gathers** (``Only 2D gather is supported``): a LUT
    lookup inside a kernel is a select chain over compile-time constants
    (:func:`select_lut`) or arithmetic on the code bits;
  * **no f16 vectors** (``Invalid vector type for load``; f32->f16 casts
    fail to legalize): f16 operands cross the kernel boundary as f32
    (:func:`io_dtype`, :func:`widen`; both directions are exact), and a
    value that must land on the f16 grid is rounded with integer ops
    (``core.fp8.round_to``);
  * **uint8 -> f32 casts** are refused; uint8 codes go through int32 first.

``exp2`` is not exact on every backend, so powers of two are built from
exponent bits (:func:`pow2`), the same construction as ``core.floatsd.exp2i``.
And an f32 MXU dot asks for f32 contraction precision explicitly
(:func:`mxu_dot`) rather than leaving the pass count to the compiler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "io_dtype", "widen", "pow2", "select_lut", "mxu_dot", "vmem_scratch",
]

_F16 = jnp.dtype(jnp.float16)


def io_dtype(dtype) -> jnp.dtype:
    """The dtype a kernel ref carries for a tensor of logical ``dtype``."""
    dt = jnp.dtype(dtype)
    return jnp.dtype(jnp.float32) if dt == _F16 else dt


def widen(x: jax.Array) -> jax.Array:
    """Cast an operand to :func:`io_dtype` (outside the kernel; exact)."""
    return x.astype(io_dtype(x.dtype))


def pow2(k: jax.Array) -> jax.Array:
    """Exact 2^k as f32 for int32 ``k`` in [-126, 127] (callers clamp)."""
    return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)


def select_lut(x: jax.Array, mids: np.ndarray, values: np.ndarray) -> jax.Array:
    """``values[#(mids < x)]`` for ascending ``mids`` (len(values) ==
    len(mids) + 1): the compare-count LUT lookup as a select chain over
    compile-time constants. ``x > mids[k]`` holds for a prefix of k, so the
    last select that fires picks exactly the counted index (NaN -> values[0],
    as the count gives)."""
    assert len(values) == len(mids) + 1
    out = jnp.full(x.shape, values[0], np.asarray(values).dtype)
    for m, v in zip(np.asarray(mids).tolist(), np.asarray(values)[1:]):
        out = jnp.where(x > m, v, out)
    return out


def mxu_dot(a: jax.Array, b: jax.Array, *, trans_b: bool = False) -> jax.Array:
    """[m, k] x [k, n] (``trans_b``: x [n, k]^T) -> f32 accumulation; f32
    operands contract at f32 precision (the precise datapath), narrower
    ones at their own. Both are explicit, so an enclosing
    ``jax.default_matmul_precision`` cannot ask Mosaic for f32 contraction
    of bf16 operands, which it refuses."""
    f32 = a.dtype == jnp.float32 and b.dtype == jnp.float32
    P = jax.lax.Precision
    return jax.lax.dot_general(
        a, b, (((1,), (1 if trans_b else 0,)), ((), ())),
        precision=P.HIGHEST if f32 else P.DEFAULT,
        preferred_element_type=jnp.float32)


def vmem_scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
