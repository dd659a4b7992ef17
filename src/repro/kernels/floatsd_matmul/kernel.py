"""Pallas TPU kernel: fused FloatSD8-decode + matmul.

The paper's MAC multiplies FP8 activations by FloatSD8 weights using two
shifted partial products. The TPU-native adaptation (DESIGN.md §3.1): weights
travel HBM->VMEM as 1-byte codes (2x less bandwidth than bf16), are decoded
*in VMEM* by the VPU (a 32-entry mantissa LUT gather + exp2 scale — the
vector-unit analogue of the two shifts), and feed the MXU in bf16 with f32
accumulation.

Grid (M/bm, N/bn, K/bk); K is the innermost (sequential) axis so the f32
accumulator tile stays resident in VMEM across K steps (output-stationary,
exactly like the paper's PE). Block sizes default to MXU-aligned multiples
of 128; VMEM working set = bm*bk (x) + bk*bn (codes) + bm*bn*4 (acc)
= 256*512*1 + 512*256*1 + 256*256*4 ~= 0.5 MB « 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import floatsd
from .. import mosaic

__all__ = ["floatsd_matmul_kernel", "floatsd_matmul_pallas", "decode_tile"]


def decode_tile(codes: jax.Array, bias: jax.Array) -> jax.Array:
    """uint8 FloatSD8 codes -> f32 values, bit-identical to
    ``core.floatsd.decode`` (``bias`` already clamped) without a table.

    The 31 sorted mantissas are symmetric around index 15 (value 0), and
    the positive half in quarters is 1..10 followed by 14..18 (the grid's
    2.5 -> 3.5 hole), so ``|mantissa| = (a + 3 * (a > 10)) / 4`` with
    ``a = |idx - 15|``. Index 31 is clipped to 30, as the oracle does.
    """
    c = codes.astype(jnp.int32)
    d = jnp.minimum(c & 0x1F, 30) - 15
    a = jnp.abs(d)
    q = a + 3 * (a > 10).astype(jnp.int32)
    mant = jnp.where(d < 0, -q, q).astype(jnp.float32) * 0.25
    return mant * mosaic.pow2((c >> 5) + bias)


def floatsd_matmul_kernel(
    x_ref, codes_ref, bias_ref, out_ref, acc_ref, *, n_k: int,
    compute_dtype=jnp.bfloat16,
):
    """One (bm x bn) output tile; accumulates over the K grid axis.

    x_ref:     [bm, bk]  activation tile (fp8/bf16/f32 storage)
    codes_ref: [bk, bn]  uint8 FloatSD8 codes
    bias_ref:  [1, 1]    int32 per-tensor exponent bias (clamped)
    acc_ref:   [bm, bn]  f32 VMEM accumulator scratch

    ``compute_dtype`` is the MXU issue dtype: bf16 (default, full MXU rate)
    or f32 (bit-tight vs the oracle — the dispatch layer's parity mode).
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # decoded tile stays in VMEM
    w = decode_tile(codes_ref[...], bias_ref[0, 0]).astype(compute_dtype)

    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += mosaic.mxu_dot(x, w)

    @pl.when(k_step == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "compute_dtype", "interpret"),
)
def floatsd_matmul_pallas(
    x: jax.Array,  # [M, K]
    codes: jax.Array,  # [K, N] uint8
    bias: jax.Array,  # scalar int32
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=jnp.float32,
    compute_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    m, k = x.shape
    k2, n = codes.shape
    assert k == k2, (x.shape, codes.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    return pl.pallas_call(
        functools.partial(
            floatsd_matmul_kernel, n_k=n_k, compute_dtype=compute_dtype
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((1, 1), lambda i, j, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), mosaic.io_dtype(out_dtype)),
        scratch_shapes=[mosaic.vmem_scratch((bm, bn), jnp.float32)],
        interpret=interpret,
    )(mosaic.widen(x), codes,
      jnp.reshape(floatsd._clamp_bias(bias), (1, 1))).astype(out_dtype)
