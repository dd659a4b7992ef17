"""Pallas TPU kernel: fused FloatSD4 nibble-unpack + decode + matmul.

Sub-byte sibling of ``floatsd_matmul.kernel``: weights travel HBM->VMEM as
*half* a byte per code (two 4-bit codes per byte, packed along K) plus one
int8 exponent per GROUP x column, are unpacked and decoded in VMEM by the
VPU (nibble mask/shift, the mantissa computed from the 4-bit code, an
exact power of two from the group exponent's bits), and feed the MXU with
f32 accumulation.

Grid (M/bm, N/bn, K/bk), K innermost (output-stationary, accumulator tile
resident in VMEM). The packed-code BlockSpec is (bk/2, bn) and the
exponent BlockSpec (bk/GROUP, bn): the dispatch layer always pads K to a
multiple of 128, so every resolved bk (128/256/512) is divisible by both
2 and GROUP=32. VMEM working set ~= bm*bk (x) + bk/2*bn (bytes) +
bk/32*bn (exps) + bk*bn (decoded, compute dtype) + bm*bn*4 (acc).

``transpose_w`` takes the weight stored [N, K], packed along N (the tied
logits head: the embedding table [vocab, emb] times x [M, emb]): the code
block is (bn/2, bk), decodes exactly as above to a [bn, bk] tile, and the
MXU contracts both operands' last axes. bn (128/256) is divisible by 2
and GROUP as bk is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import floatsd4
from .. import mosaic

__all__ = ["floatsd4_matmul_kernel", "floatsd4_matmul_pallas"]


def _mantissa4(code: jax.Array) -> jax.Array:
    """int32 FloatSD4 codes -> f32 mantissas, equal to ``core.floatsd4
    .LUT16[code]`` without a table: the 15 sorted values are symmetric
    around ZERO_CODE and the positive half in quarters is 1, 3, 4, 5, 7,
    8, 9, i.e. ``a + (a >= 2) + (a >= 5)`` for ``a = |code - ZERO_CODE|``;
    the spare code decodes to 0."""
    d = code - floatsd4.ZERO_CODE
    a = jnp.abs(d)
    q = a + (a >= 2).astype(jnp.int32) + (a >= 5).astype(jnp.int32)
    q = jnp.where(code == floatsd4.SPARE_CODE, 0, q)
    return jnp.where(d < 0, -q, q).astype(jnp.float32) * 0.25


def floatsd4_matmul_kernel(
    x_ref, codes_ref, exps_ref, out_ref, acc_ref, *, n_k: int,
    group: int, compute_dtype=jnp.bfloat16, transpose_w: bool = False,
):
    """One (bm x bn) output tile; accumulates over the K grid axis.

    x_ref:     [bm, bk]        activation tile
    codes_ref: [bk//2, bn]     nibble-packed uint8 FloatSD4 codes
                               ([bn//2, bk] under ``transpose_w``)
    exps_ref:  [bk//group, bn] int8 per-group exponents ([bn//group, bk])
    acc_ref:   [bm, bn]        f32 VMEM accumulator scratch
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = codes_ref[...].astype(jnp.int32)  # [rows//2, cols], packed rows
    r2, cols = packed.shape
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    # interleave rows: unpacked[2i] = lo[i], unpacked[2i+1] = hi[i]
    idx = jnp.stack([lo, hi], axis=1).reshape(2 * r2, cols)
    mant = _mantissa4(idx)
    e = jnp.clip(exps_ref[...].astype(jnp.int32), -126, 127)  # [rows//group, cols]
    scale = mosaic.pow2(jnp.broadcast_to(
        e[:, None, :], (e.shape[0], group, cols)
    ).reshape(2 * r2, cols))
    w = (mant * scale).astype(compute_dtype)

    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += mosaic.mxu_dot(x, w, trans_b=transpose_w)

    @pl.when(k_step == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "compute_dtype",
                     "transpose_w", "interpret"),
)
def floatsd4_matmul_pallas(
    x: jax.Array,  # [M, K]
    codes: jax.Array,  # [K//2, N] uint8, nibble-packed along K ([N//2, K])
    exps: jax.Array,  # [K//GROUP, N] int8 ([N//GROUP, K])
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=jnp.float32,
    compute_dtype=jnp.bfloat16,
    transpose_w: bool = False,
    interpret: bool = False,
):
    m, k = x.shape
    g = floatsd4.GROUP
    if transpose_w:
        n2, kc = codes.shape
        n = 2 * n2
        assert exps.shape == (n // g, k), (exps.shape, k, n)
    else:
        k2, n = codes.shape
        kc = 2 * k2
        assert exps.shape == (k // g, n), (exps.shape, k, n)
    assert k == kc, (x.shape, codes.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    bp = bn if transpose_w else bk  # the packed axis of the code block
    assert bp % 2 == 0 and bp % g == 0, (bp, g)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    if transpose_w:
        w_specs = [
            pl.BlockSpec((bn // 2, bk), lambda i, j, s: (j, s)),
            pl.BlockSpec((bn // g, bk), lambda i, j, s: (j, s)),
        ]
    else:
        w_specs = [
            pl.BlockSpec((bk // 2, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((bk // g, bn), lambda i, j, s: (s, j)),
        ]

    return pl.pallas_call(
        functools.partial(
            floatsd4_matmul_kernel, n_k=n_k, group=g,
            compute_dtype=compute_dtype, transpose_w=transpose_w,
        ),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)), *w_specs],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), mosaic.io_dtype(out_dtype)),
        scratch_shapes=[mosaic.vmem_scratch((bm, bn), jnp.float32)],
        interpret=interpret,
    )(mosaic.widen(x), codes, exps).astype(out_dtype)
