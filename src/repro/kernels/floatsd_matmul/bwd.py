"""Backward implementations for the FloatSD8 matmul (the training hot path).

Forward: y = x @ decode(codes). The VJP splits into two ops with different
precision contracts (paper §III-D):

  dx = g @ decode(codes)^T        — f32 issue + f32 accumulation: the
       activation-gradient path feeds the recurrent BPTT chain, so it runs
       the *precise* datapath; the FP8 activation-gradient quantization
       happens at the act_quant STE nodes, not here.
  dw = fp8(x^T @ g)               — f32 accumulation, then the paper's FP8
       weight-gradient quantizer applied AT THE FLUSH, inside the kernel:
       the gradient leaves VMEM already on the FP8 grid, so train_state
       no longer runs a separate full-tree ``grad_quant`` pass.

``dx`` reuses the forward fused decode+matmul kernel on transposed codes
(decode is element-wise: decode(codes)^T == decode(codes^T), and transposing
the 1-byte codes is 4x cheaper than transposing a decoded f32 tensor). ``dw``
is a dedicated kernel: both operands are dense floats (no decode), and the
FP8 grid-snap rides the accumulator flush for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.fp8 import FP8_E5M2, quantize_fp8
from .. import mosaic
from .kernel import floatsd_matmul_pallas
from .ref import floatsd_matmul_ref

__all__ = [
    "matmul_dx_ref", "matmul_dx_pallas", "matmul_dw_ref", "matmul_dw_pallas",
]


# ---------------------------------------------------------------------------
# dx: g [M, N] x decode(codes [K, N])^T -> [M, K]
# ---------------------------------------------------------------------------


def matmul_dx_ref(g: jax.Array, codes: jax.Array, bias) -> jax.Array:
    """Oracle: g @ decode(codes)^T in f32 (precise datapath)."""
    return floatsd_matmul_ref(g, codes.T, bias, out_dtype=jnp.float32)


def matmul_dx_pallas(g: jax.Array, codes: jax.Array, bias, *, bm: int,
                     bn: int, bk: int, interpret: bool = False) -> jax.Array:
    """The forward fused decode-in-VMEM kernel on transposed codes, f32
    issue dtype (the gradient path is always precise)."""
    return floatsd_matmul_pallas(
        g, codes.T, bias, bm=bm, bn=bn, bk=bk, out_dtype=jnp.float32,
        compute_dtype=jnp.float32, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# dw: x [M, K]^T x g [M, N] -> fp8-quantized f32 [K, N]
# ---------------------------------------------------------------------------


def matmul_dw_ref(x: jax.Array, g: jax.Array, quant: bool = True) -> jax.Array:
    """Oracle: x^T @ g with f32 accumulation, FP8-e5m2 grid snap on the way
    out (fake-quant: f32 storage, FP8 values — the optimizer consumes it
    directly)."""
    dw = jnp.dot(
        x.astype(jnp.float32).T, g.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return quantize_fp8(dw, FP8_E5M2) if quant else dw


def matmul_dw_kernel(xt_ref, g_ref, out_ref, acc_ref, *, n_k: int, quant: bool):
    """One (bk_w x bn) dw tile, accumulating over the M (batch*time) grid
    axis; the flush snaps the f32 accumulator to the FP8-e5m2 grid."""
    m_step = pl.program_id(2)

    @pl.when(m_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xt = xt_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    acc_ref[...] += mosaic.mxu_dot(xt, g)

    @pl.when(m_step == n_k - 1)
    def _flush():
        acc = acc_ref[...]
        if quant:
            # saturating FP8 e5m2 round-trip == core.fp8.quantize_fp8
            acc = jnp.clip(acc, -57344.0, 57344.0)
            acc = acc.astype(jnp.float8_e5m2).astype(jnp.float32)
        out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "quant", "interpret")
)
def matmul_dw_pallas(
    x: jax.Array,  # [M, K]
    g: jax.Array,  # [M, N]
    *,
    bm: int = 256,  # tile over K (dw rows)
    bn: int = 256,  # tile over N (dw cols)
    bk: int = 512,  # tile over M (the contraction axis here)
    quant: bool = True,
    interpret: bool = False,
):
    m, k = x.shape
    m2, n = g.shape
    assert m == m2, (x.shape, g.shape)
    xt = mosaic.widen(x).T  # [K, M]
    bm, bn, bk = min(bm, k), min(bn, n), min(bk, m)
    assert k % bm == 0 and n % bn == 0 and m % bk == 0, (k, n, m, bm, bn, bk)
    n_k = m // bk
    grid = (k // bm, n // bn, n_k)

    return pl.pallas_call(
        functools.partial(matmul_dw_kernel, n_k=n_k, quant=quant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        scratch_shapes=[mosaic.vmem_scratch((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xt, mosaic.widen(g))
