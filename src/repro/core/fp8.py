"""FP8 quantization (paper §III-D): 1-bit sign, 5-bit exponent, 2-bit
mantissa == IEEE-style ``float8_e5m2`` [Wang et al., NeurIPS'18].

Forward activations, backward activation-gradients, and weight gradients are
all quantized to FP8 with *regular* (round-to-nearest-even, hardware native)
rounding, per paper §III-D. ``float8_e4m3fn`` is available as a beyond-paper
option for inference activations.

The rounding is done with integer ops on the f32 bits (``round_to``), not
with a cast pair ``x.astype(fp8).astype(f32)``: XLA drops such a lossy
pair inside a fusion when it allows excess precision, the TPU default, so
on a TPU the cast pair silently skips the quantizer. Integer ops cannot be
elided, and give bit-identical values on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FP8_E5M2", "FP8_E4M3", "FP16",
    "round_to", "quantize_fp8", "cast_fp8", "act_quant", "grad_quant",
]

FP8_E5M2 = jnp.float8_e5m2
FP8_E4M3 = jnp.float8_e4m3fn
FP16 = jnp.float16

_MAX = {FP8_E5M2: 57344.0, FP8_E4M3: 448.0, FP16: 65504.0}

# dtype -> (exponent bits, mantissa bits, largest finite, has inf)
_FORMATS = {
    jnp.dtype(FP16): (5, 10, 65504.0, True),
    jnp.dtype(FP8_E5M2): (5, 2, 57344.0, True),
    jnp.dtype(FP8_E4M3): (4, 3, 448.0, False),
}


def _bits(v: float) -> int:
    return int(np.float32(v).view(np.int32))


def round_to(x: jax.Array, dtype) -> jax.Array:
    """f32 ``x`` rounded onto ``dtype``'s grid and kept as f32, bit-identical
    to ``x.astype(dtype).astype(float32)`` for float16, float8_e5m2 and
    float8_e4m3fn: round-to-nearest-even, subnormals, signed zeros, NaN,
    overflow to inf (or to NaN where the format has no inf) — with integer
    ops only, so no compiler can drop it (module docstring). Differentiates
    like the cast pair: the cotangent is rounded the same way. float32
    passes through; other dtypes use the cast pair."""
    dt = jnp.dtype(dtype)
    x = x.astype(jnp.float32)
    if dt == jnp.float32:
        return x
    if dt not in _FORMATS:
        return x.astype(dt).astype(jnp.float32)
    return _round_bits(x, dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_bits(x: jax.Array, dt) -> jax.Array:
    e, m, top, has_inf = _FORMATS[dt]
    bias = 2 ** (e - 1) - 1
    drop = 23 - m
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    sign = bits & jnp.int32(-(2**31))
    mag = bits & 0x7FFFFFFF
    exp = mag >> 23
    # normal range: drop the low mantissa bits, ties to even (a carry into
    # the exponent is the correct rounding up to the next binade)
    half = (1 << (drop - 1)) - 1
    normal = (mag + half + ((mag >> drop) & 1)) & ~((1 << drop) - 1)
    over = 0x7F800000 if has_inf else 0x7FC00000
    normal = jnp.where(normal > _bits(top), over, normal)
    # subnormals: the significand s * 2^(exp - 150) in units of the format's
    # quantum 2^(1 - bias - m) is s >> (151 - bias - m - exp), rounded to
    # even (capped at 25 bits, beyond which every s rounds to 0)
    s = (mag & 0x7FFFFF) | 0x800000
    d = jnp.clip(151 - bias - m - exp, 1, 25)
    q = (s + (1 << (d - 1)) - 1 + ((s >> d) & 1)) >> d
    sub = jax.lax.bitcast_convert_type(
        q.astype(jnp.float32) * np.float32(2.0 ** (1 - bias - m)), jnp.int32
    )
    out = jnp.where(exp < 128 - bias, sub, normal)
    nonfinite = mag if has_inf else jnp.full_like(mag, 0x7FC00000)
    out = jnp.where(exp == 255, nonfinite, out)  # inf / NaN
    return jax.lax.bitcast_convert_type(out | sign, jnp.float32)


def _round_bits_fwd(x, dt):
    return _round_bits(x, dt), None


def _round_bits_bwd(dt, _, g):
    # the transpose of the cast pair: the cotangent is cast the same way
    return (_round_bits(g, dt),)


_round_bits.defvjp(_round_bits_fwd, _round_bits_bwd)


def quantize_fp8(x: jax.Array, dtype=FP8_E5M2) -> jax.Array:
    """Round-trip cast x -> fp8 -> original dtype (fake-quant), saturating
    on *finite* overflow only.

    Saturation (rather than inf) keeps large loss-scaled gradients finite,
    matching hardware clamp behaviour — but genuinely nonfinite inputs must
    stay nonfinite: ``jnp.clip`` maps ``inf`` to the finite max, which
    would launder an overflowed gradient past the loss-scaler's
    ``unscale_and_check`` (the skip-and-backoff loop could then never
    fire on inf, only NaN). ``where(isfinite)`` preserves inf/NaN through
    the quantizer; the downstream finite check is the policy point that
    decides what happens to them.
    """
    if dtype is None:
        return x
    m = _MAX[dtype]
    xf = x.astype(jnp.float32)
    xc = jnp.where(jnp.isfinite(xf), jnp.clip(xf, -m, m), xf)
    return round_to(xc, dtype).astype(x.dtype)


def cast_fp8(x: jax.Array, dtype=FP8_E5M2) -> jax.Array:
    """Real (storage) cast x -> fp8, saturating like ``quantize_fp8`` but
    returning the 1-byte array itself — the format the serving frontend
    stores cached LSTM states in. ``x.astype(back)`` recovers the
    fake-quant value exactly (fp8 -> wider float is lossless). Nonfinite
    inputs stay nonfinite (e4m3fn has no inf code, so inf lands on NaN —
    still detectable) rather than silently saturating to a finite code."""
    m = _MAX[dtype]
    xf = x.astype(jnp.float32)
    return jnp.where(jnp.isfinite(xf), jnp.clip(xf, -m, m), xf).astype(dtype)


def _make_roundtrip(fwd_dtype, bwd_dtype):
    @jax.custom_vjp
    def f(x):
        return quantize_fp8(x, fwd_dtype)

    def fwd(x):
        return quantize_fp8(x, fwd_dtype), None

    def bwd(_, g):
        return (quantize_fp8(g, bwd_dtype),)

    f.defvjp(fwd, bwd)
    return f


# cache of (fwd, bwd) -> function, keyed by dtype names so jit caching works
_CACHE: dict = {}


def act_quant(x: jax.Array, fwd_dtype=FP8_E5M2, bwd_dtype=FP8_E5M2) -> jax.Array:
    """Quantization node: forward activation -> fwd_dtype, incoming
    activation-gradient -> bwd_dtype (both fake-quant). Either may be None
    (pass-through) or jnp.float16 for the paper's last-layer FP16 setting."""
    key = (fwd_dtype, bwd_dtype)
    if key not in _CACHE:
        _CACHE[key] = _make_roundtrip(fwd_dtype, bwd_dtype)
    return _CACHE[key](x)


def grad_quant(tree, dtype=FP8_E5M2):
    """Quantize a (loss-scaled) gradient pytree to FP8 (fake-quant).

    Applied after backward, before the optimizer: the paper's weight update is
    'addition of the FP16 master copy weight and the FP8 gradient'.

    Under the fused-BPTT path the LSTM dW leaves arrive already ON the fp8
    grid (emitted by ``kernels.dispatch.matmul_dw`` at the kernel flush), so
    this pass is an exact no-op on them — ``quantize_fp8`` is idempotent —
    while still providing the paper's §III-D coverage (and overflow
    saturation) for params no kernel emits: biases, embedding tables, and
    the non-LSTM archs' direct-use params (rwkv decay/bonus, norms).
    """
    return jax.tree_util.tree_map(lambda g: quantize_fp8(g, dtype), tree)
