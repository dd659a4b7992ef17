"""FloatSD4 number format (sub-byte serving variant — ours, not the paper's).

A 4-bit weight code indexing a 15-entry signed-digit mantissa grid, with a
*per-group shared exponent* instead of FloatSD8's per-code 3-bit exponent
field:

  * MSG  (2-digit group): one non-zero digit max -> m in {0, ±1, ±2}
  * 2nd  (1-digit group): s in {0, ±1}, placed two binary positions below
    the MSG unit, contributing s/4.

mantissa = m + s/4  -> 15 distinct values, range [-2.25, +2.25], at most
two non-zero SD digits per weight (same partial-product budget as
FloatSD8).  The 16th code (0xF) is spare and decodes to exactly 0.0, which
also makes an all-spare pad nibble safe.

value = mantissa * 2^e(group),  one int8 exponent per GROUP consecutive
rows (axis 0 — the contraction axis of a [K, N] weight) per column.

The format exists for serving density: two codes pack per byte, so a
packed [K, N] weight streams ceil(K/2)*N code bytes + ceil(K/GROUP)*N
exponent bytes — about half FloatSD8's K*N + 4.  It is derived offline
from a trained FloatSD8 master copy (``serving.weight_store
.pack_floatsd4``); there is no FloatSD4 training path.

Same bit-exactness discipline as :mod:`repro.core.floatsd`: scales are
built via ``exp2i`` (exact powers of two from exponent bits), the group
exponent fit is corrected with exact integer comparisons, and the grid is
dyadic, so ``decode(encode(w))`` is idempotent bit-identically — the
serving weight-store invariant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .floatsd import exp2i

__all__ = [
    "MANTISSA_VALUES",
    "ZERO_CODE",
    "SPARE_CODE",
    "GROUP",
    "TOP",
    "fit_group_exp",
    "quantize",
    "encode",
    "decode",
    "pack_nibbles",
    "unpack_nibbles",
    "decode_packed",
    "gather_decode",
]

GROUP = 32  # rows (axis 0) sharing one exponent; divides every pallas bk
TOP = 2.25  # largest representable |mantissa|


def _build_mantissas() -> np.ndarray:
    vals = sorted({m + s / 4.0 for m in (-2, -1, 0, 1, 2) for s in (-1, 0, 1)})
    arr = np.array(vals, dtype=np.float32)
    assert arr.size == 15, arr.size  # no collisions in this digit set
    return arr


MANTISSA_VALUES = _build_mantissas()
_MANTISSA_J = jnp.asarray(MANTISSA_VALUES)
_MANTISSA_MID = jnp.asarray((MANTISSA_VALUES[1:] + MANTISSA_VALUES[:-1]) / 2.0)

# code that decodes to exactly 0.0 at any exponent (index of 0.0 in the
# sorted symmetric grid) — the odd-K / tile padding convention
ZERO_CODE = int(np.searchsorted(MANTISSA_VALUES, 0.0))
assert ZERO_CODE == 7
# the unused 16th code; the decode LUT maps it to 0.0 as well
SPARE_CODE = 15

# 16-entry decode LUT (spare code -> 0.0) for the nibble-unpack kernels
LUT16 = np.zeros(16, dtype=np.float32)
LUT16[:15] = MANTISSA_VALUES
_LUT16_J = jnp.asarray(LUT16)


def _count_idx(mids: jax.Array, n: jax.Array) -> jax.Array:
    """index = #(mids < n), i.e. searchsorted(mids, n, side='left') — but as
    one broadcast compare-count instead of jnp.searchsorted. searchsorted
    lowers to a log2(len)-trip while loop whose body round-trips the full
    tensor each iteration (~7x the HBM traffic on activation-sized inputs,
    measured in EXPERIMENTS.md §Perf); the compare-count is a single fusion
    with a [..., len(mids)] intermediate. (FloatSD8's ``core.floatsd`` and
    the Pallas kernels use ``core.floatsd.select_lut`` instead, a select
    chain with no such intermediate and no table gather.)"""
    return jnp.sum(
        (n[..., None] > mids[(None,) * n.ndim]).astype(jnp.int32), axis=-1
    )


def _num_groups(k: int) -> int:
    return -(-k // GROUP)


def _expand_group_rows(e: jax.Array, k: int) -> jax.Array:
    """[G, ...] per-group array -> [k, ...] per-row (repeat + crop)."""
    return jnp.repeat(e, GROUP, axis=0)[:k]


def fit_group_exp(x: jax.Array) -> jax.Array:
    """Per-(group, column) exponent: put the group's max|x| in (1.125, 2.25]
    after scaling, i.e. the tightest e with TOP * 2^e >= max|x|.

    Exact by construction: the float log2 estimate is corrected with
    integer-exponent comparisons against ``TOP * exp2i(e)``, so the fit
    never lands off-by-one at a power-of-two boundary.  All-zero groups
    get e = 0.  Returns int8 of shape [ceil(K/GROUP), ...trailing dims].
    """
    xf = jnp.abs(x.astype(jnp.float32))
    k = x.shape[0]
    g = _num_groups(k)
    pad = g * GROUP - k
    if pad:
        xf = jnp.pad(xf, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    amax = xf.reshape((g, GROUP) + x.shape[1:]).max(axis=1)
    amax = jnp.where(jnp.isfinite(amax), amax, 0.0)
    raw = jnp.where(
        amax > 0,
        jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-45) / TOP)).astype(jnp.int32),
        0,
    )
    # one-step exact correction of the float estimate
    raw = jnp.where(amax > TOP * exp2i(raw), raw + 1, raw)
    raw = jnp.where((amax > 0) & (amax <= TOP * exp2i(raw - 1)), raw - 1, raw)
    e = jnp.where(amax > 0, jnp.clip(raw, -126, 127), 0)
    return e.astype(jnp.int8)


def _round_codes(x: jax.Array, exps: jax.Array) -> jax.Array:
    """Nearest-grid-value code per element under the group exponents."""
    xf = x.astype(jnp.float32)
    scale = exp2i(_expand_group_rows(exps.astype(jnp.int32), x.shape[0]))
    n = jnp.clip(xf / scale, -TOP, TOP)
    return _count_idx(_MANTISSA_MID, n).astype(jnp.uint8)  # 0..14


def encode(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """FloatSD4-quantize ``x`` (axis 0 = grouped/contraction axis).

    Returns ``(codes, exps)``: unpacked uint8 codes in [0, 14] with the
    same shape as ``x``, and int8 exponents of shape
    ``[ceil(K/GROUP), ...]``.  Same finiteness precondition as FloatSD8's
    ``encode``: NaN/inf have no code; the deployment path
    (``serving.weight_store``) raises on nonfinite weights first.
    """
    exps = fit_group_exp(x)
    return _round_codes(x, exps), exps


def decode(codes: jax.Array, exps: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Decode unpacked uint8 FloatSD4 codes back to real values."""
    m = _LUT16_J[codes.astype(jnp.int32) & 0xF]
    scale = exp2i(_expand_group_rows(exps.astype(jnp.int32), codes.shape[0]))
    return (m * scale).astype(dtype)


def quantize(x: jax.Array, dtype=None) -> jax.Array:
    """Fake-quant convenience: decode(encode(x)) in one call."""
    codes, exps = encode(x)
    return decode(codes, exps, dtype=dtype or x.dtype)


# ---------------------------------------------------------------------------
# 2-codes/byte nibble packing (axis 0; low nibble = even row)
# ---------------------------------------------------------------------------


def pack_nibbles(codes: jax.Array) -> jax.Array:
    """[K, ...] uint8 codes -> [ceil(K/2), ...] bytes.

    byte[i] = codes[2i] | codes[2i+1] << 4.  Odd K pads one ZERO_CODE row
    (decodes to exact 0.0 at any exponent), so a pad byte is 0x77.
    """
    k = codes.shape[0]
    c = codes.astype(jnp.uint8)
    if k % 2:
        pad = jnp.full((1,) + codes.shape[1:], ZERO_CODE, jnp.uint8)
        c = jnp.concatenate([c, pad], axis=0)
    return (c[0::2] | (c[1::2] << 4)).astype(jnp.uint8)


def unpack_nibbles(packed: jax.Array, k: int) -> jax.Array:
    """[ceil(K/2), ...] bytes -> [k, ...] uint8 codes (bit-exact inverse)."""
    lo = packed & jnp.uint8(0xF)
    hi = (packed >> 4) & jnp.uint8(0xF)
    inter = jnp.stack([lo, hi], axis=1)
    return inter.reshape((2 * packed.shape[0],) + packed.shape[1:])[:k]


def decode_packed(packed: jax.Array, exps: jax.Array, k: int,
                  dtype=jnp.float32) -> jax.Array:
    """Decode a nibble-packed code stream back to a dense [k, ...] tensor."""
    return decode(unpack_nibbles(packed, k), exps, dtype=dtype)


def gather_decode(packed: jax.Array, exps: jax.Array, tokens: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """Row-gather + decode for a nibble-packed [V, D] table (the packed
    embedding lookup): fetch byte row ``t // 2``, select the nibble by
    ``t % 2``, scale by exponent row ``t // GROUP``. Bit-identical to
    decode-then-gather (decode is element-wise) at half the gather
    traffic of the FloatSD8 path."""
    t = tokens.astype(jnp.int32)
    byte = jnp.take(packed, t // 2, axis=0)  # [..., D]
    code = (byte >> ((t % 2) * 4)[..., None].astype(jnp.uint8)) & jnp.uint8(0xF)
    m = _LUT16_J[code.astype(jnp.int32)]
    e = jnp.take(exps, t // GROUP, axis=0).astype(jnp.int32)
    return (m * exp2i(e)).astype(dtype)
