"""FloatSD8 number format (paper §III-A).

An 8-bit weight code: 3-bit exponent field | 5-bit mantissa code.

The mantissa encodes two signed-digit groups:
  * MSG  (3-digit group): one non-zero digit max -> {0, ±1, ±2, ±4}
  * 2nd  (2-digit group): one non-zero digit max -> {0, ±1, ±2}, placed two
    binary positions below the MSG unit, i.e. contributing s/4.

mantissa = m + s/4 with m in {0,±1,±2,±4}, s in {0,±1,±2}  -> 35 combos,
31 distinct values (collisions at ±0.5, ±1.5), range [-4.5, +4.5].

value = mantissa * 2^(e + bias),  e in [0, 7], per-tensor integer ``bias``.

The per-tensor bias is the one deviation from the paper's fixed-field circuit
(recorded in DESIGN.md §3.5a): the 3-bit exponent *field* is unchanged; the
bias is fitted once per tensor so the 8-bit code spends its dynamic range
(~2^11.2) on the tensor's actual magnitude window.

Everything here is pure jnp so it can serve as the oracle for the Pallas
kernels and run under jit on any backend.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MANTISSA_VALUES",
    "MANTISSA_TO_SD",
    "EXP_BITS",
    "EXP_LEVELS",
    "floatsd8_value_grid",
    "fit_bias",
    "quantize",
    "quantize_ste",
    "encode",
    "decode",
    "select_lut",
    "pack",
    "unpack",
    "partial_product_count",
]

EXP_BITS = 3
EXP_LEVELS = 1 << EXP_BITS  # 8

# ---------------------------------------------------------------------------
# Mantissa value set (31 distinct values; paper says "only 31 distinct
# combinations exist, making 5 bits enough").
# ---------------------------------------------------------------------------


def _build_mantissas() -> tuple[np.ndarray, dict[float, tuple[int, int]]]:
    vals: dict[float, tuple[int, int]] = {}
    for m in (-4, -2, -1, 0, 1, 2, 4):
        for s in (-2, -1, 0, 1, 2):
            v = m + s / 4.0
            # Prefer the decomposition with the fewest non-zero digits on
            # collisions (matches minimal partial-product hardware cost).
            if v not in vals or _nzd(m, s) < _nzd(*vals[v]):
                vals[v] = (m, s)
    keys = np.array(sorted(vals.keys()), dtype=np.float32)
    assert keys.size == 31, keys.size
    return keys, vals


def _nzd(m: int, s: int) -> int:
    return int(m != 0) + int(s != 0)


MANTISSA_VALUES, MANTISSA_TO_SD = _build_mantissas()


def _value_grid_np() -> np.ndarray:
    """All distinct non-negative representable values at bias=0, sorted."""
    g = np.unique(
        np.abs(MANTISSA_VALUES)[:, None] * (2.0 ** np.arange(EXP_LEVELS))[None, :]
    )
    return g.astype(np.float64)


_GRID_POS = _value_grid_np()  # includes 0
_GRID_MID = (_GRID_POS[1:] + _GRID_POS[:-1]) / 2.0


def floatsd8_value_grid(bias: int = 0) -> np.ndarray:
    """Every distinct non-negative value representable with this bias."""
    return _GRID_POS * (2.0**bias)


# Precompute, for every distinct grid value, a canonical (e, mantissa-index)
# pair used by ``encode``; chooses the smallest exponent (finest grid) that
# represents the value exactly.
def _grid_codes() -> tuple[np.ndarray, np.ndarray]:
    es = np.zeros(_GRID_POS.size, dtype=np.int32)
    mi = np.zeros(_GRID_POS.size, dtype=np.int32)
    for i, v in enumerate(_GRID_POS):
        found = False
        for e in range(EXP_LEVELS):
            m = v / (2.0**e)
            j = np.searchsorted(MANTISSA_VALUES, m)
            for jj in (j - 1, j, j + 1):
                if 0 <= jj < 31 and MANTISSA_VALUES[jj] == m:
                    es[i], mi[i] = e, jj
                    found = True
                    break
            if found:
                break
        assert found, v
    return es, mi


_GRID_E, _GRID_MIDX = _grid_codes()
_GRID_POS32 = _GRID_POS.astype(np.float32)
_GRID_MID32 = _GRID_MID.astype(np.float32)
# per grid slot, the code of +value in the low byte and of -value in the
# next: code = e << 5 | mantissa index, the sign folded into the index as
# 30 - index (the sorted mantissa set is symmetric around index 15 == 0.0)
_GRID_CODES = ((_GRID_E << 5) | _GRID_MIDX) | (((_GRID_E << 5) | (30 - _GRID_MIDX)) << 8)


def select_lut(x: jax.Array, mids: np.ndarray, values: np.ndarray) -> jax.Array:
    """``values[#(mids < x)]`` for ascending ``mids`` (len(values) ==
    len(mids) + 1): the compare-count LUT lookup as a select chain over
    compile-time constants. ``x > mids[k]`` holds for a prefix of k, so the
    last select that fires picks exactly the counted index (NaN -> values[0],
    as the count gives).

    Elementwise, so XLA fuses the chain into one vectorised loop, and Mosaic
    lowers it inside a Pallas kernel; a gather from a constant table does
    neither (XLA on TPU runs it element by element, Mosaic refuses it)."""
    assert len(values) == len(mids) + 1
    out = jnp.full(x.shape, values[0], np.asarray(values).dtype)
    for m, v in zip(np.asarray(mids).tolist(), np.asarray(values)[1:]):
        out = jnp.where(x > m, v, out)
    return out


class QuantResult(NamedTuple):
    values: jax.Array  # dequantized (same shape/dtype as input)
    bias: jax.Array  # scalar int32 per-tensor exponent bias


def exp2i(k: jax.Array) -> jax.Array:
    """Exact 2^k as f32 for integer k in the normal range [-126, 127].

    jnp.exp2 lowers to exp(k*ln2) on some backends and is ~1 ulp off even
    for integer arguments, which puts quantize() outputs slightly OFF the
    representable grid and breaks the serving weight-store invariant
    decode(encode(w)) == quantize(w).values. Building the float from its
    exponent bits is exact by construction. k is clamped to the normal
    range: below -126 the bit pattern would wrap into the sign bit (a
    fit_bias of ~-135 is reachable for tensors with max|x| ~1e-38), so tiny
    tensors saturate to 2^-126 instead of producing garbage scales.
    """
    k = jnp.clip(jnp.asarray(k, jnp.int32), -126, 127)
    return jax.lax.bitcast_convert_type(
        ((k + 127).astype(jnp.uint32) << 23), jnp.float32
    )


def _clamp_bias(bias) -> jax.Array:
    """Clamp the per-tensor bias so every reachable exponent e + bias
    (e in [0, 7]) stays in f32's normal range. Applied identically by
    quantize/encode/decode so the decode(encode(w)) == quantize(w).values
    invariant holds even for tensors with max|x| near the subnormal floor
    (fit_bias can otherwise reach < -126)."""
    return jnp.clip(jnp.asarray(bias, jnp.int32), -126, 127 - (EXP_LEVELS - 1))


def fit_bias(x: jax.Array) -> jax.Array:
    """Per-tensor exponent bias: put max|x| in the top exponent bin.

    4.5 * 2^(7+bias) >= max|x|  and as tight as possible.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    amax = jnp.where(jnp.isfinite(amax) & (amax > 0), amax, 1.0)
    raw = jnp.ceil(jnp.log2(amax / 4.5)).astype(jnp.int32) - (EXP_LEVELS - 1)
    return _clamp_bias(raw)


def quantize(x: jax.Array, bias: jax.Array | int | None = None) -> QuantResult:
    """Exact nearest-representable-value FloatSD8 quantization (fake-quant).

    Searches the full (exponent x mantissa) grid, which is necessary because
    the mantissa grid has a hole (2.5 -> 3.5): e.g. 3.0 is *exactly*
    representable as 1.5 * 2^1 but naive choose-smallest-exponent rounding
    would return 2.5 or 3.5.
    """
    if bias is None:
        bias = fit_bias(x)
    bias = _clamp_bias(bias)
    xf = x.astype(jnp.float32)
    scale = exp2i(bias)
    n = jnp.abs(xf) / scale
    # clamp into representable window, saturating rounding at the top
    top = _GRID_POS[-1]
    n = jnp.clip(n, 0.0, top)
    q = select_lut(n, _GRID_MID32, _GRID_POS32) * scale
    out = jnp.sign(xf) * q
    return QuantResult(out.astype(x.dtype), bias)


@jax.custom_vjp
def quantize_ste(x: jax.Array, bias: jax.Array) -> jax.Array:
    return quantize(x, bias).values


def _ste_fwd(x, bias):
    return quantize(x, bias).values, None


def _ste_bwd(_, g):
    return g, None  # straight-through: identity grad, no grad to bias


quantize_ste.defvjp(_ste_fwd, _ste_bwd)


# ---------------------------------------------------------------------------
# int8 encode / decode (storage + kernel path)
# ---------------------------------------------------------------------------


def encode(x: jax.Array, bias: jax.Array | int | None = None) -> tuple[jax.Array, jax.Array]:
    """Quantize and pack to uint8 codes. The paper's layout is a 3-bit
    exponent field and a 5-bit SD-group code; here

        code8 = (e << 5) | m_idx        (m_idx in [0, 30])

    with m_idx the index into the 31 sorted ``MANTISSA_VALUES``, which
    carries the sign (the set is symmetric; index 15 is 0.0). Each grid
    value takes its smallest exponent. Returns (codes uint8, bias int32).

    Precondition: ``x`` must be finite. uint8 codes carry no NaN/inf
    representation, and the grid-slot search maps NaN to slot 0, the code
    of 0.0 (every ``>`` comparison is False) — a silent finite encoding.
    Inf saturates to the top grid point (the documented clip behaviour).
    Callers that can see corrupt data must check first; ``serving
    .weight_store.pack_tree`` (the deployment path) raises on nonfinite
    weights before calling this.
    """
    if bias is None:
        bias = fit_bias(x)
    bias = _clamp_bias(bias)
    xf = x.astype(jnp.float32)
    scale = exp2i(bias)
    n = jnp.clip(jnp.abs(xf) / scale, 0.0, _GRID_POS[-1])
    pair = select_lut(n, _GRID_MID32, _GRID_CODES)
    code = jnp.where(xf < 0, pair >> 8, pair & 0xFF)
    return code.astype(jnp.uint8), bias


def decode(codes: jax.Array, bias: jax.Array | int, dtype=jnp.float32) -> jax.Array:
    """Decode uint8 FloatSD8 codes back to real values, in closed form:
    the positive half of the sorted mantissas, in quarters, is 1..10 and
    then 14..18 (the grid's 2.5 -> 3.5 hole), so with ``a = |m_idx - 15|``
    ``|mantissa| = (a + 3 * (a > 10)) / 4``. Index 31 reads as 30."""
    c = codes.astype(jnp.int32)
    e = c >> 5
    d = jnp.clip(c & 0x1F, 0, 30) - 15
    a = jnp.abs(d)
    q = a + 3 * (a > 10).astype(jnp.int32)
    m = jnp.where(d < 0, -q, q).astype(jnp.float32) * 0.25
    bias = _clamp_bias(bias)
    return (m * exp2i(e + bias)).astype(dtype)


# aliases used by the serving/storage path
pack = encode
unpack = decode


def partial_product_count(codes: jax.Array) -> jax.Array:
    """Number of non-zero SD digits (== partial products) per weight, <= 2.

    Used by the Table-VII complexity model.
    """
    midx = jnp.clip(codes.astype(jnp.int32) & 0x1F, 0, 30)
    nz = jnp.asarray(
        [_nzd(*MANTISSA_TO_SD[float(v)]) for v in MANTISSA_VALUES], jnp.int32
    )
    return nz[midx]


@functools.partial(jax.jit, static_argnames=("dtype",))
def fake_quant(x: jax.Array, dtype=None) -> jax.Array:
    """Convenience jitted fake-quant with auto bias (no STE)."""
    out = quantize(x).values
    return out if dtype is None else out.astype(dtype)
