"""Number formats of the plain reference, written from their definitions.

Nothing here comes from the program under test. Each function states the
rule it implements:

* ``round_float``: round-to-nearest-even onto a binary float format with
  ``mbits`` stored mantissa bits and smallest normal exponent ``emin``
  (float16: 10, -14; float8 e5m2: 2, -14), overflow to infinity.
* ``fp8``: the paper's FP8 (1 sign, 5 exponent, 2 mantissa bits) with
  saturation of finite values at its largest magnitude, 57344.
* ``floatsd8``: FloatSD8 (arXiv:2001.08450 section III-A), value =
  m * 2^(e + bias) with m in the 31 sums a + s/4, a in {0, +-1, +-2, +-4},
  s in {0, +-1, +-2}, e in 0..7, one integer ``bias`` per tensor chosen so
  that 4.5 * 2^(7 + bias) is the least such bound >= max|w|; nearest value,
  an exact midpoint going to the smaller magnitude.
* ``floatsd8_sigmoid``: the two-region quantized sigmoid of the paper's
  Eqs. 7-8 with the fixed bias -7: Q(s(x)) for x <= 0, 1 - Q(s(-x)) else.
* ``floatsd4``: the 4-bit serving format: m in the 15 sums a + s/4,
  a in {0, +-1, +-2}, s in {0, +-1}, one exponent per 32 consecutive rows of
  axis 0 and per column, the least e with 2.25 * 2^e >= the group's max|w|;
  nearest value, an exact midpoint going to the smaller value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _pow2(k):
    """Exact 2**k as float32 for integer k in [-126, 127]."""
    k = jnp.clip(jnp.asarray(k, jnp.int32), -126, 127)
    return jax.lax.bitcast_convert_type((k + 127).astype(jnp.uint32) << 23, F32)


def round_float(x, mbits: int, emin: int):
    """x (float32) rounded to nearest-even on a format with ``mbits``
    mantissa bits and smallest normal exponent ``emin``; kept as float32."""
    x = jnp.asarray(x, F32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127          # floor(log2|x|) for normal x
    e = jnp.maximum(e, emin)                  # subnormal range: fixed quantum
    q = _pow2(e - mbits)
    y = jnp.round(x / q) * q                  # division by 2^k is exact
    top = (2.0 - 2.0 ** -mbits) * 2.0 ** (15 if emin == -14 else 127)
    y = jnp.where(jnp.abs(y) > top, jnp.sign(x) * jnp.inf, y)
    return jnp.where(jnp.isfinite(x), y, x)


def fp16(x):
    return round_float(x, 10, -14)


def fp8(x):
    """FP8 e5m2 with saturation of finite values."""
    x = jnp.asarray(x, F32)
    x = jnp.where(jnp.isfinite(x), jnp.clip(x, -57344.0, 57344.0), x)
    return round_float(x, 2, -14)


def _grid_floatsd8() -> np.ndarray:
    mant = {a + s / 4 for a in (0, 1, 2, 4, -1, -2, -4) for s in (0, 1, 2, -1, -2)}
    pos = sorted({abs(m) * 2.0 ** e for m in mant for e in range(8)})
    return np.array(pos, np.float64)


_SD8 = _grid_floatsd8()                       # 0 ... 4.5 * 2^7
_SD8_MID = (_SD8[1:] + _SD8[:-1]) / 2


def _nearest(v, grid: np.ndarray, mids: np.ndarray):
    """grid[#(mids < v)]: the nearest grid value, a tie to the lower one."""
    idx = jnp.sum(v[..., None] > jnp.asarray(mids, F32), axis=-1)
    return jnp.asarray(grid, F32)[idx]


def floatsd8_bias(w):
    amax = jnp.max(jnp.abs(jnp.asarray(w, F32)))
    amax = jnp.where(jnp.isfinite(amax) & (amax > 0), amax, 1.0)
    bias = jnp.ceil(jnp.log2(amax / 4.5)).astype(jnp.int32) - 7
    return jnp.clip(bias, -126, 120)


def floatsd8(w):
    """FloatSD8 value of every element of ``w`` (float32)."""
    w = jnp.asarray(w, F32)
    scale = _pow2(floatsd8_bias(w))
    n = jnp.clip(jnp.abs(w) / scale, 0.0, float(_SD8[-1]))
    return jnp.sign(w) * _nearest(n, _SD8, _SD8_MID) * scale


_SIG = _SD8 * 2.0 ** -7
_SIG = _SIG[_SIG <= 0.5]
_SIG_MID = (_SIG[1:] + _SIG[:-1]) / 2


def floatsd8_sigmoid(z):
    s = jax.nn.sigmoid(-jnp.abs(z))           # s(x) at x = -|z|, in (0, 0.5]
    q = _nearest(s, _SIG, _SIG_MID)
    return jnp.where(z > 0, 1.0 - q, q)


_SD4 = np.array(sorted({a + s / 4 for a in (0, 1, 2, -1, -2) for s in (0, 1, -1)}),
                np.float64)
_SD4_MID = (_SD4[1:] + _SD4[:-1]) / 2
GROUP4 = 32


def floatsd4(w):
    """FloatSD4 value of every element of a [K, ...] tensor (groups of 32
    rows of axis 0 share one exponent per column)."""
    w = jnp.asarray(w, F32)
    k = w.shape[0]
    g = -(-k // GROUP4)
    pad = g * GROUP4 - k
    wp = jnp.pad(w, ((0, pad),) + ((0, 0),) * (w.ndim - 1))
    amax = jnp.abs(wp).reshape((g, GROUP4) + w.shape[1:]).max(axis=1)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-38) / 2.25)).astype(jnp.int32)
    # the least e with 2.25 * 2^e >= amax, exact at powers of two
    e = jnp.where(amax > 2.25 * _pow2(e), e + 1, e)
    e = jnp.where(amax <= 2.25 * _pow2(e - 1), e - 1, e)
    e = jnp.where(amax > 0, e, 0)
    scale = jnp.repeat(_pow2(e), GROUP4, axis=0)[:k]
    n = jnp.clip(w / scale, -2.25, 2.25)
    return _nearest(n, _SD4, _SD4_MID) * scale
