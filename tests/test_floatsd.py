"""Unit + property tests for the FloatSD8 format (paper §III-A, Table I)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import floatsd

jax.config.update("jax_enable_x64", False)


def test_mantissa_set_has_31_distinct_values():
    # Paper: "out of the 35 combinations, only 31 distinct combinations exist"
    assert floatsd.MANTISSA_VALUES.size == 31
    assert floatsd.MANTISSA_VALUES.min() == -4.5
    assert floatsd.MANTISSA_VALUES.max() == 4.5
    # symmetric set
    np.testing.assert_allclose(
        floatsd.MANTISSA_VALUES, -floatsd.MANTISSA_VALUES[::-1]
    )


def test_msg_values_match_table1():
    # Table I: 3-digit group values are exactly {+-4, +-2, +-1, 0}
    msgs = sorted({m for (m, s) in floatsd.MANTISSA_TO_SD.values()})
    assert msgs == [-4, -2, -1, 0, 1, 2, 4]
    sgs = sorted({s for (m, s) in floatsd.MANTISSA_TO_SD.values()})
    assert sgs == [-2, -1, 0, 1, 2]


def test_at_most_two_partial_products():
    # the entire hardware claim: <= 2 non-zero SD digits per weight
    for v, (m, s) in floatsd.MANTISSA_TO_SD.items():
        assert (m != 0) + (s != 0) <= 2
        assert m + s / 4.0 == v


def test_exact_values_roundtrip():
    # every representable value must quantize to itself
    for bias in (-10, -7, 0, 3):
        grid = floatsd.floatsd8_value_grid(bias)
        x = jnp.asarray(np.concatenate([grid, -grid]), jnp.float32)
        q = floatsd.quantize(x, bias=bias).values
        np.testing.assert_array_equal(np.asarray(q), np.asarray(x))


def test_encode_decode_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(512,)).astype(np.float32))
    codes, bias = floatsd.encode(x)
    back = floatsd.decode(codes, bias)
    q = floatsd.quantize(x, bias=bias).values
    np.testing.assert_allclose(np.asarray(back), np.asarray(q), rtol=0, atol=0)
    assert codes.dtype == jnp.uint8


def test_quantize_is_nearest_value():
    # brute-force nearest against the full grid
    rng = np.random.default_rng(1)
    x = rng.uniform(-6, 6, size=(4096,)).astype(np.float32)
    bias = 0
    grid = floatsd.floatsd8_value_grid(bias)
    full = np.concatenate([-grid[::-1], grid])
    q = np.asarray(floatsd.quantize(jnp.asarray(x), bias=bias).values)
    dist_q = np.abs(x - q)
    dist_best = np.min(np.abs(x[:, None] - full[None, :]), axis=1)
    np.testing.assert_allclose(dist_q, dist_best, rtol=1e-6, atol=1e-7)


def test_hole_in_grid_handled():
    # 3.0 is exactly representable as 1.5 * 2^1 even though the bias-0
    # mantissa grid jumps 2.5 -> 3.5
    q = floatsd.quantize(jnp.asarray([3.0, -3.0]), bias=0).values
    np.testing.assert_array_equal(np.asarray(q), [3.0, -3.0])


def test_auto_bias_covers_tensor():
    rng = np.random.default_rng(2)
    for scale in (1e-3, 1.0, 37.0):
        x = jnp.asarray(rng.normal(scale=scale, size=(1024,)).astype(np.float32))
        q, bias = floatsd.quantize(x)
        amax = float(jnp.max(jnp.abs(x)))
        # top of range covers max|x| and is tight (within one exponent step)
        top = 4.5 * 2.0 ** (7 + int(bias))
        assert top >= amax * 0.999
        assert top <= amax * 2 * 1.001
        # relative error bounded: worst-case mantissa gap is 1.0 around 3.0
        rel = np.abs(np.asarray(q) - np.asarray(x)) / (np.abs(np.asarray(x)) + 1e-30)
        big = np.abs(np.asarray(x)) > 2.0 ** (int(bias) + 2)
        assert rel[big].max() < 0.25


def test_ste_gradient_is_identity():
    x = jnp.asarray([0.3, -1.7, 2.2], jnp.float32)
    g = jax.grad(lambda v: jnp.sum(floatsd.quantize_ste(v, jnp.int32(-3)) * 3.0))(x)
    np.testing.assert_allclose(np.asarray(g), 3.0)


def test_zero_and_saturation():
    q = floatsd.quantize(jnp.asarray([0.0, 1e9, -1e9]), bias=0).values
    np.testing.assert_array_equal(np.asarray(q), [0.0, 576.0, -576.0])


@settings(max_examples=200, deadline=None)
@given(
    st.lists(
        st.floats(-1e4, 1e4, allow_nan=False, width=32), min_size=1, max_size=64
    ),
    st.integers(-12, 4),
)
def test_property_quantization_invariants(xs, bias):
    x = jnp.asarray(np.asarray(xs, np.float32))
    q = np.asarray(floatsd.quantize(x, bias=bias).values)
    grid = floatsd.floatsd8_value_grid(bias)
    full = np.concatenate([-grid[::-1], grid])
    # 1) idempotent  2) sign-preserving  3) output on the representable grid
    q2 = np.asarray(floatsd.quantize(jnp.asarray(q), bias=bias).values)
    np.testing.assert_array_equal(q, q2)
    assert np.all(np.sign(q) * np.sign(np.asarray(x)) >= 0)
    for v in q:
        assert np.min(np.abs(full - v)) < 1e-6 * max(1.0, abs(v))


@settings(max_examples=100, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_property_encode_decode_consistent(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(scale=rng.uniform(0.01, 10), size=(64,)), jnp.float32)
    codes, bias = floatsd.encode(x)
    np.testing.assert_array_equal(
        np.asarray(floatsd.decode(codes, bias)),
        np.asarray(floatsd.quantize(x, bias=bias).values),
    )


def test_partial_product_count_le_2():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    codes, _ = floatsd.encode(x)
    pp = np.asarray(floatsd.partial_product_count(codes))
    assert pp.max() <= 2
    assert pp.min() >= 0


# ---------------------------------------------------------------------------
# The table-gather forms quantize / encode / decode had before they became
# select chains and closed forms: kept here as the bit-exact oracle.
# ---------------------------------------------------------------------------


def _count_idx_table(mids, n):
    return jnp.sum((n[..., None] > mids[(None,) * n.ndim]).astype(jnp.int32), axis=-1)


def _table_slot(x, bias):
    bias = floatsd._clamp_bias(bias)
    xf = x.astype(jnp.float32)
    scale = floatsd.exp2i(bias)
    n = jnp.clip(jnp.abs(xf) / scale, 0.0, floatsd._GRID_POS[-1])
    return xf, scale, _count_idx_table(jnp.asarray(floatsd._GRID_MID, jnp.float32), n)


def _quantize_table(x, bias):
    xf, scale, idx = _table_slot(x, bias)
    q = jnp.asarray(floatsd._GRID_POS, jnp.float32)[idx] * scale
    return (jnp.sign(xf) * q).astype(x.dtype)


def _encode_table(x, bias):
    xf, _, gidx = _table_slot(x, bias)
    e = jnp.asarray(floatsd._GRID_E, jnp.int32)[gidx]
    midx = jnp.asarray(floatsd._GRID_MIDX, jnp.int32)[gidx]
    midx_signed = jnp.where(xf < 0, 30 - midx, midx)
    return ((e << 5) | midx_signed).astype(jnp.uint8)


def _decode_table(codes, bias):
    c = codes.astype(jnp.int32)
    m = jnp.asarray(floatsd.MANTISSA_VALUES)[jnp.clip(c & 0x1F, 0, 30)]
    return m * floatsd.exp2i((c >> 5) + floatsd._clamp_bias(bias))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _assert_format_matches_table(x, bias):
    """quantize, encode and decode against the table forms, bit for bit,
    at ``bias`` (None: the fitted bias)."""
    x = jnp.asarray(x, jnp.float32)
    q, qb = floatsd.quantize(x, bias)
    codes, eb = floatsd.encode(x, bias)
    assert int(qb) == int(eb)
    np.testing.assert_array_equal(_bits(q), _bits(_quantize_table(x, qb)))
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(_encode_table(x, qb)))
    back = floatsd.decode(codes, eb)
    np.testing.assert_array_equal(_bits(back), _bits(_decode_table(codes, eb)))
    # the serving invariant, by value: a negative x that rounds to zero
    # quantizes to -0.0 and decodes to +0.0
    finite = np.isfinite(np.asarray(x))
    np.testing.assert_array_equal(np.asarray(back)[finite], np.asarray(q)[finite])


_BIASES = [None, -140, -30, -9, -3, 0, 4, 120, 200]
_SCALES = [1e-3, 0.7, 40.0]


@pytest.mark.parametrize("bias", _BIASES)
@pytest.mark.parametrize("scale", _SCALES)
def test_format_matches_table_forms_random(bias, scale):
    rng = np.random.default_rng([_BIASES.index(bias), _SCALES.index(scale)])
    x = rng.normal(scale=scale, size=(48, 40)).astype(np.float32)
    # plus every grid value and midpoint at the fitted bias, and their
    # neighbours, where rounding ties and the 2.5 -> 3.5 hole sit
    b = int(floatsd.fit_bias(jnp.asarray(x))) if bias is None else bias
    s = 2.0 ** int(floatsd._clamp_bias(b))
    with np.errstate(over="ignore"):  # the top of the grid at bias 120 is inf
        pts = (np.concatenate([floatsd._GRID_POS, floatsd._GRID_MID]) * s).astype(np.float32)
    pts = np.concatenate([pts, np.nextafter(pts, np.inf), np.nextafter(pts, -np.inf)])
    _assert_format_matches_table(np.concatenate([x.ravel(), pts, -pts]), bias)


@pytest.mark.parametrize("bias", [None, 0, -7])
def test_format_matches_table_forms_special_values(bias):
    x = np.array([0.0, -0.0, np.inf, -np.inf, 1e30, -1e30, np.nan, 1.5, -2.0],
                 np.float32)
    _assert_format_matches_table(x, bias)
    codes, b = floatsd.encode(jnp.asarray(x), bias)
    codes = np.asarray(codes)
    top = floatsd._GRID_POS[-1] * 2.0 ** int(b)
    back = np.asarray(floatsd.decode(codes, b))
    # +-inf saturate to the top of the grid; NaN takes the code of 0.0
    np.testing.assert_array_equal(back[2:4], [top, -top])
    assert codes[6] == codes[0] and back[6] == 0.0
    q = np.asarray(floatsd.quantize(jnp.asarray(x), bias).values)
    np.testing.assert_array_equal(q[2:4], [top, -top])
    assert np.isnan(q[6])


@pytest.mark.parametrize("amax", [1e-37, 4e-37, 2e-36])
def test_format_matches_table_forms_near_subnormal_floor(amax):
    rng = np.random.default_rng(7)
    x = (rng.uniform(-1, 1, size=(16, 24)) * amax).astype(np.float32)
    x[0, 0] = amax
    # the fitted bias would reach below -126 here (-132 at 1e-37); it is
    # clamped, and the tensor's smallest values round to zero
    assert int(floatsd.fit_bias(jnp.asarray(x))) == -126
    _assert_format_matches_table(x, None)


@pytest.mark.parametrize("bias", [-140, -126, -20, 0, 7, 120, 300])
def test_decode_all_codes_matches_table(bias):
    codes = jnp.arange(256, dtype=jnp.int32).astype(jnp.uint8).reshape(16, 16)
    got = floatsd.decode(codes, bias)
    np.testing.assert_array_equal(_bits(got), _bits(_decode_table(codes, bias)))


# ---------------------------------------------------------------------------
# Regression guard: on TPU, XLA runs a gather from a small constant table
# element by element, which made the table forms most of a training step.
# ---------------------------------------------------------------------------

_X = jax.ShapeDtypeStruct((24, 40), jnp.float32)


def _telemetry(w):
    from repro.obs import telemetry

    return telemetry.floatsd_update_stats({"w": w, "b": w[0]}, {"w": w * 1.01, "b": w[0]})


_GATHER_FREE = {
    "quantize": (lambda w: floatsd.quantize(w).values, _X),
    "encode": (lambda w: floatsd.encode(w)[0], _X),
    "decode": (lambda c: floatsd.decode(c, jnp.int32(-3)),
               jax.ShapeDtypeStruct((24, 40), jnp.uint8)),
    "quantize_ste_grad": (jax.grad(lambda w: jnp.sum(
        floatsd.quantize_ste(w, floatsd.fit_bias(w)) ** 2)), _X),
    "telemetry.floatsd_update_stats": (_telemetry, _X),
}


def _table_tell(fn, arg) -> list:
    """What of the table form a lowering shows: gathers and [..., 64]
    intermediates (the broadcast compare against the 64 grid midpoints)."""
    import re

    text = jax.jit(fn).lower(arg).as_text()
    return re.findall(r"\bgather\b", text) + re.findall(r"\b64x[a-z]+\d*>", text)


@pytest.mark.parametrize("name", sorted(_GATHER_FREE))
def test_format_lowers_without_table_gather(name):
    assert _table_tell(*_GATHER_FREE[name]) == []


def test_table_tell_sees_the_table_forms():
    assert _table_tell(lambda w: _quantize_table(w, jnp.int32(0)), _X)
