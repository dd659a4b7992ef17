"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp ref.py oracle.

Every kernel is swept over shapes x dtypes and asserted allclose against its
oracle, per the deliverable spec. Property tests (hypothesis) cover the
tiling-independence invariant: block shape must never change the result.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import floatsd
from repro.kernels.floatsd_matmul.kernel import floatsd_matmul_pallas
from repro.kernels.floatsd_matmul.ops import floatsd_matmul
from repro.kernels.floatsd_matmul.ref import floatsd_matmul_ref
from repro.kernels.floatsd_quantize.kernel import quantize_pallas
from repro.kernels.floatsd_quantize.ops import floatsd_quantize
from repro.kernels.floatsd_quantize.ref import quantize_ref
from repro.kernels.lstm_cell.kernel import lstm_cell_pallas
from repro.kernels.lstm_cell.ops import lstm_cell
from repro.kernels.lstm_cell.ref import lstm_cell_ref

def _w(shape, scale=1.0, dtype=np.float32):
    # order-independent: seed from the call signature, not shared state
    seed = (hash((shape, float(scale))) & 0x7FFFFFFF) or 1
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# floatsd_quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 256), (256, 256), (64, 512), (2, 1024)])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 37.5])
def test_quantize_kernel_matches_oracle(shape, scale):
    x = jnp.asarray(_w(shape, scale))
    bias = floatsd.fit_bias(x)
    got = quantize_pallas(x, bias, bm=min(256, shape[0]), bn=256, interpret=True)
    want = quantize_ref(x, bias)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_quantize_kernel_dtypes(dtype):
    x = jnp.asarray(_w((64, 256))).astype(dtype)
    bias = floatsd.fit_bias(x)
    got = quantize_pallas(x, bias, bm=64, bn=256, interpret=True)
    want = quantize_ref(x, bias)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "shape", [(3,), (5, 7), (4, 64), (2, 3, 256), (1024,), (16, 16, 16)]
)
def test_quantize_wrapper_any_shape(shape):
    """ops.floatsd_quantize handles arbitrary shapes (kernel or fallback) and
    decode(quantize(x)) == quantize(x).values exactly."""
    x = jnp.asarray(_w(shape))
    codes, bias = floatsd_quantize(x, interpret=True)
    assert codes.shape == x.shape and codes.dtype == jnp.uint8
    dec = floatsd.decode(codes, bias)
    want = floatsd.quantize(x, bias).values
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want), rtol=0, atol=0)


@settings(max_examples=30, deadline=None)
@given(
    bm=st.sampled_from([8, 16, 32, 64, 128, 256]),
    bn=st.sampled_from([256]),
    scale=st.floats(1e-4, 1e3),
)
def test_quantize_tiling_independence(bm, bn, scale):
    """Property: block shape never changes the quantization result."""
    x = jnp.asarray(_w((256, 256), scale))
    bias = floatsd.fit_bias(x)
    a = quantize_pallas(x, bias, bm=bm, bn=bn, interpret=True)
    b = quantize_ref(x, bias)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# floatsd_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,n", [(8, 128, 128), (32, 256, 256), (128, 512, 256), (256, 1024, 512)]
)
def test_matmul_kernel_matches_oracle(m, k, n):
    x = jnp.asarray(_w((m, k), 0.5))
    wts = jnp.asarray(_w((k, n), 0.05))
    codes, bias = floatsd.encode(wts)
    got = floatsd_matmul(x, codes, bias, interpret=True)
    want = floatsd_matmul_ref(x, codes, bias)
    # kernel computes in bf16 (MXU issue dtype), oracle in f32: bf16 has 8
    # mantissa bits -> rtol ~ 2^-7 per element, K-sum in f32 keeps it tight
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16, jnp.float8_e5m2])
def test_matmul_kernel_activation_dtypes(xdtype):
    """The paper's MAC takes FP8 activations; bf16/f32 also supported."""
    x = jnp.asarray(_w((32, 256), 0.5)).astype(xdtype)
    wts = jnp.asarray(_w((256, 128), 0.05))
    codes, bias = floatsd.encode(wts)
    got = floatsd_matmul(x, codes, bias, interpret=True)
    want = floatsd_matmul_ref(x, codes, bias)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.float16, jnp.bfloat16])
def test_matmul_kernel_out_dtypes(out_dtype):
    x = jnp.asarray(_w((16, 128), 0.5))
    wts = jnp.asarray(_w((128, 128), 0.05))
    codes, bias = floatsd.encode(wts)
    got = floatsd_matmul(x, codes, bias, out_dtype=out_dtype, interpret=True)
    assert got.dtype == out_dtype
    want = floatsd_matmul_ref(x, codes, bias, out_dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


@settings(max_examples=20, deadline=None)
@given(
    bm=st.sampled_from([8, 16, 64, 128]),
    bn=st.sampled_from([128, 256]),
    bk=st.sampled_from([128, 256, 512]),
)
def test_matmul_tiling_independence(bm, bn, bk):
    """Property: (bm, bn, bk) tiling never changes the accumulated result
    beyond bf16 rounding of the decoded weight tile (which is tile-invariant
    because decode is element-wise)."""
    x = jnp.asarray(_w((128, 512), 0.5))
    wts = jnp.asarray(_w((512, 256), 0.05))
    codes, bias = floatsd.encode(wts)
    got = floatsd_matmul_pallas(x, codes, bias, bm=bm, bn=bn, bk=bk, interpret=True)
    ref = floatsd_matmul_pallas(
        x, codes, bias, bm=128, bn=256, bk=512, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_matmul_fallback_indivisible_shapes():
    x = jnp.asarray(_w((7, 130), 0.5))
    wts = jnp.asarray(_w((130, 66), 0.05))
    codes, bias = floatsd.encode(wts)
    got = floatsd_matmul(x, codes, bias, interpret=True)
    want = floatsd_matmul_ref(x, codes, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# lstm_cell (fused element-wise neuron stage)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h", [(8, 128), (32, 256), (128, 512), (16, 1024)])
@pytest.mark.parametrize("quantized", [True, False])
def test_lstm_cell_kernel_matches_oracle(b, h, quantized):
    z = jnp.asarray(_w((b, 4 * h), 1.5))
    c = jnp.asarray(_w((b, h), 0.8))
    h_got, c_got = lstm_cell(z, c, quantized=quantized, interpret=True)
    h_want, c_want = lstm_cell_ref(z, c, quantized)
    assert c_got.dtype == jnp.float16  # paper: FP16 cell state
    # h tolerance: one FP16 rounding of c feeding tanh can differ by half an
    # ulp between the fused and unfused compute orders -> rel ~6e-4
    np.testing.assert_allclose(
        np.asarray(h_got), np.asarray(h_want), rtol=1e-3, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(c_got, np.float32), np.asarray(c_want, np.float32),
        rtol=1e-3, atol=1e-4,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell_kernel_dtypes(dtype):
    z = jnp.asarray(_w((8, 4 * 128), 1.5)).astype(dtype)
    c = jnp.asarray(_w((8, 128), 0.8)).astype(dtype)
    h_got, c_got = lstm_cell(z, c, quantized=True, interpret=True)
    h_want, c_want = lstm_cell_ref(z, c, True)
    assert h_got.dtype == dtype
    got = np.asarray(h_got, np.float32)
    want = np.asarray(h_want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    else:
        # bf16: the kernel computes sigma in f32 while the oracle's sigma is
        # bf16-rounded — inputs that straddle a quantizer midpoint flip by
        # one FloatSD8 grid step. Require: <5% boundary flips, each within
        # one grid step (~0.094 around sigma ~ 0.3), everything else tight.
        diff = np.abs(got - want)
        bad = diff > 2e-2 + 2e-2 * np.abs(want)
        assert bad.mean() < 0.05, bad.mean()
        assert diff.max() <= 0.13, diff.max()  # max FloatSD8 grid gap in (0,1)


@settings(max_examples=15, deadline=None)
@given(
    bb=st.sampled_from([8, 16, 32]),
    bh=st.sampled_from([128, 256]),
)
def test_lstm_cell_tiling_independence(bb, bh):
    z = jnp.asarray(_w((32, 4 * 256), 1.5))
    c = jnp.asarray(_w((32, 256), 0.8))
    h_got, c_got = lstm_cell_pallas(z, c, bb=bb, bh=bh, quantized=True, interpret=True)
    h_want, c_want = lstm_cell_ref(z, c, True)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(c_got, np.float32), np.asarray(c_want, np.float32), rtol=1e-3, atol=1e-4
    )


def test_lstm_cell_fallback_indivisible():
    z = jnp.asarray(_w((5, 4 * 70), 1.5))
    c = jnp.asarray(_w((5, 70), 0.8))
    h_got, c_got = lstm_cell(z, c, quantized=True, interpret=True)
    h_want, c_want = lstm_cell_ref(z, c, True)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want), rtol=1e-6)


def test_lstm_cell_gate_saturation():
    """Saturated gates: f=1,i=0 must preserve c exactly (memory retention),
    f=0,i=1 must overwrite with g — the LSTM invariant the paper's FP16 cell
    state must not break."""
    b, h = 8, 128
    big = 30.0
    c = jnp.asarray(_w((b, h), 0.4))
    # z layout: [i | f | g | o]
    z_keep = jnp.concatenate(
        [jnp.full((b, h), -big), jnp.full((b, h), big),
         jnp.zeros((b, h)), jnp.full((b, h), big)], axis=-1
    ).astype(jnp.float32)
    _, c_keep = lstm_cell(z_keep, c, quantized=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(c_keep, np.float32), np.asarray(c, np.float32),
        rtol=1e-3, atol=1e-3,  # one FP16 round of c
    )
    g_val = 0.75
    zg = jnp.arctanh(jnp.asarray(g_val, jnp.float32))
    z_over = jnp.concatenate(
        [jnp.full((b, h), big), jnp.full((b, h), -big),
         jnp.full((b, h), zg), jnp.full((b, h), big)], axis=-1
    ).astype(jnp.float32)
    _, c_over = lstm_cell(z_over, c, quantized=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(c_over, np.float32), g_val, rtol=3e-2, atol=1e-2  # FP8 tanh LUT
    )


# ---------------------------------------------------------------------------
# cross-kernel integration: quantize -> matmul == fake-quant dense
# ---------------------------------------------------------------------------


def test_quantize_then_matmul_equals_fakequant_dense():
    x = jnp.asarray(_w((32, 256), 0.5))
    wts = jnp.asarray(_w((256, 128), 0.05))
    codes, bias = floatsd_quantize(wts, interpret=True)
    y_kernel = floatsd_matmul(x, codes, bias, interpret=True)
    wq = floatsd.quantize(wts).values
    y_fake = x @ wq
    np.testing.assert_allclose(
        np.asarray(y_kernel), np.asarray(y_fake), rtol=2e-2, atol=2e-2
    )


# ---------------------------------------------------------------------------
# the Mosaic-lowerable building blocks are exact against what they replace
# (table gathers and f16 casts, which the TPU kernel compiler refuses; cast
# pairs, which XLA may drop on a TPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", [jnp.float16, jnp.float8_e5m2, jnp.float8_e4m3fn]
)
def test_round_to_matches_cast(dtype):
    """The integer-op rounding the oracles and the f16 cell kernel use is
    bit-identical to a cast there and back, on a sweep of f32 patterns."""
    from repro.core.fp8 import round_to

    bits = np.arange(0, 2**32, 4099, dtype=np.uint64).astype(np.uint32)
    special = np.array(
        [0.0, -0.0, np.inf, -np.inf, np.nan, 65504.0, 65519.99, 65520.0,
         57344.0, 61440.0, 448.0, 463.9, 464.0, 2.0**-14, 2.0**-24,
         2.0**-25, 2.0**-16, 2.0**-9, 3 * 2.0**-11, -(2.0**-15) * 1.5],
        np.float32,
    )
    x = jnp.asarray(np.concatenate([bits.view(np.float32), special]))
    got = np.asarray(jax.jit(lambda v: round_to(v, dtype))(x))
    want = np.asarray(x.astype(dtype).astype(jnp.float32))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


@pytest.mark.parametrize(
    "dtype", [jnp.float16, jnp.float8_e5m2, jnp.float8_e4m3fn]
)
def test_round_to_gradient_matches_cast(dtype):
    """Autodiff through ``round_to`` rounds the cotangent as the transpose
    of the cast pair does (the inline LSTM cell and the FP8 fake-quant are
    differentiated through it)."""
    from repro.core.fp8 import round_to

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    w = jnp.asarray(rng.standard_normal(4096) * 3.0, jnp.float32)
    got = jax.grad(lambda v: jnp.sum(round_to(v, dtype) * w))(x)
    want = jax.grad(
        lambda v: jnp.sum(v.astype(dtype).astype(jnp.float32) * w))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bias", [-126, -9, 0, 120])
def test_decode_tile_matches_floatsd_decode(bias):
    from repro.kernels.floatsd_matmul.kernel import decode_tile

    codes = jnp.arange(256, dtype=jnp.uint8).reshape(16, 16)
    got = np.asarray(decode_tile(codes, jnp.int32(bias)))
    want = np.asarray(floatsd.decode(codes, bias))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_mantissa4_matches_lut16():
    from repro.core import floatsd4
    from repro.kernels.floatsd4_matmul.kernel import _mantissa4

    got = np.asarray(_mantissa4(jnp.arange(16, dtype=jnp.int32)))
    np.testing.assert_array_equal(got, floatsd4.LUT16)


def test_select_lut_matches_compare_count():
    from repro.kernels import mosaic
    from repro.kernels.floatsd_quantize.kernel import _CODES, _MID

    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.uniform(-1.0, float(_MID[-1]) * 1.1, 4096), _MID,
        np.nextafter(_MID, np.inf), [np.nan],
    ]).astype(np.float32)
    got = np.asarray(mosaic.select_lut(jnp.asarray(x), _MID, _CODES))
    want = _CODES[np.sum(x[:, None] > _MID[None, :], axis=-1)]
    np.testing.assert_array_equal(got, want)
