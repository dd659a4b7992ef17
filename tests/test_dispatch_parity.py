"""Golden-parity harness for the kernel dispatch layer.

For EVERY op in ``kernels.dispatch.REGISTRY``: pallas(interpret) vs the jnp
oracle across a shape grid that includes non-tile-divisible (padded) shapes,
plus assertions on the dispatch decisions themselves — which backend ran,
whether padding kicked in, and that fallbacks are recorded, never silent.

The grid runs without hypothesis (the property sweeps live in
tests/test_dispatch_properties.py behind the importorskip guard) so parity
stays in the < 2 min smoke tier.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import floatsd, floatsd4
from repro.kernels import dispatch as kd
from repro.kernels.floatsd_matmul.ops import floatsd_matmul


def _w(shape, scale=1.0, dtype=np.float32, seed_extra=0):
    seed = (hash((shape, float(scale), seed_extra)) & 0x7FFFFFFF) or 1
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# the per-op parity grids; completeness against the registry is asserted so
# a newly registered op without a grid fails loudly
# ---------------------------------------------------------------------------

MATMUL_SHAPES = [
    (8, 128, 128),   # native tiles
    (32, 256, 256),  # native tiles
    (7, 130, 66),    # all three axes padded
    (1, 32, 48),     # tiny, heavily padded
    (30, 100, 200),  # mixed
]

# FloatSD4 grid mirrors MATMUL_SHAPES but forces odd K twice: K=101 / 127
# exercise the nibble pad (one ZERO_CODE row -> 0x77 pad byte) AND a
# non-multiple-of-GROUP row count for the group exponents
MATMUL4_SHAPES = [
    (8, 128, 128),   # native tiles, K % 2 == 0, K % GROUP == 0
    (32, 256, 256),  # native tiles
    (7, 130, 66),    # all three axes padded, K even but K % GROUP != 0
    (1, 32, 48),     # tiny, heavily padded
    (30, 101, 200),  # odd K: packed stream carries a half-empty last byte
    (5, 127, 96),    # odd K and last group only 31 rows deep
]

LSTM_SHAPES = [(8, 128), (32, 256), (5, 70), (3, 200)]

ELEMWISE_SHAPES = [(8, 256), (7, 33), (1000,), (2, 3, 7), (64, 512)]

# (bh, s, dk, dv); last shape is chunk-indivisible -> recorded ref fallback
WKV_SHAPES = [(2, 32, 8, 16), (1, 48, 16, 16), (2, 30, 8, 8)]

# (bh, sq, skv, d); last shape is misaligned -> recorded ref fallback
FLASH_SHAPES = [(2, 16, 128, 8), (1, 32, 256, 16), (2, 10, 100, 8)]

GRIDS = {
    "floatsd_matmul": MATMUL_SHAPES,
    "floatsd4_matmul": MATMUL4_SHAPES,
    "lstm_cell": LSTM_SHAPES,
    "floatsd_quantize": ELEMWISE_SHAPES,
    "qsigmoid": ELEMWISE_SHAPES,
    # backward op pairs (the fused-BPTT training path)
    "floatsd_matmul_dx": MATMUL_SHAPES,
    "floatsd_matmul_dw": MATMUL_SHAPES,
    "lstm_cell_grad": LSTM_SHAPES,
    # fallback-only dispatch (no padding path): pallas iff tiles divide
    "rwkv_wkv": WKV_SHAPES,
    "flash_attention": FLASH_SHAPES,
}


def test_every_registered_op_has_a_parity_grid():
    assert set(GRIDS) == set(kd.REGISTRY), (
        "every op registered in kernels.dispatch must have a parity grid here"
    )


def _expect_padded(m, k, n):
    return bool(m % 8 or k % 128 or n % 128)


@pytest.mark.parametrize("m,k,n", MATMUL_SHAPES)
def test_matmul_parity_and_decision(m, k, n):
    x = jnp.asarray(_w((m, k), 0.5))
    wts = jnp.asarray(_w((k, n), 0.05))
    codes, bias = floatsd.encode(wts)
    with kd.use_backend("pallas"):
        got = kd.matmul(x, codes, bias)
        dec = kd.STATS.last["floatsd_matmul"]
    want = kd.matmul(x, codes, bias, backend="ref")
    assert dec.backend == "pallas"
    assert dec.padded == _expect_padded(m, k, n), dec
    # precise (f32-issue) kernel: <= 1e-5 deviation across the grid
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


def test_matmul_batched_leading_dims():
    """dispatch.matmul flattens [..., K] leading dims like the weight sites."""
    x = jnp.asarray(_w((2, 3, 130), 0.5))
    wts = jnp.asarray(_w((130, 66), 0.05))
    codes, bias = floatsd.encode(wts)
    with kd.use_backend("pallas"):
        got = kd.matmul(x, codes, bias)
    want = kd.matmul(x, codes, bias, backend="ref")
    assert got.shape == (2, 3, 66)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("b,h", LSTM_SHAPES)
@pytest.mark.parametrize("quantized", [True, False])
def test_lstm_cell_parity_and_decision(b, h, quantized):
    z = jnp.asarray(_w((b, 4 * h), 1.5))
    c = jnp.asarray(_w((b, h), 0.8))
    with kd.use_backend("pallas"):
        h_got, c_got = kd.lstm_cell(z, c, quantized=quantized)
        dec = kd.STATS.last["lstm_cell"]
    h_want, c_want = kd.lstm_cell(z, c, quantized=quantized, backend="ref")
    assert dec.backend == "pallas"
    assert dec.padded == bool(b % 8 or h % 128), dec
    assert c_got.dtype == jnp.float16 and c_want.dtype == jnp.float16
    np.testing.assert_allclose(
        np.asarray(h_got), np.asarray(h_want), rtol=1e-3, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(c_got, np.float32), np.asarray(c_want, np.float32),
        rtol=1e-3, atol=1e-4,
    )


@pytest.mark.parametrize("m,k,n", MATMUL_SHAPES)
def test_matmul_dx_parity_and_decision(m, k, n):
    """Backward dx op: g [M,N] x decode(codes [K,N])^T, f32 precise path."""
    g = jnp.asarray(_w((m, n), 0.5))
    wts = jnp.asarray(_w((k, n), 0.05))
    codes, bias = floatsd.encode(wts)
    with kd.use_backend("pallas"):
        got = kd.matmul_dx(g, codes, bias)
        dec = kd.STATS.last["floatsd_matmul_dx"]
    want = kd.matmul_dx(g, codes, bias, backend="ref")
    assert dec.backend == "pallas"
    assert dec.padded == _expect_padded(m, n, k), dec
    assert got.shape == (m, k) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("m,k,n", MATMUL_SHAPES)
def test_matmul_dw_parity_and_decision(m, k, n):
    """Backward dw op: x^T g with the FP8 grid snap applied in-kernel —
    outputs must land EXACTLY on the fp8-e5m2 grid on both backends."""
    x = jnp.asarray(_w((m, k), 0.5))
    g = jnp.asarray(_w((m, n), 0.5))
    with kd.use_backend("pallas"):
        got = kd.matmul_dw(x, g)
        dec = kd.STATS.last["floatsd_matmul_dw"]
    want = kd.matmul_dw(x, g, backend="ref")
    assert dec.backend == "pallas"
    assert dec.padded == bool(k % 8 or m % 128 or n % 128), dec
    assert got.shape == (k, n)
    # the in-kernel quantizer really ran: every value is fp8-representable
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(got.astype(jnp.float8_e5m2), np.float32)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,h", LSTM_SHAPES)
@pytest.mark.parametrize("quantized", [True, False])
@pytest.mark.parametrize("c_dtype", [jnp.float32, jnp.float16])
def test_lstm_cell_grad_parity_and_decision(b, h, quantized, c_dtype):
    """Recompute-gates backward: pallas(interpret) vs the jnp oracle.

    f32 cell state: tight (pure f32 elementwise chain). f16 cell state:
    f16-rounding envelope — the recomputed c_t can land one f16 ulp apart
    between lowerings (fma/fusion), which the tanh path amplifies to ~1e-3
    relative (same envelope as the forward cell parity above).
    """
    z = jnp.asarray(_w((b, 4 * h), 1.5))
    c = jnp.asarray(_w((b, h), 0.8)).astype(c_dtype)
    dh = jnp.asarray(_w((b, h), 1.0, seed_extra=1))
    dc = jnp.asarray(_w((b, h), 1.0, seed_extra=2)).astype(c_dtype)
    with kd.use_backend("pallas"):
        dz_got, dcp_got = kd.lstm_cell_grad(
            z, c, dh, dc, quantized=quantized, c_dtype=c_dtype
        )
        dec = kd.STATS.last["lstm_cell_grad"]
    dz_want, dcp_want = kd.lstm_cell_grad(
        z, c, dh, dc, quantized=quantized, c_dtype=c_dtype, backend="ref"
    )
    assert dec.backend == "pallas"
    assert dec.padded == bool(b % 8 or h % 128), dec
    assert dz_got.shape == (b, 4 * h) and dcp_got.shape == (b, h)
    assert dcp_got.dtype == c_dtype
    tol = dict(rtol=1e-5, atol=1e-6) if c_dtype == jnp.float32 else dict(
        rtol=2e-3, atol=1e-5
    )
    np.testing.assert_allclose(np.asarray(dz_got), np.asarray(dz_want), **tol)
    np.testing.assert_allclose(
        np.asarray(dcp_got, np.float32), np.asarray(dcp_want, np.float32),
        rtol=2e-3, atol=1e-4,
    )


def test_lstm_cell_c_dtype_follows_policy():
    """fp32-master policies keep the cell state f32 through the dispatch."""
    z = jnp.asarray(_w((8, 4 * 128), 1.5))
    c = jnp.asarray(_w((8, 128), 0.8))
    for backend in ("ref", "pallas"):
        _, c_out = kd.lstm_cell(z, c, c_dtype=jnp.float32, backend=backend)
        assert c_out.dtype == jnp.float32, backend


@pytest.mark.parametrize("shape", ELEMWISE_SHAPES)
def test_quantize_parity_and_decision(shape):
    x = jnp.asarray(_w(shape, 0.7))
    with kd.use_backend("pallas"):
        codes, bias = kd.quantize(x)
        dec = kd.STATS.last["floatsd_quantize"]
    ref_codes, ref_bias = kd.quantize(x, backend="ref")
    assert dec.backend == "pallas"
    assert dec.padded == bool(x.size % (8 * 256)), dec
    assert codes.shape == x.shape and codes.dtype == jnp.uint8
    assert int(bias) == int(ref_bias)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(ref_codes))


@pytest.mark.parametrize("shape", ELEMWISE_SHAPES)
def test_qsigmoid_parity_and_decision(shape):
    x = jnp.asarray(_w(shape, 2.0))
    with kd.use_backend("pallas"):
        got = kd.qsigmoid(x)
        dec = kd.STATS.last["qsigmoid"]
    want = kd.qsigmoid(x, backend="ref")
    assert dec.backend == "pallas"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bh,s,dk,dv", WKV_SHAPES)
def test_rwkv_wkv_parity_and_decision(bh, s, dk, dv):
    """No padding path: pallas when S % chunk == 0, recorded ref fallback
    otherwise (never silent)."""
    rng = np.random.default_rng(7 + bh + s + dk)
    r = jnp.asarray(rng.standard_normal((bh, s, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, s, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, s, dv)), jnp.float32)
    w = jnp.asarray(
        np.exp(-np.exp(rng.standard_normal((bh, s, dk)) * 0.3 - 2.0)),
        jnp.float32,
    )
    u = jnp.asarray(rng.standard_normal((bh, dk)) * 0.1, jnp.float32)
    with kd.use_backend("pallas"):
        got = kd.rwkv_wkv(r, k, v, w, u, chunk=16)
        dec = kd.STATS.last["rwkv_wkv"]
    want = kd.rwkv_wkv(r, k, v, w, u, chunk=16, backend="ref")
    if s % 16 == 0:
        assert dec.backend == "pallas", dec
    else:
        assert dec.backend == "ref" and "oracle" in dec.reason, dec
    assert got.shape == (bh, s, dv)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("bh,sq,skv,d", FLASH_SHAPES)
def test_flash_attention_parity_and_decision(bh, sq, skv, d):
    """No padding path: pallas when (Sq, Skv, D) are tile-aligned, recorded
    ref fallback otherwise (never silent)."""
    rng = np.random.default_rng(11 + bh + sq + d)
    q = jnp.asarray(rng.standard_normal((bh, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, skv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, skv, d)), jnp.float32)
    with kd.use_backend("pallas"):
        got = kd.flash_attention(q, k, v, causal=False)
        dec = kd.STATS.last["flash_attention"]
    want = kd.flash_attention(q, k, v, causal=False, backend="ref")
    if sq % 8 == 0 and skv % 128 == 0 and d % 8 == 0:
        assert dec.backend == "pallas", dec
    else:
        assert dec.backend == "ref" and "oracle" in dec.reason, dec
    assert got.shape == (bh, sq, d)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-2, atol=6e-3
    )


# ---------------------------------------------------------------------------
# the dispatch decision logic itself
# ---------------------------------------------------------------------------


def test_auto_resolves_to_ref_off_tpu():
    x = jnp.asarray(_w((8, 128), 0.5))
    wts = jnp.asarray(_w((128, 128), 0.05))
    codes, bias = floatsd.encode(wts)
    kd.matmul(x, codes, bias)  # default policy: auto
    dec = kd.STATS.last["floatsd_matmul"]
    assert dec.backend == "ref" and dec.reason.startswith("auto:off-tpu")


def test_backend_precedence_argument_over_context_over_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    assert kd.backend_policy() == "pallas"
    with kd.use_backend("ref"):
        assert kd.backend_policy() == "ref"
        assert kd.backend_policy("auto") == "auto"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
    with pytest.raises(ValueError):
        kd.backend_policy()


def test_auto_padding_profitability_on_tpu(monkeypatch):
    """With compiled pallas available (simulated), auto takes the kernel at
    any padding waste: the oracle never stands in for it (a decode step
    of one row pads M 1 -> 8, 8x the work, and still runs the kernel)."""
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")  # pretend compiled
    d = kd._choose("x", native=True, waste=1.0, backend="auto")
    assert d.backend == "pallas" and not d.padded
    for waste in (1.9, 8.0, 64.0):
        d = kd._choose("x", native=False, waste=waste, backend="auto")
        assert d.backend == "pallas" and d.padded, d
        assert f"{waste:.2f}x" in d.reason


def test_interpret_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(kd, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    with pytest.raises(kd.OracleRefused, match="REPRO_KERNEL_INTERPRET"):
        kd.interpret_mode()
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    assert kd.interpret_mode() is False
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET")
    assert kd.interpret_mode() is False


@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_no_padded_path_fallback_refused_on_tpu(monkeypatch, backend):
    """Off a TPU an indivisible shape falls back to the recorded oracle; on
    one it raises unless the caller asked for ref."""
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    d = kd._decide_fallback("rwkv_wkv", False, "S=5 % chunk=16", backend)
    assert d.backend == "ref" and "no padded path" in d.reason
    monkeypatch.setattr(kd, "_on_tpu", lambda: True)
    with pytest.raises(kd.OracleRefused, match="rwkv_wkv: S=5"):
        kd._decide_fallback("rwkv_wkv", False, "S=5 % chunk=16", backend)
    assert kd._decide_fallback("rwkv_wkv", False, "", "ref").backend == "ref"
    assert kd._decide_fallback("rwkv_wkv", True, "", backend).backend == "pallas"


def test_stats_counters_accumulate():
    x = jnp.asarray(_w((8, 128), 0.5))
    wts = jnp.asarray(_w((128, 128), 0.05))
    codes, bias = floatsd.encode(wts)
    before = kd.STATS.count("floatsd_matmul", "ref")
    kd.matmul(x, codes, bias, backend="ref")
    kd.matmul(x, codes, bias, backend="ref")
    assert kd.STATS.count("floatsd_matmul", "ref") == before + 2


def test_ops_wrapper_records_fallback_not_silent():
    """The legacy wrapper's oracle fallback is observable via STATS — a
    tiling regression can't quietly turn every call into jnp."""
    x = jnp.asarray(_w((7, 130), 0.5))
    wts = jnp.asarray(_w((130, 66), 0.05))
    codes, bias = floatsd.encode(wts)
    floatsd_matmul(x, codes, bias, interpret=True)
    dec = kd.STATS.last["floatsd_matmul"]
    assert dec.backend == "ref" and "fallback" in dec.reason
    x2 = jnp.asarray(_w((8, 128), 0.5))
    wts2 = jnp.asarray(_w((128, 128), 0.05))
    codes2, bias2 = floatsd.encode(wts2)
    floatsd_matmul(x2, codes2, bias2, interpret=True)
    assert kd.STATS.last["floatsd_matmul"].backend == "pallas"


# ---------------------------------------------------------------------------
# packed-weight entry points
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eq,xshape,wshape", [
    ("bd,dk->bk", (4, 80), (80, 96)),
    ("...d,df->...f", (2, 3, 80), (80, 96)),
    ("...d,vd->...v", (2, 3, 80), (96, 80)),  # tied logits head layout
])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_packed_einsum_matches_dense(eq, xshape, wshape, backend):
    x = jnp.asarray(_w(xshape, 0.5))
    w = jnp.asarray(_w(wshape, 0.05))
    pt = kd.PackedTensor(*floatsd.encode(w))
    with kd.use_backend(backend):
        got = kd.packed_einsum(eq, x, pt)
    wq = floatsd.decode(pt.codes, pt.bias)
    want = jnp.einsum(eq, x, wq, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_packed_einsum_rejects_unsupported_eq():
    w = jnp.asarray(_w((8, 8), 0.05))
    pt = kd.PackedTensor(*floatsd.encode(w))
    with pytest.raises(NotImplementedError):
        kd.packed_einsum("ab,bcd->acd", jnp.zeros((2, 8)), pt)


def test_hoist_packed_decodes_for_ref_keeps_codes_for_pallas():
    w = jnp.asarray(_w((16, 32), 0.05))
    pt = kd.PackedTensor(*floatsd.encode(w))
    with kd.use_backend("ref"):
        dense = kd.hoist_packed(pt)
    assert not kd.is_packed(dense)
    np.testing.assert_array_equal(
        np.asarray(dense), np.asarray(floatsd.decode(pt.codes, pt.bias))
    )
    with kd.use_backend("pallas"):
        assert kd.hoist_packed(pt) is pt
    # non-packed passthrough
    assert kd.hoist_packed(w) is w


def test_zero_code_pads_decode_to_exact_zero():
    codes = jnp.full((4, 4), kd.ZERO_CODE, jnp.uint8)
    for bias in (-30, 0, 25):
        np.testing.assert_array_equal(
            np.asarray(floatsd.decode(codes, bias)), 0.0
        )


# ---------------------------------------------------------------------------
# FloatSD4 sub-byte packed entry points (2 codes/byte)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", MATMUL4_SHAPES)
def test_matmul4_parity_and_decision(m, k, n):
    """Compiled-vs-ref golden parity for the nibble-packed GEMM, mirroring
    the FloatSD8 grid: the kernel's in-VMEM LUT unpack + group-exponent
    scale must match the decode-then-dot oracle on padded and odd-K
    shapes alike."""
    x = jnp.asarray(_w((m, k), 0.5))
    wts = jnp.asarray(_w((k, n), 0.05))
    w4 = kd.pack4(wts)
    with kd.use_backend("pallas"):
        got = kd.matmul4(x, w4)
        dec = kd.STATS.last["floatsd4_matmul"]
    want = kd.matmul4(x, w4, backend="ref")
    assert dec.backend == "pallas"
    assert dec.padded == _expect_padded(m, k, n), dec
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


def test_matmul4_batched_leading_dims():
    x = jnp.asarray(_w((2, 3, 101), 0.5))
    wts = jnp.asarray(_w((101, 66), 0.05))
    w4 = kd.pack4(wts)
    with kd.use_backend("pallas"):
        got = kd.matmul4(x, w4)
    want = kd.matmul4(x, w4, backend="ref")
    assert got.shape == (2, 3, 66)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_matmul4_ref_is_decode_then_dot():
    """The ref oracle is literally decode_packed + jnp.dot — anchor the
    dispatched ref branch to the layer-0 definition."""
    x = jnp.asarray(_w((6, 70), 0.5))
    wts = jnp.asarray(_w((70, 40), 0.05))
    w4 = kd.pack4(wts)
    got = kd.matmul4(x, w4, backend="ref")
    wq = floatsd4.decode_packed(w4.codes, w4.exps, w4.k)
    want = jnp.dot(x, wq, preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matmul4_stats_counters_accumulate():
    x = jnp.asarray(_w((8, 128), 0.5))
    w4 = kd.pack4(jnp.asarray(_w((128, 128), 0.05)))
    before = kd.STATS.count("floatsd4_matmul", "ref")
    kd.matmul4(x, w4, backend="ref")
    kd.matmul4(x, w4, backend="ref")
    assert kd.STATS.count("floatsd4_matmul", "ref") == before + 2


@pytest.mark.parametrize("eq,xshape,wshape", [
    ("bd,dk->bk", (4, 80), (80, 96)),
    ("...d,df->...f", (2, 3, 80), (80, 96)),
    ("...d,vd->...v", (2, 3, 80), (96, 80)),  # tied logits head layout
    ("...d,vd->...v", (2, 3, 81), (95, 81)),  # odd dims: nbyte asymmetry
])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_packed4_einsum_matches_dense(eq, xshape, wshape, backend):
    x = jnp.asarray(_w(xshape, 0.5))
    w = jnp.asarray(_w(wshape, 0.05))
    p4 = kd.pack4(w)
    with kd.use_backend(backend):
        got = kd.packed_einsum(eq, x, p4)
        dec = kd.STATS.last["floatsd4_matmul"]
    assert "packed4" in dec.reason or dec.backend == "pallas", dec
    wq = floatsd4.decode_packed(p4.codes, p4.exps, p4.k)
    want = jnp.einsum(eq, x, wq, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_packed4_einsum_transpose_fallback_is_recorded():
    """The tied-head layout (packed along the free axis) no longer falls
    back to decode+einsum: the kernel takes it as stored, padded, and the
    Decision is recorded like any other."""
    x = jnp.asarray(_w((2, 80), 0.5))
    p4 = kd.pack4(jnp.asarray(_w((96, 80), 0.05)))
    with kd.use_backend("pallas"):
        got = kd.packed_einsum("...d,vd->...v", x, p4)
        dec = kd.STATS.last["floatsd4_matmul"]
    assert dec.backend == "pallas" and dec.padded, dec
    want = kd.matmul4(x, p4, transpose=True, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("m,n,k", [(8, 256, 128), (3, 95, 81), (16, 33, 200)])
def test_matmul4_transpose_parity(m, n, k):
    """x @ decode4(w [N, K] packed along N)^T: kernel (interpreted) vs the
    oracle, native and padded."""
    x = jnp.asarray(_w((m, k), 0.5))
    p4 = kd.pack4(jnp.asarray(_w((n, k), 0.05)))
    got = kd.matmul4(x, p4, transpose=True, backend="pallas")
    want = kd.matmul4(x, p4, transpose=True, backend="ref")
    dense = floatsd4.decode_packed(p4.codes, p4.exps, p4.k)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(x @ dense.T), rtol=1e-5, atol=1e-6)


def test_hoist_packed4_decodes_for_ref_keeps_codes_for_pallas():
    w = jnp.asarray(_w((33, 32), 0.05))  # odd K: crop must survive hoist
    p4 = kd.pack4(w)
    with kd.use_backend("ref"):
        dense = kd.hoist_packed(p4)
    assert not kd.is_packed4(dense)
    assert dense.shape == (33, 32)
    np.testing.assert_array_equal(
        np.asarray(dense),
        np.asarray(floatsd4.decode_packed(p4.codes, p4.exps, p4.k)),
    )
    with kd.use_backend("pallas"):
        assert kd.hoist_packed(p4) is p4


def test_zero_byte4_pads_decode_to_exact_zero():
    """Tile padding for the packed stream uses ZERO_BYTE4 = two ZERO_CODE
    nibbles; both nibbles must decode to exactly 0 at any group exponent."""
    assert kd.ZERO_BYTE4 == (floatsd4.ZERO_CODE << 4) | floatsd4.ZERO_CODE
    packed = jnp.full((4, 4), kd.ZERO_BYTE4, jnp.uint8)
    for e in (-30, 0, 25):
        exps = jnp.full((1, 4), e, jnp.int8)
        np.testing.assert_array_equal(
            np.asarray(floatsd4.decode_packed(packed, exps, 8)), 0.0
        )


def test_packed4_bytes_resident_exactly_half():
    """Acceptance criterion: the packed code stream is exactly
    ceil(K/2) * N bytes vs K * N for FloatSD8, at even and odd K."""
    for k, n in [(128, 96), (101, 66), (33, 32)]:
        w = jnp.asarray(_w((k, n), 0.05))
        p8 = kd.PackedTensor(*floatsd.encode(w))
        p4 = kd.pack4(w)
        assert p8.codes.nbytes == k * n
        assert p4.codes.nbytes == -(-k // 2) * n
        assert p4.exps.nbytes == -(-k // floatsd4.GROUP) * n
        assert p4.shape == (k, n)
