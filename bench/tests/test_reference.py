"""The reference's number formats, written from their definitions, agree
bit for bit with the program's on the CPU (the reference itself imports
nothing of the program; this test does, to compare)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import formats as F

KEY = jax.random.PRNGKey(0)
X = jnp.concatenate(
    [jax.random.normal(KEY, (20000,)) * s for s in (1e-6, 1e-3, 0.03, 1.0, 100.0, 1e5)]
    + [jnp.array([0.0, -0.0, 57344.0, 60000.0, 65504.0, 70000.0, 2 ** -16, 2 ** -17,
                  2 ** -25, 1e-30])])


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return bool(np.all((a == b) | (np.isnan(a) & np.isnan(b))))


def test_fp16_and_fp8_rounding():
    from repro.core import fp8 as P

    assert _same(F.fp16(X), P.round_to(X, jnp.float16))
    assert _same(F.fp8(X), P.quantize_fp8(X))
    assert _same(F.round_float(X, 2, -14), X.astype(jnp.float8_e5m2).astype(jnp.float32))


@pytest.mark.parametrize("scale", [0.02, 0.03, 1.0])
def test_floatsd8_and_floatsd4(scale):
    from repro.core import floatsd, floatsd4

    w = jax.random.normal(KEY, (256, 128)) * scale
    q8 = floatsd.quantize(w).values
    assert _same(F.floatsd8(w), q8)
    assert _same(F.floatsd4(F.floatsd8(w)), floatsd4.decode(*floatsd4.encode(q8)))


def test_two_region_sigmoid():
    from repro.core.qsigmoid import qsigmoid_raw

    z = jnp.linspace(-12, 12, 100001)
    assert _same(F.floatsd8_sigmoid(z), qsigmoid_raw(z))
