"""Gradient parity for the fused quantized-BPTT path.

The oracle is plain autodiff through the inline STE math (the pre-fusion
training path). The fused path must produce, on BOTH dispatch backends:

  * bit-identical FORWARD values (decode(encode(w)) == quantize(w).values),
  * weight gradients equal to fp8(oracle dW) — exactly when the cell state
    is f32 (table2-style policies) and the oracle's bf16 dW emission is off;
    within the fp16-rounding envelope when the cell state is fp16 (the fused
    dc chain stays f32 where autodiff rounds through the fp16 cell — the
    recorded deviation in kernels/lstm_cell/bwd.py),

across the plain scan, the lengths-masked scan, a reverse layer, a padded
(non-tile-multiple) hidden size, and both remat modes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import floatsd
from repro.core.fp8 import quantize_fp8
from repro.core.policy import get_policy
from repro.kernels import dispatch as kd
from repro.nn import linear as lin
from repro.nn import lstm as lstm_mod
from repro.nn.lstm import LSTMLayer

T2 = get_policy("floatsd8_table2")  # fp32 master -> f32 cell state
T6 = get_policy("floatsd8_table6")  # fp16 master -> fp16 cell state


@pytest.fixture
def no_bf16_dw():
    """Disable the oracle's bf16 dW emission so fp8(oracle) is exact."""
    old = lin.GRAD_REDUCE_BF16
    lin.GRAD_REDUCE_BF16 = False
    yield
    lin.GRAD_REDUCE_BF16 = old


@pytest.fixture(params=[False, True], ids=["save-z", "remat"])
def remat(request):
    old = lstm_mod.BPTT_REMAT
    lstm_mod.BPTT_REMAT = request.param
    yield request.param
    lstm_mod.BPTT_REMAT = old


# ---------------------------------------------------------------------------
# unit level: the dispatch custom-VJP wrappers vs the autodiff oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (6, 20, 28)])
def test_train_matmul_grads_vs_ste_oracle(backend, m, k, n):
    """dx matches the STE oracle exactly (f32); dw == fp8(oracle dw)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32) * 0.05)

    def f_fused(x, w):
        with kd.use_backend(backend):
            wq = kd.hoist_train(w)
            return jnp.sum(kd.train_matmul(x, w, wq) ** 2)

    def f_oracle(x, w):
        bias = jax.lax.stop_gradient(floatsd.fit_bias(w))
        wq = floatsd.quantize_ste(w, bias)
        return jnp.sum(jnp.dot(x, wq, preferred_element_type=jnp.float32) ** 2)

    gx1, gw1 = jax.grad(f_fused, (0, 1))(x, w)
    gx0, gw0 = jax.grad(f_oracle, (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(gw1), np.asarray(quantize_fp8(gw0)), rtol=1e-5, atol=1e-7
    )


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("b,h", [(8, 128), (5, 70)])  # native + padded
def test_lstm_cell_train_grads_vs_ste_oracle(backend, b, h):
    """The recompute-gates cell VJP == autodiff through the inline STE cell
    (f32 cell state -> no fp16-chain deviation; pallas tolerance is kernel
    lowering noise)."""
    from repro.core.qsigmoid import qsigmoid, qtanh_fp8

    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.standard_normal((b, 4 * h)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((b, h)).astype(np.float32))

    def f_fused(z, c):
        with kd.use_backend(backend):
            h_t, c_t = kd.lstm_cell_train(z, c, quantized=True,
                                          c_dtype=jnp.float32)
        return jnp.sum(h_t ** 2) + jnp.sum(c_t.astype(jnp.float32) ** 2)

    def f_oracle(z, c):
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        i_t, f_t, o_t = qsigmoid(zi), qsigmoid(zf), qsigmoid(zo)
        g_t = qtanh_fp8(zg)
        c_t = (f_t * c + i_t * g_t).astype(jnp.float32)
        h_t = o_t * qtanh_fp8(c_t)
        return jnp.sum(h_t ** 2) + jnp.sum(c_t ** 2)

    gz1, gc1 = jax.grad(f_fused, (0, 1))(z, c)
    gz0, gc0 = jax.grad(f_oracle, (0, 1))(z, c)
    tol = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gz1), np.asarray(gz0), **tol)
    np.testing.assert_allclose(np.asarray(gc1), np.asarray(gc0), **tol)


# ---------------------------------------------------------------------------
# layer level: the scan engine vs autodiff through the whole BPTT
# ---------------------------------------------------------------------------


def _layer_losses(layer, xs, lengths=None):
    def make(policy):
        def loss(p):
            h, fin = layer.apply(p, xs, policy, lengths=lengths)
            return (jnp.sum(h.astype(jnp.float32) ** 2)
                    + jnp.sum(fin.c.astype(jnp.float32) ** 2))
        return loss
    return make


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("variant", ["plain", "masked", "reverse", "padded"])
def test_fused_layer_grads_match_fp8_of_oracle(no_bf16_dw, remat, backend,
                                               variant):
    """Full-scan gradient grid: fused engine vs fp8(autodiff oracle), exact
    for the f32-cell policy, on both backends, incl. the lengths-masked
    scan and a padded (non-tile-multiple) hidden size."""
    hidden = 70 if variant == "padded" else 16
    layer = LSTMLayer(12, hidden, reverse=(variant == "reverse"))
    p = layer.init(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 9, 12))
    lengths = (jnp.asarray([3, 9, 5, 7], jnp.int32)
               if variant == "masked" else None)
    make = _layer_losses(layer, xs, lengths)

    # forward bit-parity first (fused routing must not change values)
    h0, _ = layer.apply(p, xs, T2, lengths=lengths)
    with kd.use_backend(backend):
        h1, _ = layer.apply(p, xs, T2.replace(grad_quant="fp8_kernel"),
                            lengths=lengths)
    if backend == "ref":
        np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))
    else:
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1),
                                   rtol=2e-3, atol=1e-5)

    v0, g0 = jax.value_and_grad(make(T2))(p)
    with kd.use_backend(backend):
        v1, g1 = jax.value_and_grad(
            make(T2.replace(grad_quant="fp8_kernel"))
        )(p)
    kwargs = (dict(rtol=0, atol=0) if backend == "ref"
              else dict(rtol=2e-3, atol=1e-5))
    if backend == "ref":
        assert float(v0) == float(v1)
    for key in ("wx", "wh"):
        # dW: in-kernel FP8 emission == fp8(oracle dW)
        np.testing.assert_allclose(
            np.asarray(g1[key]), np.asarray(quantize_fp8(g0[key])),
            err_msg=f"{variant}/{key}", **kwargs,
        )
    # bias: no kernel emission at layer level — raw vs raw (train_state's
    # idempotent tree pass quantizes both identically afterwards)
    np.testing.assert_allclose(
        np.asarray(g1["b"]), np.asarray(g0["b"]),
        err_msg=f"{variant}/b", **(dict(rtol=1e-6, atol=1e-6)
                                   if backend == "ref" else kwargs),
    )


def test_fused_layer_grads_fp16_cell_within_envelope(remat):
    """table6 (fp16 cell state): the fused dc chain stays f32 where autodiff
    rounds through fp16 — gradients agree within the fp16 envelope after
    removing the fp8 binning (compare pre-optimizer cosine + max rel)."""
    layer = LSTMLayer(12, 16)
    p = layer.init(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (4, 9, 12))
    make = _layer_losses(layer, xs)
    v0, g0 = jax.value_and_grad(make(T6))(p)
    v1, g1 = jax.value_and_grad(make(T6.replace(grad_quant="fp8_kernel")))(p)
    assert float(v0) == float(v1)  # forward identical
    for key in ("wx", "wh", "b"):
        oracle = quantize_fp8(g0[key]) if key != "b" else g0[key]
        a = np.asarray(oracle, np.float32).ravel()
        c = np.asarray(g1[key], np.float32).ravel()
        cos = np.dot(a, c) / max(np.linalg.norm(a) * np.linalg.norm(c), 1e-12)
        assert cos > 0.999, (key, cos)


def test_engine_residuals_shrink_vs_autodiff(remat):
    """The residual contract is real: saved forward->backward bytes of the
    fused engine are well below autodiff's per-gate stacking (>=2x; ~4x
    under remat)."""
    layer = LSTMLayer(32, 32)
    p = layer.init(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32))

    def res_bytes(policy):
        _, vjp_fn = jax.vjp(
            lambda p: jnp.sum(layer.apply(p, xs, policy)[0] ** 2), p
        )
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(vjp_fn)
                   if hasattr(l, "size"))

    base = res_bytes(T6)
    fused = res_bytes(T6.replace(grad_quant="fp8_kernel"))
    floor = 2.0 if not remat else 3.5
    assert base / fused >= floor, (base, fused, base / fused)


# ---------------------------------------------------------------------------
# trajectory level (slow tier): determinism + cross-backend divergence
# ---------------------------------------------------------------------------


def _train_losses(steps, backend, seed=0):
    from repro.data import synthetic
    from repro.models.lstm_models import WikiText2LM
    from repro.optim import sgd
    from repro.optim.train_state import init_state, make_train_step

    model = WikiText2LM(vocab=128, emb=16, hidden=16, n_layers=2)
    data = synthetic.wikitext2(batch=8, seq=16, vocab=model.vocab, seed=seed)
    opt = sgd(0.9)
    with kd.use_backend(backend):
        state = init_state(model.init(jax.random.PRNGKey(seed)), opt, T6)
        step = make_train_step(model.loss, opt, T6, lr=0.5, fused=True,
                               donate=True)
        losses = []
        for _ in range(steps):
            batch = {k: jnp.asarray(v) for k, v in next(data.batches).items()}
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return losses


@pytest.mark.slow
def test_fused_loss_trajectory_deterministic_on_ref():
    """Deterministic recompute: two identical fused runs on ref are
    bit-identical."""
    assert _train_losses(10, "ref") == _train_losses(10, "ref")


@pytest.mark.slow
def test_fused_loss_trajectory_ref_vs_pallas_interpret():
    """<= 1e-3 relative loss divergence over 50 steps between the ref
    backward kernels and the Pallas(interpret) ones (acceptance bound)."""
    ref = np.asarray(_train_losses(50, "ref"))
    pal = np.asarray(_train_losses(50, "pallas"))
    rel = np.max(np.abs(ref - pal) / np.maximum(np.abs(ref), 1e-9))
    assert rel <= 1e-3, rel


@pytest.mark.parametrize("policy", [T2, T6], ids=["fp32-master", "fp16-master"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_mesh_step_matches_meshless_step(no_bf16_dw, backend, policy):
    """A step given a "data" mesh (here of one device) differentiates f32
    copies of the masters, sums raw f32 dW over the axis and snaps the sum
    in its FP8 sweep; the meshless step snaps the gate dW at the kernel
    flush. With the oracle's bf16 dW emission off: fp32 masters give
    bit-identical updates; fp16 masters too for the gate weights, while the
    meshless step rounds the other gradients to fp16 before their FP8 snap,
    which moves a few elements by one FP8 step."""
    from jax.sharding import Mesh

    from repro.data import synthetic
    from repro.models.lstm_models import WikiText2LM
    from repro.optim import sgd
    from repro.optim.train_state import init_state, make_train_step

    model = WikiText2LM(vocab=128, emb=16, hidden=16, n_layers=2)
    data = synthetic.wikitext2(batch=8, seq=16, vocab=model.vocab, seed=0)
    batch = {k: jnp.asarray(v) for k, v in next(data.batches).items()}
    opt = sgd(0.9)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    out = []
    with kd.use_backend(backend):
        for m in (None, mesh):
            state = init_state(model.init(jax.random.PRNGKey(0)), opt, policy)
            step = jax.jit(make_train_step(model.loss, opt, policy, lr=0.5,
                                           fused=True, mesh=m))
            state, metrics = step(state, batch)
            assert bool(metrics["grads_finite"])
            out.append(state)
    trees = [jax.tree_util.tree_leaves_with_path((st.params, st.opt_state))
             for st in out]
    for (path, a), (_, b) in zip(*trees):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        gate = jax.tree_util.keystr(path).endswith(("['wx']", "['wh']"))
        if policy is T2 or gate:
            np.testing.assert_array_equal(a, b)
        else:
            assert np.mean(a != b) <= 0.01, jax.tree_util.keystr(path)
