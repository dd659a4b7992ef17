"""Compile rehearsal for TPU v5e: every main-path Pallas kernel, compiled
(``interpret=False``) at the published widths of the WikiText-2 LSTM LM
(emb = hidden = 1024, vocab 33278 padded to 33280, M = 8 decode rows and
256 prefill/training rows), for a v5e chip that is described, not attached.

The TPU compiler runs here without a chip: it refuses what Mosaic cannot
lower (table gathers, f16 vectors) and kernels that overflow VMEM, which
interpret mode never sees. Tiles come from the dispatch planners, so the
planners are judged by the chip's compiler too. Nothing runs, so this says
nothing about results or speed (``chip_smoke.py`` does that on the chip).

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library, and every worker collects the same
tests.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch as kd
from repro.kernels.floatsd4_matmul.kernel import floatsd4_matmul_pallas
from repro.kernels.floatsd_matmul.bwd import matmul_dw_pallas, matmul_dx_pallas
from repro.kernels.floatsd_matmul.kernel import floatsd_matmul_pallas
from repro.kernels.floatsd_quantize.kernel import quantize_pallas
from repro.kernels.lstm_cell.bwd import lstm_cell_bwd_pallas
from repro.kernels.lstm_cell.kernel import lstm_cell_pallas
from repro.kernels.qsigmoid.kernel import qsigmoid_pallas

K = H = 1024  # emb = hidden
VOCAB = 33280  # 33278 padded to a multiple of 256
ROWS = (8, 256)  # decode batch, prefill / training rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for(topo):
    """Compile ``fn`` at ``ShapeDtypeStruct`` args placed on one described
    v5e chip; returns the HLO text. The persistent compilation cache is off
    meanwhile: an entry compiled without a chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _assert_kernel(hlo: str):
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", (4 * H, VOCAB))
@pytest.mark.parametrize("m", ROWS)
def test_floatsd_matmul_compiles(compile_for, m, n):
    bm, bn, bk = kd.matmul_tiles(m, n, K)
    fn = functools.partial(floatsd_matmul_pallas, bm=bm, bn=bn, bk=bk,
                           compute_dtype=jnp.bfloat16)
    _assert_kernel(compile_for(
        fn, ((m, K), jnp.float32), ((K, n), jnp.uint8), ((), jnp.int32)))


@pytest.mark.parametrize("n", (4 * H, VOCAB))
@pytest.mark.parametrize("m", ROWS)
def test_floatsd4_matmul_compiles(compile_for, m, n):
    bm, bn, bk = kd.matmul_tiles(m, n, K)
    fn = functools.partial(floatsd4_matmul_pallas, bm=bm, bn=bn, bk=bk,
                           compute_dtype=jnp.bfloat16)
    _assert_kernel(compile_for(
        fn, ((m, K), jnp.float32), ((K // 2, n), jnp.uint8),
        ((K // 32, n), jnp.int8)))


@pytest.mark.parametrize("m", ROWS)
def test_floatsd4_tied_head_compiles(compile_for, m):
    """The tied logits head: the [vocab, emb] table packed along vocab,
    taken as stored (``transpose_w``), f32 issue as serving uses it."""
    bm, bn, bk = kd.matmul_tiles(m, VOCAB, K)
    fn = functools.partial(floatsd4_matmul_pallas, bm=bm, bn=bn, bk=bk,
                           compute_dtype=jnp.float32, transpose_w=True)
    _assert_kernel(compile_for(
        fn, ((m, K), jnp.float32), ((VOCAB // 2, K), jnp.uint8),
        ((VOCAB // 32, K), jnp.int8)))


@pytest.mark.parametrize("m", ROWS)
def test_matmul_dx_compiles(compile_for, m):
    # dx = g [m, 4H] x decode(codes [K, 4H])^T: contraction over 4H
    bm, bn, bk = kd.matmul_tiles(m, K, 4 * H)
    fn = functools.partial(matmul_dx_pallas, bm=bm, bn=bn, bk=bk)
    _assert_kernel(compile_for(
        fn, ((m, 4 * H), jnp.float32), ((K, 4 * H), jnp.uint8),
        ((), jnp.int32)))


@pytest.mark.parametrize("m", ROWS)
def test_matmul_dw_compiles(compile_for, m):
    # dw = x [m, K]^T x g [m, 4H]: contraction over the m rows
    bm, bn, bk = kd.matmul_tiles(K, 4 * H, m)
    fn = functools.partial(matmul_dw_pallas, bm=bm, bn=bn, bk=bk)
    _assert_kernel(compile_for(
        fn, ((m, K), jnp.float32), ((m, 4 * H), jnp.float32)))


@pytest.mark.parametrize("c_dtype", (jnp.float16, jnp.float32))
@pytest.mark.parametrize("b", ROWS)
def test_lstm_cell_compiles(compile_for, b, c_dtype):
    bb, bh = kd.lstm_tiles(b, H)
    fn = functools.partial(lstm_cell_pallas, bb=bb, bh=bh, c_dtype=c_dtype)
    _assert_kernel(compile_for(
        fn, ((b, 4 * H), jnp.float32), ((b, H), c_dtype)))


@pytest.mark.parametrize("c_dtype", (jnp.float16, jnp.float32))
@pytest.mark.parametrize("b", ROWS)
def test_lstm_cell_grad_compiles(compile_for, b, c_dtype):
    bb, bh = kd.lstm_tiles(b, H)
    fn = functools.partial(lstm_cell_bwd_pallas, bb=bb, bh=bh,
                           c_dtype=c_dtype)
    _assert_kernel(compile_for(
        fn, ((b, 4 * H), jnp.float32), ((b, H), c_dtype),
        ((b, H), jnp.float32), ((b, H), c_dtype)))


@pytest.mark.parametrize("n", (K * 4 * H, VOCAB * K))
def test_floatsd_quantize_compiles(compile_for, n):
    """A gate weight and the embedding table, flattened to [rows, 256] as
    ``dispatch.quantize`` does."""
    rows = n // 256
    fn = functools.partial(quantize_pallas, bm=kd.row_tile(rows), bn=256)
    _assert_kernel(compile_for(
        fn, ((rows, 256), jnp.float32), ((), jnp.int32)))


@pytest.mark.parametrize("n", (8 * 4 * H, 256 * 4 * H))
def test_qsigmoid_compiles(compile_for, n):
    """Gate pre-activations of a decode and a training step."""
    rows = n // 256
    fn = functools.partial(qsigmoid_pallas, bm=kd.row_tile(rows), bn=256)
    _assert_kernel(compile_for(fn, ((rows, 256), jnp.float32)))


@pytest.mark.parametrize("fmt", ("floatsd8", "floatsd4"))
def test_serve_step_compiles_at_full_width(compile_for, monkeypatch, fmt):
    """The engine's step program (packed FloatSD8 or FloatSD4 weights, an
    8-lane chunk-8 prefill block) with every weight site on compiled
    Pallas, the tied logits head included: what ``auto`` resolves to on a
    TPU. Shapes only: the 33280 x 1024 table and both LSTM layers are never
    materialized."""
    from repro.core import floatsd
    from repro.core.policy import get_policy
    from repro.models.lstm_models import WikiText2LM

    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")
    model, policy = WikiText2LM(), get_policy("floatsd8_table6")
    serve_policy = policy.replace(weight_quant="none")
    op = "floatsd4_matmul" if fmt == "floatsd4" else "floatsd_matmul"

    def pack(w):
        if w.ndim < 2:
            return w
        if fmt == "floatsd4":
            return kd.pack4(w)
        return kd.PackedTensor(*floatsd.encode(w))

    packed = jax.eval_shape(
        lambda: jax.tree_util.tree_map(pack, model.init(jax.random.PRNGKey(0)))
    )
    caches = jax.eval_shape(lambda: model.init_cache(8, policy))
    leaves, tree = jax.tree_util.tree_flatten((packed, caches))

    def step(tokens, lengths, *flat):
        p, c = jax.tree_util.tree_unflatten(tree, flat)
        return model.decode_step(p, tokens, c, serve_policy, lengths=lengths)

    kd.STATS.reset()
    with kd.use_backend("auto"):
        hlo = compile_for(step, ((8, 8), jnp.int32), ((8,), jnp.int32),
                          *[(l.shape, l.dtype) for l in leaves])
    _assert_kernel(hlo)
    assert kd.STATS.count(backend="ref") == 0
    assert not kd.STATS.interpreted
    assert kd.STATS.count(op, "pallas") > 0
    assert kd.STATS.count("lstm_cell", "pallas") > 0


def test_bf16_matmul_compiles_under_highest_matmul_precision(compile_for):
    """A caller's ``jax.default_matmul_precision("highest")`` (how the
    ref backend is held to f32 on the chip) must not reach the kernel's
    bf16 MXU dot: Mosaic refuses f32 contraction of bf16 operands."""
    bm, bn, bk = kd.matmul_tiles(8, 4 * H, K)
    fn = functools.partial(floatsd_matmul_pallas, bm=bm, bn=bn, bk=bk,
                           compute_dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        _assert_kernel(compile_for(
            fn, ((8, K), jnp.float32), ((K, 4 * H), jnp.uint8),
            ((), jnp.int32)))
