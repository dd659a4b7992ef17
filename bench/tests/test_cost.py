"""The closed-form counts at the cells' shapes, against numbers worked out
by hand (2*M*K*N flops; bytes as each module's docstring lists them)."""
import pytest

from bench.cost import floor_seconds, floatsd4_matmul, floatsd_matmul
from bench.cost import floatsd_matmul_dw, floatsd_matmul_dx, lstm_cell, lstm_cell_grad
from bench.cost import model
from bench.harness.device import PEAKS

CFG = {"vocab": 33278, "emb": 1024, "hidden": 1024, "n_layers": 2}
V5E = PEAKS["TPU v5 lite"]


@pytest.mark.parametrize("fn, shape, flops, nbytes", [
    # decode step, one gate matmul: 64 lanes x [1024 -> 4096]
    (floatsd_matmul.cost, (64, 1024, 4096), 536_870_912, 5_505_028),
    # prefill step's head: 64 lanes x 8 positions x [1024 -> 33278]
    (floatsd_matmul.cost, (512, 1024, 33278), 34_894_512_128, 104_327_172),
    # FloatSD4: 512 code bytes and 32 exponent bytes per column of 1024 rows
    (floatsd4_matmul.cost, (64, 1024, 4096), 536_870_912, 3_538_944),
    # training: dW over batch 64 x 128 steps
    (floatsd_matmul_dw.cost, (8192, 1024, 4096), 68_719_476_736, 184_549_376),
    (floatsd_matmul_dx.cost, (64, 1024, 4096), 536_870_912, 5_505_028),
])
def test_matmul_counts(fn, shape, flops, nbytes):
    assert fn(*shape) == (flops, nbytes)


@pytest.mark.parametrize("fn, flops, nbytes", [
    # z 64x4096 f32 + c 64x1024 f16 in; h f32 + c f16 out
    (lstm_cell.cost, 786_432, 1_572_864),
    # z, c_prev, dh, dc f32 in; dz, dc_prev f32 out
    (lstm_cell_grad.cost, 1_966_080, 3_145_728),
])
def test_cell_counts(fn, flops, nbytes):
    assert fn(64, 1024) == (flops, nbytes)


def test_model_flops_per_token():
    assert model.lstm_flops(CFG) == 33_554_432          # 2 x 2*(1024+1024)*4096
    assert model.head_flops(CFG) == 68_153_344          # 2*1024*33278
    assert model.train_flops_per_token(CFG) == 305_123_328


def test_floor_picks_the_binding_bound():
    t, bound = floor_seconds(*floatsd_matmul.cost(64, 1024, 4096), V5E)
    assert bound == "hbm" and t == pytest.approx(5_505_028 / 819e9)
    t, bound = floor_seconds(*floatsd_matmul_dw.cost(8192, 1024, 4096), V5E)
    assert bound == "compute" and t == pytest.approx(68_719_476_736 / 197e12)
