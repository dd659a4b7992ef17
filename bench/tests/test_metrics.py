"""Each per-layer reader on a hand-made run: the value it must give, and
nothing where it finds nothing to read."""
import types

import pytest

from bench.cost import floor_seconds
from bench.cost import floatsd_matmul, lstm_cell_grad, model
from bench.harness import spec as S
from bench.harness.device import PEAKS

CFG = S.config(S.benchmark(), "lstm_wt2_fsd8")
V5E = PEAKS["TPU v5 lite"]


def _run(events, layer, window=(0, 10 ** 9)):
    tr = {"window": list(window), "devices": {"/device:TPU:0": events}, "host": []}
    return types.SimpleNamespace(result={"trace": tr}, layer=layer, cfg=CFG, peaks=V5E)


def _floor(fb):
    return floor_seconds(*fb, V5E)[0]


def test_serve_matmul_roofline_of_one_decode_step():
    # one decode step at 64 lanes: 2 layers x (x@Wx, h@Wh) + the head
    calls = [floatsd_matmul.cost(64, 1024, 4096)] * 4 + [floatsd_matmul.cost(64, 1024, 33278)]
    floor = sum(_floor(c) for c in calls)
    ns = int(2 * floor * 1e9)
    run = _run([["%floatsd_matmul_pallas.3 = f32[] custom-call()", 0, ns]],
               {"traced_steps": {"prefill": 0, "decode": 1}, "lanes": 64, "chunk": 8})
    read = S.metric_reader("floatsd_matmul_roofline.serve")
    assert read(run) == pytest.approx(50.0, rel=1e-4)


def test_train_cell_grad_roofline():
    n = 2 * 2 * 128                       # 2 traced steps x 2 layers x 128 steps
    floor = n * _floor(lstm_cell_grad.cost(64, 1024))
    run = _run([["%lstm_cell_bwd_pallas.1 = x", 0, int(4 * floor * 1e9)]],
               {"traced_steps": 2, "rows": 64, "seq": 128, "chips": 1})
    assert S.metric_reader("lstm_cell_grad_roofline.train")(run) == pytest.approx(25.0, rel=1e-4)


def test_idle_share_and_mfu():
    run = _run([["%a = x", 0, 250_000_000]], {"tokens_per_s": 5000.0, "chips": 1})
    assert S.metric_reader("device.idle_share.train")(run) == pytest.approx(75.0)
    want = 100 * 5000.0 * model.train_flops_per_token(CFG) / 197e12
    assert S.metric_reader("train_mfu")(run) == pytest.approx(want)


@pytest.mark.parametrize("name", [m["name"] for m in S.benchmark()["per_layer"]])
def test_reader_finds_nothing_and_returns_nothing(name):
    run = types.SimpleNamespace(result={"trace": None}, layer={}, cfg=CFG, peaks=None)
    assert S.metric_reader(name)(run) is None


def test_engine_step_spans():
    ev = [{"name": "engine.step", "ph": "B", "ts": 0, "tid": 1, "args": {"kind": "decode"}},
          {"name": "engine.step", "ph": "E", "ts": 2000, "tid": 1},
          {"name": "engine.step", "ph": "B", "ts": 5000, "tid": 1, "args": {"kind": "prefill"}},
          {"name": "engine.step", "ph": "E", "ts": 9000, "tid": 1}]
    run = types.SimpleNamespace(result={}, layer={"spans": ev}, cfg=CFG, peaks=V5E)
    assert S.metric_reader("engine.decode_step_ms.serve")(run) == pytest.approx(2.0)
    assert S.metric_reader("engine.prefill_step_ms.serve")(run) == pytest.approx(4.0)
