"""The reduction from a profiler trace to device numbers, on a small trace
recorded on a TPU v5e (a serving window of lstm_wt2_fsd8.chat) and on
hand-made events; and the extraction from a real trace file on the CPU."""
import json
import os

import pytest

from bench.harness import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "serve_trace.json")


def _hand():
    # window 0..100 ns; ops at 10-30, 20-40 (overlapping), 60-70; host spans
    return {"window": [0, 100],
            "devices": {"/device:TPU:0": [["%a.1 = f32[] x", 10, 20],
                                          ["%b.2 = f32[] y", 20, 20],
                                          ["%a.3 = f32[] z", 60, 10]]},
            "host": [["bench.pump", 0, 50], ["bench.idle", 40, 30],
                     ["bench.pump", 70, 30]]}


def test_busy_union_and_idle_share():
    tr = _hand()
    assert T.busy_intervals(tr["devices"]["/device:TPU:0"], 0, 100) == [[10, 40], [60, 70]]
    assert T.busy_seconds(tr) == pytest.approx(40e-9)
    assert T.idle_share(tr) == pytest.approx(0.6)


def test_op_seconds_by_instruction_name():
    tr = _hand()
    assert T.op_seconds(tr, r"^a$") == (pytest.approx(30e-9), 2)
    assert T.op_name("%floatsd_matmul_pallas.46 = f32[64,4096] custom-call(...)") == \
        "floatsd_matmul_pallas"
    assert T.top_ops(tr)[0] == ["a", pytest.approx(30e-9)]


def test_idle_gaps_are_labelled_by_host_span():
    gaps = T.idle_gaps(_hand())
    # gaps 0-10 (pump), 40-60 (idle: innermost span at 50), 70-100 (pump)
    assert gaps == [["bench.pump", pytest.approx(30e-9)],
                    ["bench.idle", pytest.approx(20e-9)],
                    ["bench.pump", pytest.approx(10e-9)]]


def test_recorded_chip_trace():
    tr = json.load(open(DATA))
    busy, win = T.busy_seconds(tr), T.window_seconds(tr)
    assert 0 < busy < win
    secs, n = T.op_seconds(tr, r"^floatsd_matmul_pallas$")
    assert n > 0 and 0 < secs < busy
    cell, m = T.op_seconds(tr, r"^lstm_cell_pallas$")
    assert m > 0
    # per position of a step: 2 layers x 2 gate matmuls per lstm cell call pair
    assert n >= 2 * m
    b = T.breakdown(tr)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) <= win - busy + 1e-12


def test_extract_reads_a_cpu_trace():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    out = {}
    with T.capture(out):
        with T.span("bench.pump"):
            f(x).block_until_ready()
    tr = out["trace"]
    assert tr["window"][1] > tr["window"][0]
    assert [h[0] for h in tr["host"]] == ["bench.pump"]
    assert tr["devices"] == {}          # the CPU has no device op line
    assert T.idle_share(tr) is None
