"""The control of each cell must come out as not correct: the program with
its own path one precision lower switched on (policy ``floatsd8_tpu``:
bfloat16 matmuls where the configuration states float32), at a small size
on the CPU. On the chip, at the cells' own sizes, bench/tools/readings.py
reads the same controls (PERF.md gives the readings).

Serving: FP8 activations and FloatSD weights are exact in bfloat16, so the
control differs only where the float16 head input is rounded, and a served
token flips only at a near-tie. At a small vocabulary that needs the
published widths and a few hundred served tokens, and the logit gaps are
smaller than at the cell's size: the serving control is held to a limit
set from readings at this size (program 0.0 on four seeds; control 4.8e-4
to 4.1e-3 on four seeds, FloatSD8 and FloatSD4). The chat traffic is also
served from FloatSD4 weights, the format of a later cell."""
import pytest

from bench.tests.cells import SMALL_CFG, SMALL_MIX, run_small

WIDE = {**SMALL_CFG, "emb": 1024, "hidden": 1024}
LONG = {**SMALL_MIX["serve"], "output_len": {"median": 64, "min": 32, "max": 128}}
SMALL_LOGIT_GAP = 1e-4


@pytest.mark.parametrize("fmt", ["floatsd8", "floatsd4"])
def test_lower_precision_serving_is_not_correct(fmt):
    over = {"cfg": {**WIDE, "weight_format": fmt}, "mix": LONG,
            "limits": {"logit_gap": SMALL_LOGIT_GAP}}
    sound, _ = run_small("lstm_wt2_fsd8.chat", 2 ** 31 + 201, **over)
    assert sound["correct"], sound["checks"]
    out, _ = run_small("lstm_wt2_fsd8.chat", 2 ** 31 + 201, policy="floatsd8_tpu", **over)
    assert not out["correct"], out["checks"]


def test_lower_precision_training_is_not_correct():
    out, _ = run_small("lstm_wt2_fsd8.train", 2 ** 31 + 201, policy="floatsd8_tpu")
    assert not out["correct"], out["checks"]
