"""A whole run at a small size on the CPU, sound and with the timed path
broken underneath: ``correct`` must come out true, then false for each
fault the cell can have (a token altered where it is produced; a train
step that returns its state unchanged; half of the batch left out)."""
import jax
import pytest

from bench.tests.cells import SMALL_CFG, run_small


@pytest.mark.parametrize("fmt", ["floatsd8", "floatsd4"])
def test_sound_serving_run_is_correct(fmt):
    out, _ = run_small("lstm_wt2_fsd8.chat", 2 ** 31 + 101,
                       cfg={**SMALL_CFG, "weight_format": fmt})
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["logit_gap"]["value"] <= out["checks"]["logit_gap"]["limit"]


def test_altered_token_is_caught(monkeypatch):
    from repro.serving.engine import ServeEngine

    emit = ServeEngine._emit
    count = {"n": 0}

    def altered(self, lane, tok, now, first=False):
        count["n"] += 1
        if count["n"] % 7 == 0:
            tok = (tok + 1) % 512
        return emit(self, lane, tok, now, first)

    monkeypatch.setattr(ServeEngine, "_emit", altered)
    out, _ = run_small("lstm_wt2_fsd8.chat", 2 ** 31 + 102)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


def test_sound_train_run_is_correct():
    out, _ = run_small("lstm_wt2_fsd8.train", 2 ** 31 + 103)
    assert out["correct"], out["checks"]


def _broken_step(monkeypatch, fault):
    import repro.optim.train_state as ts

    make = ts.make_train_step

    def broken(*a, donate=False, **kw):
        step = jax.jit(make(*a, donate=False, **kw))
        if fault == "freeze":
            return lambda s, b: (s, step(s, b)[1])
        return lambda s, b: step(s, {k: v[: v.shape[0] // 2] for k, v in b.items()})

    monkeypatch.setattr(ts, "make_train_step", broken)


@pytest.mark.parametrize("fault", ["freeze", "half_batch"])
def test_train_fault_is_caught(monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    out, _ = run_small("lstm_wt2_fsd8.train", 2 ** 31 + 104)
    assert not out["correct"], out["checks"]
