"""Roofline share of the LSTM cell's backward kernel in training: one call
per layer and time step of each traced step, on the chip's rows."""
from bench.cost.lstm_cell_grad import cost
from bench.harness import roofline

PATTERN = r"^lstm_cell_bwd_pallas$"


def read(run):
    steps = run.layer.get("traced_steps")
    if not steps:
        return None
    cfg, seq, b = run.cfg, run.layer["seq"], run.layer["rows"] // run.layer["chips"]
    n = steps * cfg["n_layers"] * seq
    return roofline.share(run, PATTERN, [(n, cost(b, cfg["hidden"]))])
