"""Roofline share of the fused LSTM cell kernel while serving: one call per
layer and position of each step, on [lanes, 4H] gate pre-activations."""
from bench.cost.lstm_cell import cost
from bench.harness import roofline

PATTERN = r"^lstm_cell_pallas$"


def read(run):
    st = roofline.serve_steps(run)
    if not st:
        return None
    lanes, chunk, cfg = run.layer["lanes"], run.layer["chunk"], run.cfg
    n = cfg["n_layers"] * (st[0] * chunk + st[1])
    return roofline.share(run, PATTERN, [(n, cost(lanes, cfg["hidden"]))])
