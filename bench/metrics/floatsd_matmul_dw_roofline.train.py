"""Roofline share of the weight-gradient kernel in training: per layer and
traced step, dWx = X^T dZ and dWh = Hq^T dZ over all B*S rows at once."""
from bench.cost.floatsd_matmul_dw import cost
from bench.harness import roofline

PATTERN = r"matmul_dw_pallas"


def read(run):
    steps = run.layer.get("traced_steps")
    if not steps:
        return None
    cfg, h = run.cfg, run.cfg["hidden"]
    m = run.layer["seq"] * run.layer["rows"] // run.layer["chips"]
    per = []
    for i in range(cfg["n_layers"]):
        d = cfg["emb"] if i == 0 else h
        per += [(steps, cost(m, d, 4 * h)), (steps, cost(m, h, 4 * h))]
    return roofline.share(run, PATTERN, per)
