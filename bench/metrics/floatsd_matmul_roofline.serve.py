"""Roofline share of the FloatSD8 decode+matmul kernel while serving. Per
step of width S over B lanes: per layer and position x @ Wx and
h @ Wh ([B, D] x [D, 4H]), then the tied head [B*S, emb] x [emb, vocab]."""
from bench.cost.floatsd_matmul import cost
from bench.harness import roofline

PATTERN = r"^floatsd_matmul_pallas$"


def calls(cfg, lanes, width):
    h = cfg["hidden"]
    out = []
    for i in range(cfg["n_layers"]):
        d = cfg["emb"] if i == 0 else h
        out += [(width, cost(lanes, d, 4 * h)), (width, cost(lanes, h, 4 * h))]
    return out + [(1, cost(lanes * width, cfg["emb"], cfg["vocab"]))]


def read(run):
    st = roofline.serve_steps(run)
    if not st:
        return None
    lanes, chunk = run.layer["lanes"], run.layer["chunk"]
    per = [(n * c, fb) for n, w in ((st[0], chunk), (st[1], 1))
           for c, fb in calls(run.cfg, lanes, w)]
    return roofline.share(run, PATTERN, per)
