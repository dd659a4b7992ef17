"""Training model FLOPs per second (three times the forward matmuls per
token, ``bench.cost.model``) over the chips' bf16 peak."""
from bench.cost.model import train_flops_per_token


def read(run):
    rate = run.layer.get("tokens_per_s")
    if not rate or not run.peaks:
        return None
    flops = rate * train_flops_per_token(run.cfg)
    return 100.0 * flops / (run.layer["chips"] * run.peaks["bf16_flops"])
