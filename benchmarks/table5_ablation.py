"""Paper Table V: WikiText-2 activation-precision ablation.

Five (first layer, last layer, other layers) activation settings on the LM
task; reproduces the paper's finding that the LAST layer's activation
precision dominates (fp8 last-layer hurts; fp16 last-layer recovers the
baseline even with fp8 everywhere else).
"""
from __future__ import annotations

import argparse
import json
import os

from ._trainers import train_task

# (first, last, other) -> paper rows, in order
SETTINGS = [
    ("fp8", "fp8", "fp8"),
    ("fp16", "fp16", "fp16"),
    ("fp8", "fp16", "fp8"),
    ("fp16", "fp8", "fp8"),
    ("fp16", "fp16", "fp8"),
]


def run(steps=200, full=False, verbose=True, out=None, seed=0):
    rows = []
    for first, last, other in SETTINGS:
        overrides = {
            "first_layer_act": first,
            "last_layer_act": last,
            "act_fwd": other,
            "act_bwd": other,
            # Table V is run on the Table-II scheme (fp32 master)
        }
        r = train_task(
            "wikitext2", "floatsd8_table2", steps=steps, seed=seed, full=full,
            policy_overrides=overrides,
        )
        r.update(first=first, last=last, other=other)
        rows.append(r)
        if verbose:
            print(
                f"  first={first:5s} last={last:5s} other={other:5s} "
                f"ppl={r['value']:.3f}  loss {r['loss_first10']:.3f}->"
                f"{r['loss_last10']:.3f}",
                flush=True,
            )
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="results/table5_ablation.json")
    a = ap.parse_args()
    print("Table V reproduction (WikiText-2 activation-precision ablation):")
    run(a.steps, a.full, out=a.out)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
