"""Paper Table IV: FP32 baseline vs FloatSD8 vs FloatSD8+FP16-master across
the four LSTM tasks (UDPOS / SNLI / Multi30K / WikiText-2).

Default runs the reduced configuration (CPU container); ``--full`` runs the
paper-scale models. The reproduction claim validated here is *relative*:
FloatSD8 (Table II) and FloatSD8+FP16 master (Table VI) track the FP32
baseline's metric within noise on the first three tasks, and land within a
few percent on the LM task — the paper's Fig. 6 / Table IV shape.
"""
from __future__ import annotations

import argparse
import json
import os

from ._trainers import POLICIES, train_task

TASKS = ("udpos", "snli", "multi30k", "wikitext2")


def run(tasks=TASKS, steps=200, full=False, verbose=True, out=None, seeds=(0,)):
    rows = []
    for task in tasks:
        for pol in POLICIES:
            for seed in seeds:
                r = train_task(task, pol, steps=steps, seed=seed, full=full)
                r["seed"] = seed
                rows.append(r)
                if verbose:
                    print(
                        f"  {task:10s} {pol:18s} seed{seed} "
                        f"{r['metric']}={r['value']:.4f}  "
                        f"loss {r['loss_first10']:.3f}->{r['loss_last10']:.3f}  "
                        f"({r['train_s']}s)",
                        flush=True,
                    )
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", nargs="*", default=list(TASKS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seeds", type=int, nargs="*", default=[0])
    ap.add_argument("--out", default="results/table4_accuracy.json")
    a = ap.parse_args()
    print("Table IV reproduction (FP32 vs FloatSD8 Table-II vs Table-VI):")
    run(a.tasks, a.steps, a.full, out=a.out, seeds=tuple(a.seeds))


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
