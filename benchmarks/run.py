"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one benchmark per paper artifact (fast settings sized for the CPU
container) and prints a summary. Individual benchmarks accept --full /
--steps for paper-scale runs:

    python -m benchmarks.fig4_5_sigmoid
    python -m benchmarks.table4_accuracy --steps 2000 --full
    python -m benchmarks.table5_ablation --steps 2000 --full
    python -m benchmarks.table7_mac
    python -m benchmarks.roofline_report
    python -m benchmarks.bench_kernels
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60, help="train steps per task")
    ap.add_argument("--skip-train", action="store_true")
    a = ap.parse_args()
    t0 = time.time()

    print("=" * 72)
    print("[1/7] Fig. 4-5: two-region sigmoid quantization error")
    from . import fig4_5_sigmoid

    fig4_5_sigmoid.run()

    print("=" * 72)
    print("[2/7] Table VII: MAC complexity model")
    from . import table7_mac

    table7_mac.run(out="results/table7_mac.json")

    print("=" * 72)
    print("[3/7] Kernel microbenchmarks (decode-fused matmul vs oracle)")
    from . import bench_kernels

    bench_kernels.run()

    if not a.skip_train:
        print("=" * 72)
        print("[4/7] Train-step benchmark (fused quantized BPTT vs autodiff)")
        from . import bench_train

        bench_train.run(steps=max(5, a.steps // 10),
                        out="results/BENCH_train.json")

        print("=" * 72)
        print(f"[5/7] Table IV: 4-task accuracy, 3 policies ({a.steps} steps, reduced cfg)")
        from . import table4_accuracy

        table4_accuracy.run(steps=a.steps, out="results/table4_accuracy.json")

        print("=" * 72)
        print(f"[6/7] Table V: WikiText-2 activation ablation ({a.steps} steps)")
        from . import table5_ablation

        table5_ablation.run(steps=a.steps, out="results/table5_ablation.json")

    print("=" * 72)
    print("[7/7] Roofline report (from dry-run artifacts)")
    from . import roofline_report

    roofline_report.run()

    print("=" * 72)
    print(f"benchmarks.run complete in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
