"""Small versions of the cells for the CPU: the harness's look for a chip
is skipped and the widths cut, everything else runs as on the chip."""
import time

import bench.run as R

SMALL_CFG = {"vocab": 512, "emb": 128, "hidden": 128}
SMALL_MIX = {
    "serve": {"rate_rps": 8.0, "prompt_len": {"median": 12, "min": 4, "max": 40},
              "output_len": {"median": 8, "min": 2, "max": 16},
              "engine": {"lanes": 8, "prefix_cache_mb": 1}},
    "train": {"batch_per_chip": 4, "seq": 16, "distinct_batches": 6},
}


def run_small(cell: str, seed: int, seconds: float = 2.0, trace: int = 0, **over):
    kind = "train" if cell.endswith("train") else "serve"
    overrides = {"allow_cpu": True, "cfg": SMALL_CFG, "mix": SMALL_MIX[kind],
                 "t_start": time.monotonic(), **over}
    return R.run_cell(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", str(trace)], overrides)
