"""Find the highest rate a serving cell sustains: one set-up, then the
cell's open loop at each rate in turn (no reference check), one JSON line
per rate to stdout and chiprun_out/.

    python3 bench/tools/knee_sweep.py <cell> <seed> <seconds> <rate> [<rate> ...]

Each rate gets its own seed (``seed + k``): a prompt served at one rate
would hit the prefix cache at the next. A rate is sustained where the
backlog at the window's close stays small, the drain after it is short and
the TTFT of the window's last third is not far above that of its first
third (the queue does not grow through the run).
"""
import json
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import bench.run as R  # noqa: E402


def main():
    cell, seed, secs = sys.argv[1], sys.argv[2], float(sys.argv[3])
    rates = [float(x) for x in sys.argv[4:]]
    run = R.make_run(["--workload", cell, "--seed", seed, "--seconds", str(secs)],
                     {"t_start": T0})
    from bench.drivers import serve
    from bench.harness import traffic

    st = serve.setup(run)
    print(json.dumps({"setup_s": time.monotonic() - T0}), flush=True)
    rows = []
    for k, rate in enumerate(rates):
        mix = dict(run.mix, rate_rps=rate)
        sched = traffic.serve_schedule(mix, run.seed + k, secs, run.cfg["vocab"])
        w = serve.open_loop(st, sched, secs)
        s = serve.summarize(w)
        due = np.asarray(w["due"])
        ttft = np.asarray(s["ttft"])
        first, last = ttft[due < secs / 3], ttft[due >= 2 * secs / 3]
        row = {
            "rate": rate, "attempted": len(w["recs"]), "failed": s["failed"],
            "backlog_at_close": w["backlog_at_close"], "drain_s": w["drain_s"],
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "ttft_first_third_p50_ms": 1e3 * float(np.median(first)) if len(first) else None,
            "ttft_last_third_p50_ms": 1e3 * float(np.median(last)) if len(last) else None,
            "itl_mean_ms": 1e3 * float(np.mean(s["gaps"])) if s["gaps"] else None,
            "itl_p95_ms": 1e3 * float(np.percentile(s["gaps"], 95)) if s["gaps"] else None,
            "steps": w["window"], "compiles": w["compiles"],
            "lag_p95_ms": 1e3 * float(np.percentile(w["lag"], 95)) if w["lag"] else None,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"sweep_{cell}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
