"""Run one cell with --trace 1 and write what its device trace holds (op
names, counts, time) to chiprun_out/, to look at before writing readers.

    python3 bench/tools/probe_trace.py <cell> <seed> <seconds>
"""
import collections
import json
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import bench.run as R  # noqa: E402

cell, seed, secs = sys.argv[1], sys.argv[2], sys.argv[3]
out, run = R.run_cell(["--workload", cell, "--seed", seed, "--seconds", secs, "--trace", "1"],
                      {"t_start": T0})
tr = run.result.get("trace") or {"devices": {}, "host": [], "window": [0, 0]}
summary = {"window": tr["window"], "host_spans": collections.Counter(h[0] for h in tr["host"]),
           "devices": {}}
for plane, ev in tr["devices"].items():
    agg = collections.defaultdict(lambda: [0, 0])
    for name, s, d in ev:
        agg[name][0] += 1
        agg[name][1] += d
    top = sorted(agg.items(), key=lambda kv: -kv[1][1])[:80]
    summary["devices"][plane] = {"events": len(ev), "distinct": len(agg), "top": top}
os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
with open(os.path.join(ROOT, "chiprun_out", f"probe_{cell}.json"), "w") as f:
    json.dump(summary, f, indent=1)
# a small slice of the raw events, for the reduction's recorded test trace
with open(os.path.join(ROOT, "chiprun_out", f"trace_{cell}.json"), "w") as f:
    t0 = tr["window"][0]
    keep = {p: [e for e in ev if e[1] < t0 + 200_000_000] for p, ev in tr["devices"].items()}
    json.dump({"window": [t0, min(tr["window"][1], t0 + 200_000_000)], "devices": keep,
               "host": [h for h in tr["host"] if h[1] < t0 + 200_000_000]}, f)
