"""Run a cell several times, one process after another, as the check does,
and report each metric's spread.

    python3 bench/tools/repeat.py <cell> <seconds> <trace> <sets> <seed> [<seed> ...]

Makes ``sets`` sets of runs over the same seeds. Every result line goes to
chiprun_out/repeat_<cell>.jsonl; the summary gives, per metric and set, the
median and the spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    cell, seconds, trace, sets = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
    seeds = sys.argv[5:]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    runs = []
    for s in range(sets):
        for seed in seeds:
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", cell, "--seed", seed,
                 "--seconds", seconds, "--trace", trace],
                cwd=ROOT, capture_output=True, text=True, timeout=1500)
            lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
            res = json.loads(lines[-1]) if lines else None
            row = {"cell": cell, "set": s, "seed": int(seed), "rc": p.returncode,
                   "wall_s": time.monotonic() - t0, "result": res}
            if res is None or not res.get("correct"):
                row["stderr_tail"] = p.stderr[-3000:]
            runs.append(row)
            with open(os.path.join(out_dir, f"repeat_{cell}.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
            brief = {k: v["value"] for k, v in (res or {}).get("metrics", {}).items()}
            print(json.dumps({"set": s, "seed": int(seed), "rc": p.returncode,
                              "correct": (res or {}).get("correct"),
                              "checks": (res or {}).get("checks"),
                              "wall_s": round(row["wall_s"], 1), **brief}), flush=True)
    for s in range(sets):
        ok = [r["result"] for r in runs if r["set"] == s and r["result"]]
        names = sorted({k for r in ok for k in r["metrics"]})
        summary = {}
        for k in names:
            vals = [r["metrics"][k]["value"] for r in ok if k in r["metrics"]]
            if len(vals) >= 2:
                summary[k] = {"median": statistics.median(vals), "spread": spread(vals),
                              "n": len(vals)}
        print(json.dumps({"set": s, "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
