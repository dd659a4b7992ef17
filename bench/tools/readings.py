"""The readings a cell's correctness limits are set from, on many seeds in
one process (no measured window; a serving cell runs a short one at its
own load). Per seed, one JSON line:

* ``program``: the checks of the program as the configuration states it;
* ``control``: the reference put in the program's place, computed with
  bfloat16 matmuls (one precision below the configuration's float32);
* ``control_program``: the program's own bfloat16 path (policy
  floatsd8_tpu) in place of the configuration's policy;
* training only, faults planted in the reference put in the program's
  place: ``half_batch`` (the mean over half of the rows) and ``frozen``
  (a step that returns its state unchanged).

    python3 bench/tools/readings.py <cell> <seconds> <n> <seed> [<seed> ...]

The controls and faults are read on the first ``n`` seeds, the program on
all.
"""
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import bench.run as R  # noqa: E402


def serve_seed(r, seconds, controls=True):
    from bench.drivers import serve
    from bench.harness import traffic

    out = {}
    runs = [("program", {})] + ([("control_program", {"policy": "floatsd8_tpu"})]
                                if controls else [])
    for label, over in runs:
        try:
            r.overrides.update(over)
            st = serve.setup(r)
            sched = traffic.serve_schedule(r.mix, r.seed, seconds, r.cfg["vocab"])
            w = serve.open_loop(st, sched, seconds)
            s = serve.summarize(w)
            sample = serve._sample(r, w["recs"], s["done"], sched["prompts"])
            weights = st["weights"]
            st.clear()
            gc.collect()
            ref = r.module("reference", r.cfg["reference"])
            dtype = "bfloat16" if label == "program" and controls else "float32"
            gap, own = ref.serve_gaps(r.cfg, weights, sample, dtype=dtype)
            out[label] = {"logit_gap": float(np.max(gap)), "tokens": int(len(gap)),
                          "failed": s["failed"]}
            if label == "program" and controls:
                out["control"] = {"logit_gap": float(np.max(own)),
                                  "flipped": int(np.sum(own > 0))}
        except Exception as e:  # a control that crashes has failed
            out[label] = {"error": f"{type(e).__name__}: {e}"[:400]}
            traceback.print_exc()
        finally:
            r.overrides.pop("policy", None)
    return out


def train_seed(r, controls=True):
    from bench.drivers import train

    out = {}
    st = train.setup(r)
    names, losses, grad1, moved = st["readings"]
    for k in ("step", "state", "batches"):
        st.pop(k)
    gc.collect()
    ref = train.reference_readings(r, st)
    out["program"], out["program_diag"] = train.compare(r.limits, names, losses, grad1,
                                                         moved, *ref)
    out["program_finite"] = st["finite"]
    if not controls:
        return out
    rl, rg, rc = ref
    names_ref = [train._leaf(k) for k in rg]

    def as_prog(readings):
        l2, g2, c2 = readings
        return (l2, [float(np.linalg.norm(np.asarray(g2[k], np.float64))) for k in g2],
                [float(np.linalg.norm(np.asarray(c2[k], np.float64))) for k in c2])

    for label, fault in (("half_batch", {"rows_used": st["rows"] // 2}),
                         ("frozen", {"freeze": True}),
                         ("control", {"dtype": "bfloat16"})):
        l2, g2, c2 = as_prog(train.reference_readings(r, st, **fault))
        out[label], out[label + "_diag"] = train.compare(r.limits, names_ref, l2, g2, c2,
                                                         rl, rg, rc)
    try:
        r.overrides["policy"] = "floatsd8_tpu"
        st2 = train.setup(r)
        n2, l2, g2, c2 = st2["readings"]
        for k in ("step", "state", "batches"):
            st2.pop(k)
        gc.collect()
        out["control_program"], out["control_program_diag"] = train.compare(
            r.limits, n2, l2, g2, c2, rl, rg, rc)
    except Exception as e:
        out["control_program"] = {"error": f"{type(e).__name__}: {e}"[:400]}
        traceback.print_exc()
    finally:
        r.overrides.pop("policy", None)
    return out


def main():
    cell, seconds, n_ctl, seeds = sys.argv[1], float(sys.argv[2]), int(sys.argv[3]), sys.argv[4:]
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.monotonic()
        r = R.make_run(["--workload", cell, "--seed", seed, "--seconds", str(seconds)],
                       {"t_start": t0})
        ctl = i < n_ctl
        row = serve_seed(r, seconds, ctl) if r.mix["kind"] == "serve" else train_seed(r, ctl)
        row = {"seed": int(seed), "s": time.monotonic() - t0, **row}
        rows.append(row)
        print(json.dumps(row, default=float), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"readings_{cell}.json"), "a") as f:
        for row in rows:
            f.write(json.dumps(row, default=float) + "\n")


if __name__ == "__main__":
    main()
