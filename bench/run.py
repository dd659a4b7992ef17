#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name (see
``bench/harness/spec.py``), sets up the system under test on the chip,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared, with its limit. The same checks end standard error.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _paths():
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def _compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    path = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Run:
    """What a driver and a metric reader see of one run."""

    def __init__(self, args, spec, cell, overrides):
        from bench.harness import spec as S

        self.spec = spec
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.overrides = overrides
        self.cfg = {**S.config(spec, cell["config"]), **overrides.get("cfg", {})}
        self.mix = _merge(S.traffic(cell["traffic"]), overrides.get("mix", {}))
        self.limits = {**S.limits(cell["name"]), **overrides.get("limits", {})}
        self.t_start = overrides.get("t_start", T_START)
        self.devices = None
        self.peaks = None
        self.module = S.module
        # filled by the driver: the result, and what the metric readers read
        self.result = {}
        self.layer = {}


def _merge(a: dict, b: dict) -> dict:
    out = dict(a)
    for k, v in b.items():
        out[k] = _merge(a[k], v) if isinstance(v, dict) and isinstance(a.get(k), dict) else v
    return out


def _number(x):
    if x is None or (isinstance(x, float) and not math.isfinite(x)):
        return None
    return x


def main(argv=None, overrides=None) -> dict:
    """Run a cell and print its result; returns the result."""
    return run_cell(argv, overrides)[0]


def make_run(argv=None, overrides=None) -> Run:
    """Parse the arguments, find the cell's files and its chips."""
    overrides = overrides or {}
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    from bench.harness import device, spec as S

    spec = S.benchmark()
    cell = S.workload(spec, args.workload)
    if not overrides.get("allow_cpu"):
        _compile_cache()
    run = Run(args, spec, cell, overrides)
    try:
        devs = device.require(cell["chips"])
    except device.NoChip as e:
        if not overrides.get("allow_cpu"):
            print(f"bench: {e}", file=sys.stderr, flush=True)
            raise SystemExit(2)
        import jax

        devs = jax.devices()[: cell["chips"]]
    run.devices = devs
    kind = devs[0].device_kind
    run.peaks = device.PEAKS.get(kind) if overrides.get("allow_cpu") else device.peaks(kind)
    return run


def run_cell(argv=None, overrides=None):
    """Run a cell and print its result; returns (result, the Run)."""
    run = make_run(argv, overrides)
    from bench.harness import device, spec as S

    spec, cell, devs = run.spec, run.cell, run.devices
    driver = S.module("drivers", run.mix["kind"])
    driver.run(run)
    res = run.result

    group = "per_layer" if run.trace else "end_to_end"
    metrics = {}
    for m in S.cell_metrics(spec, cell, group):
        if group == "end_to_end":
            v = res["e2e"].get(m["name"])
        else:
            v = S.metric_reader(m["name"])(run)
        v = _number(v)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = res["checks"]
    correct = all(c["ok"] for c in checks.values()) and res["failed"] == 0
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = res.get("memory_peak_bytes")
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": dev}
    if run.trace and res.get("trace"):
        from bench.harness import trace as T

        dev["busy_s"] = T.busy_seconds(res["trace"])
        dev["window_s"] = T.window_seconds(res["trace"])
        out["breakdown"] = T.breakdown(res["trace"])
    out["extra"] = res.get("extra", {})
    out["checks"] = {k: {"value": _number(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out, run


if __name__ == "__main__":
    main()
