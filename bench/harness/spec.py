"""BENCHMARK.json and the files it names, found by name.

A cell ("workload") names a configuration and a traffic mix. The
configuration's entry in BENCHMARK.json gives its file; the mix is
``bench/traffic/<traffic>.json``; its ``kind`` picks the driver
``bench/drivers/<kind>.py``; the configuration names its program adapter
``bench/models/<model>.py`` and its plain reference
``bench/reference/<reference>.py``; each per-layer metric is read by
``bench/metrics/<metric>.py``; a cell's correctness limits are in
``bench/limits/<cell>.json``. Adding a cell, a mix or a metric adds files.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    return _named(spec["workloads"], name, "workload")


def config(spec: dict, name: str) -> dict:
    entry = _named(spec["configs"], name, "config")
    return load_json(os.path.join(ROOT, entry["file"]))


def traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def limits(cell: str) -> dict:
    return load_json(os.path.join(BENCH, "limits", f"{cell}.json"))


def module(kind: str, name: str):
    """bench/<kind>/<name>.py (drivers, models, reference)."""
    if not NAME.match(name):
        raise ValueError(f"bad module name {name!r}")
    return importlib.import_module(f"bench.{kind}.{name}")


def metric_reader(name: str):
    """bench/metrics/<name>.py; its ``read(run)`` gives the value or None."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: dict, group: str) -> list:
    """The metrics of ``group`` ("end_to_end" or "per_layer") this cell
    reports: those listing it under ``workloads``, or, with no such key,
    those of an end-to-end metric the cell reports."""
    e2e = [m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    out = []
    for m in spec[group]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out
