"""BENCHMARK.json: every cell finds its files by name, and every name and
unit keeps to the characters allowed."""
import os

import pytest

from bench.harness import spec as S

B = S.benchmark()
CELLS = [w["name"] for w in B["workloads"]]


def test_keys_and_names():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in B[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert S.NAME.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert S.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert {m["name"] for m in B["end_to_end"]} == {
        "ttft_p95_ms", "itl_mean_ms", "itl_p95_ms", "train_tokens_per_s", "setup_s"}


def test_entries_have_exactly_their_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert w["chips"] in (1, 4)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}, m
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}, m
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    w = S.workload(B, cell)
    assert S.NAME.match(w["config"]) and S.NAME.match(w["traffic"])
    cfg = S.config(B, w["config"])
    mix = S.traffic(w["traffic"])
    S.module("drivers", mix["kind"])
    S.module("models", cfg["model"])
    S.module("reference", cfg["reference"])
    assert S.limits(cell)
    e2e = [m["name"] for m in S.cell_metrics(B, w, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = S.cell_metrics(B, w, "per_layer")
    assert layer
    for m in layer:
        assert callable(S.metric_reader(m["name"]))
        assert m["moves"] in e2e


def test_metrics_name_known_cells_and_layers():
    e2e = {m["name"] for m in B["end_to_end"]}
    for m in B["end_to_end"] + B["per_layer"]:
        for c in m.get("workloads", []):
            assert c in CELLS
    for m in B["per_layer"]:
        assert m["moves"] in e2e and m["layer"].strip()
        assert os.path.exists(os.path.join(S.BENCH, "metrics", m["name"] + ".py"))


def test_config_files_are_under_paths():
    for c in B["configs"]:
        assert any(c["file"].startswith(p + "/") for p in B["paths"])
        assert S.workload  # every config is used by a cell
        assert any(w["config"] == c["name"] for w in B["workloads"])


def test_text_fields_fit_on_one_line():
    texts = [w["why"] for w in B["workloads"]] + [m["layer"] for m in B["per_layer"]]
    texts += [c["source"] for c in B["configs"]] + [c["why"] for c in B["configs"]]
    texts += list(B["command"])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert 1 <= B["run_seconds"] <= 51 and len(B["command"]) <= 32
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
