"""BENCHMARK.json against the rules of its format, and the harness
finding every file by name."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_names_and_units():
    b = load()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])


def test_metrics_and_cells_are_consistent():
    b = load()
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert w in moved.get("workloads", cells), (m["name"], w)
        layers.setdefault(m["layer"], m["layer"])
    for w in cells:
        reports = [m for m in b["end_to_end"]
                   if w in m.get("workloads", cells)]
        assert any(m["name"] == "setup_s" for m in reports)
        assert len(reports) >= 2, w
        assert any(w in m["workloads"] for m in b["per_layer"]), w


def test_every_file_loads_by_name():
    from bench import run

    b = load()
    for w in b["workloads"]:
        spec = run.Spec.load(w["name"], ROOT)
        kind = run.load_module(os.path.join(
            ROOT, "bench", "kinds", spec.traffic["kind"] + ".py"))
        assert hasattr(kind, "Cell")
    for m in b["end_to_end"] + b["per_layer"]:
        mod = run.load_module(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert callable(mod.read)


def test_a_new_cell_and_metric_need_no_edit(tmp_path, capsys):
    """Dropping in a traffic file, a metric reader and their entries in
    BENCHMARK.json is all a new cell takes."""
    from conftest import make_tiny, run_cell

    root = make_tiny(str(tmp_path))
    shutil.copy(os.path.join(root, "bench", "traffic", "fig7.json"),
                os.path.join(root, "bench", "traffic", "fig7b.json"))
    with open(os.path.join(root, "bench", "metrics", "grids.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.window.units\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append({"name": "fig7b.new", "config": "embench-core",
                           "traffic": "fig7b", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "grids", "unit": "grids",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["fig7b.new"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    res = run_cell(root, "fig7b.new", 5, capsys=capsys)
    assert res["correct"]
    assert res["metrics"]["grids"]["value"] == res["attempted"]


@pytest.mark.parametrize("config", ["embench-core"])
def test_config_files_state_their_reductions(config):
    b = load()
    c = next(c for c in b["configs"] if c["name"] == config)
    with open(os.path.join(ROOT, c["file"])) as f:
        data = json.load(f)
    assert data["name"] == config
    assert data["reduced"] == c["reduced"]
