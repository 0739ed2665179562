"""Every name in BENCHMARK.json resolves to a file: a cell, a
configuration, a traffic mix and a per-layer metric are each new files
plus one entry."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_command_and_paths():
    b = bench()
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark"]
    assert os.path.isfile(os.path.join(ROOT, b["command"][1]))


def test_configurations_resolve():
    b = bench()
    for c in b["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert os.path.isfile(path), path
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            BENCH, "cases", cfg["template"] + ".xml"))
        importlib.import_module("benchmark.reference." + cfg["reference"])
        assert os.path.basename(c["file"]) == c["name"] + ".json"


def test_cells_resolve():
    b = bench()
    configs = {c["name"] for c in b["configs"]}
    used = set()
    for w in b["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        with open(os.path.join(BENCH, "configs", w["config"] + ".json")) as f:
            assert json.load(f)["chips"] == w["chips"]
    assert used == configs
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_metrics_resolve():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        mod = importlib.import_module("benchmark.layer_metrics." + m["name"])
        assert callable(mod.read)
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells


def test_run_py_has_no_table_of_cells():
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    b = bench()
    for name in ([w["name"] for w in b["workloads"]]
                 + [c["name"] for c in b["configs"]]
                 + [m["name"] for m in b["per_layer"]]):
        assert name not in text, name
