"""BENCHMARK.json against the shape its contract fixes, and against
the files the harness will look for."""

import json
import os
import re

import harness
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith("benchmark/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        moved = next(e for e in b["end_to_end"]
                     if e["name"] == m["moves"])
        # every cell the metric lists reports what it moves
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)


def test_every_entry_has_its_file():
    b = bench()
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        gen = "gen_" + cfg["matrix"]["generator"] + ".py"
        assert os.path.exists(os.path.join(BENCH, "configs", gen))
    for w in b["workloads"]:
        path = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(path) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(BENCH, "kinds", kind + ".py"))
    for m in b["per_layer"]:
        assert hasattr(harness.metric_reader(m["name"]), "read")


def test_every_cell_reports_enough():
    b = bench()
    for w in b["workloads"]:
        def mine(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = [m["name"] for m in b["end_to_end"] if mine(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(mine(m) for m in b["per_layer"])
