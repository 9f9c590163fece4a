"""BENCHMARK.json against the limits of its contract that a file can show,
and against the files it names."""

import json
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_configs_name_their_files_and_sources():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
        doc = json.load(open(os.path.join(REPO, c["file"])))
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert "control" in doc and "limits" in doc and "guarantees" in doc
    assert len({c["file"] for c in BENCH["configs"]}) == len(names)
    assert len({c["source"] for c in BENCH["configs"]}) == len(names)


def test_cells_take_one_chip_or_four_and_name_files_that_exist():
    configs = {c["name"] for c in BENCH["configs"]}
    seen = set()
    # four chips in at most a quarter of the cells, rounded down; one always
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        mix = json.load(open(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "drivers", mix["driver"] + ".py"))
    assert {w["config"] for w in BENCH["workloads"]} == configs


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
        for cell in m["workloads"]:  # each reports the metric it moves
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:  # setup_s, one more end-to-end, one per-layer
        assert any(cell in m.get("workloads", cells) and m["name"] != "setup_s"
                   for m in BENCH["end_to_end"])
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    root = os.path.join(REPO, "benchmark")
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("out", "__pycache__",
                                                 ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), REPO)
            assert ok.match(rel) and len(rel) <= 200, rel
