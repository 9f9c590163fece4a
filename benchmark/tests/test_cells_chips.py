"""The contract on a cell's chips as it stands: 1 or 4; of a benchmark's
cells at most a quarter, rounded down, and always one, may ask for 4; a
four-chip cell's configuration names a program over four devices."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_chips_are_one_or_four_and_four_chip_cells_are_few():
    cells = BENCH["workloads"]
    assert all(w["chips"] in (1, 4) for w in cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_a_four_chip_cell_runs_a_four_device_program():
    files = {c["name"]: c["file"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        doc = json.load(open(os.path.join(REPO, files[w["config"]])))
        devices = doc["knn"].get("num_devices")
        if w["chips"] == 4:
            assert devices == 4, w["name"]
        else:
            assert devices in (None, 1), w["name"]


def test_cells_name_files_that_exist():
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and 1 <= len(w["why"]) <= 200
        mix = json.load(open(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "drivers", mix["driver"] + ".py"))
    assert {w["config"] for w in BENCH["workloads"]} == configs
