"""``run.py`` end to end on the CPU, in a temporary copy of the benchmark at
a few thousand rows (widths as published): the three cells' result lines,
the refusal without a chip, a throw-away configuration / mix / cell / metric
added as files and entries only, and a broken timed path seen as not
correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.tests import small_copy

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELLS = {
    "allknn-mnist8m": {"rows_per_s", "setup_s"},
    "serve-bigann10m-small": {"request_p50_ms", "setup_s"},
    "serve-bigann10m-bulk": {"rows_per_s", "setup_s"},
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return small_copy.make(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_end_to_end_line(copy, cell):
    rc, last, out = small_copy.run_cell(copy, cell, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == CELLS[cell]
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "check recall_at_k" in out and "check dist_rel_err_max" in out
    assert "chip_wait_s" in out  # taken out of setup_s, and said so
    # the copy's files were used, not the repo's
    assert not os.path.exists(os.path.join(small_copy.BENCH, "out"))


@pytest.mark.parametrize("cell", ["allknn-mnist8m", "serve-bigann10m-small"])
def test_traced_line_holds_per_layer_metrics_only(copy, cell):
    rc, last, out = small_copy.run_cell(copy, cell, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    assert KEYS <= set(last) <= KEYS | {"breakdown"}
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    if cell == "serve-bigann10m-small":
        assert {"engine_batch_ms", "batch_rows_mean", "loadgen_late_ms",
                "request_tail_p95_ms"} <= set(last["metrics"])


def test_no_chip_no_result(copy):
    """Without the test hook the CPU is refused: non-zero, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=small_copy.REPO)
    for cell in ("allknn-mnist8m", "serve-bigann10m-bulk"):
        p = subprocess.run(
            [sys.executable, os.path.join(copy, "benchmark", "run.py"),
             "--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0"],
            cwd=copy, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert '"correct"' not in p.stdout


def test_bare_benchmark_directory_fails(copy):
    """Only BENCHMARK.json and benchmark/: the program is not there."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmark", "run.py"),
         "--workload", "allknn-mnist8m", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--allow-cpu"],
        cwd=copy, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_new_config_mix_cell_and_metric_are_files_and_entries_only(copy):
    """A later PR adds a configuration, a traffic mix, a cell and a
    per-layer metric without editing a file that is there."""
    b = os.path.join(copy, "benchmark")
    before = {}
    for d, _, files in os.walk(b):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(d, f)
                before[p] = open(p, "rb").read()
    cfg = json.load(open(os.path.join(b, "configs", "mnist8m-784-l2.json")))
    cfg.update(name="throwaway-96", rows=4096, dim=96)
    json.dump(cfg, open(os.path.join(b, "configs", "throwaway-96.json"), "w"))
    mix = json.load(open(os.path.join(b, "traffic", "allknn-sweep.json")))
    mix.update(name="throwaway-sweep", slice_rows=256, probe_rows=64)
    json.dump(mix, open(os.path.join(b, "traffic", "throwaway-sweep.json"), "w"))
    with open(os.path.join(b, "layer_metrics", "calls_traced.py"), "w") as f:
        f.write("def read(run):\n"
                "    return float(len(run['traced_call_walls_s'] or [])) or None\n")
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "throwaway-96", "source": "test", "reduced": [],
        "file": "benchmark/configs/throwaway-96.json", "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-cell", "config": "throwaway-96",
        "traffic": "throwaway-sweep", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "rows_per_s":
            m["workloads"].append("throwaway-cell")
    bench["per_layer"].append({
        "name": "calls_traced", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "one-shot API",
        "moves": "rows_per_s", "workloads": ["throwaway-cell"]})
    json.dump(bench, open(os.path.join(copy, "BENCHMARK.json"), "w"))
    try:
        rc, last, out = small_copy.run_cell(copy, "throwaway-cell", seconds=1.0)
        assert rc == 0 and last["correct"], out[-3000:]
        assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
        rc, last, out = small_copy.run_cell(copy, "throwaway-cell",
                                            seconds=1.0, trace=1)
        assert rc == 0, out[-3000:]
        assert last["metrics"]["calls_traced"]["value"] >= 1
        for p, content in before.items():
            assert open(p, "rb").read() == content, p
    finally:
        small_copy.shutil.copy(
            os.path.join(small_copy.REPO, "BENCHMARK.json"), copy)


# the timed path broken underneath: the rest of a run is driven in this
# process (the look for a chip skipped) and has to come out not correct


def drive_allknn(copy, monkeypatch, break_it):
    sys.path.insert(0, copy)
    for name in [m for m in sys.modules if m.split(".")[0] == "benchmark"]:
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.syspath_prepend(copy)
    import importlib

    run_mod = importlib.import_module("benchmark.run")
    harness_mod = importlib.import_module("benchmark.harness")
    bench = run_mod.read_json(os.path.join(copy, "BENCHMARK.json"))
    cell = run_mod.resolve_cell(bench, "allknn-mnist8m")
    driver = harness_mod.load_by_path("drivers", "allknn")
    from mpi_knn_tpu import api

    real = api.all_knn
    monkeypatch.setattr(api, "all_knn", break_it(real))
    args = run_mod.argparse.Namespace(
        workload="allknn-mnist8m", seed=2**31 + 5, seconds=1.0, trace=0,
        allow_cpu=True, control=False)
    return driver.run(cell, args, run_mod.time.time())


def test_sound_path_in_process_is_correct(copy, monkeypatch):
    assert drive_allknn(copy, monkeypatch, lambda real: real)["correct"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        copy, monkeypatch):
    def break_it(real):
        def all_knn(*a, **kw):
            res = real(*a, **kw)
            # every neighbour list shifted by one row: shapes, order and
            # finiteness stay sound, the answers are another row's
            return type(res)(dists=res.dists,
                             ids=np.roll(np.asarray(res.ids), 1, axis=0))
        return all_knn

    out = drive_allknn(copy, monkeypatch, break_it)
    assert out["correct"] is False


def test_part_of_the_batch_left_out_is_not_correct(copy, monkeypatch):
    def break_it(real):
        def all_knn(corpus, *a, **kw):
            # the second half of the corpus never searched
            return real(corpus[: corpus.shape[0] // 2], *a, **kw)
        return all_knn

    out = drive_allknn(copy, monkeypatch, break_it)
    assert out["correct"] is False
