"""The ring cell on the CPU: ``drivers/allknn_ring.py`` through ``run.py
--allow-cpu`` in a temporary copy at a few thousand rows on four virtual
devices, a wrong neighbour planted on one shard seen as not correct, and
the three ``ring_*`` readers on hand-built event lists (a hidden permute
against an exposed one; the worst chip)."""

import json
import os

import pytest

from benchmark import opcount, opcount_ring
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "ring4-mnist8m"
ROWS, SLICE = 16384, 2048

PLANTED = '''"""allknn_ring with a wrong neighbour planted on one shard: every
neighbour the program names in the second chip's rows becomes the row after
it. Shapes, order, finiteness and distances stay sound."""
import numpy as np
from benchmark.harness import load_by_path

def run(cell, args, t_start):
    from mpi_knn_tpu import api
    real = api.all_knn
    lo = cell["config"]["rows"] // cell["chips"]
    def all_knn(*a, **kw):
        res = real(*a, **kw)
        ids = np.asarray(res.ids)
        there = (ids >= lo) & (ids < 2 * lo - 1)
        return type(res)(dists=res.dists, ids=np.where(there, ids + 1, ids))
    api.all_knn = all_knn
    return load_by_path("drivers", "allknn_ring").run(cell, args, t_start)
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("ring")))
    b = os.path.join(root, "benchmark")

    def ring(c):
        c["rows"] = ROWS
        c["knn"].update(query_tile=512, corpus_tile=1024)
        c["control"]["rows"] = ROWS

    small_copy.edit_json(
        os.path.join(b, "configs", "mnist8m-784-l2-ring4.json"), ring)
    small_copy.edit_json(
        os.path.join(b, "traffic", "allknn-ring-sweep.json"),
        lambda t: t.update(slice_rows=SLICE, trace_seconds=0.5))
    return root


@pytest.fixture
def four_devices(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")


def test_ring_cell_end_to_end_line(copy, four_devices):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["count"] == 4
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    assert "check recall_at_k" in out and "check dist_rel_err_max" in out
    assert f"{ROWS // 4} rows a chip" in out and "4 shards" in out


def test_ring_cell_traced_line(copy, four_devices):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    # at least these: a later PR may add a metric to the cell's list
    assert {"ring_collective_exposed_pct", "ring_wire_gbps",
            "ring_tile_roofline", "device_idle_pct.tput",
            "call_host_gap_ms"} <= allowed
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    assert last["correct"] is True


def test_fewer_devices_than_chips_is_refused(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=1.0)
    assert rc != 0 and last is None


def test_a_wrong_neighbour_planted_on_one_shard_is_not_correct(
        copy, four_devices):
    b = os.path.join(copy, "benchmark")
    with open(os.path.join(b, "drivers", "allknn_ring_planted.py"), "w") as f:
        f.write(PLANTED)
    mix = os.path.join(b, "traffic", "allknn-ring-sweep.json")
    small_copy.edit_json(mix, lambda t: t.update(driver="allknn_ring_planted"))
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=1.0)
    finally:
        small_copy.edit_json(mix, lambda t: t.update(driver="allknn_ring"))
    assert rc == 0, out[-3000:]
    assert last["correct"] is False
    assert "check recall_at_k" in out and "FAILED" in out


# ---- the readers, on two chips' events inside a 10 s span ----------------

PERMUTE = "collective-permute"
HIDDEN = [  # the block arrives under the round's compute
    ("%while.1 while (s32[], f32[2,8,4])", 0.0, 10.0),
    (f"%{PERMUTE}-start {PERMUTE}-start (f32[2,8,4], f32[2,8,4])", 0.0, 0.1),
    (f"%{PERMUTE}-start.1 {PERMUTE}-start (s32[2,8], s32[2,8])", 0.1, 0.1),
    ("%fusion.3 fusion f32[8,8]", 0.2, 3.8),
    (f"%{PERMUTE}-done.1 {PERMUTE}-done s32[2,8]", 4.0, 0.02),
    (f"%{PERMUTE}-done {PERMUTE}-done f32[2,8,4]", 4.02, 0.03),
    ("%fusion.4 fusion f32[8,8]", 4.05, 3.95),
]
EXPOSED = [  # the chip waits three seconds for it
    ("%while.1 while (s32[], f32[2,8,4])", 0.0, 9.0),
    (f"%{PERMUTE}-start {PERMUTE}-start (f32[2,8,4], f32[2,8,4])", 0.0, 0.1),
    ("%fusion.3 fusion f32[8,8]", 0.1, 1.9),
    (f"%{PERMUTE}-done {PERMUTE}-done f32[2,8,4]", 2.0, 3.0),
    ("%fusion.4 fusion f32[8,8]", 5.0, 4.0),
]


def record(**ring):
    return {"ring": {"chips": 2, "window": [0.0, 10.0],
                     "events": [HIDDEN, EXPOSED],
                     "counters_delta": {"ring_wire_bytes_total": 20e9},
                     "chip_least_s": 1.8, **ring}}


def read(name, run):
    return load_by_path("layer_metrics", name).read(run)


def test_exposed_share_counts_permutes_no_compute_covers():
    red = load_by_path("layer_metrics", "ring_collective_exposed_pct")
    assert red.seconds(red.exposed(HIDDEN)) == pytest.approx(0.25)
    assert red.seconds(red.exposed(EXPOSED)) == pytest.approx(3.1)
    assert read("ring_collective_exposed_pct", record()) == pytest.approx(
        100.0 * (0.025 + 0.31) / 2)
    # a compute operation over part of a wait hides that part
    over = EXPOSED + [("%fusion.9 fusion f32[8]", 2.5, 1.0)]
    assert red.seconds(red.exposed(over)) == pytest.approx(2.1)


def test_wire_rate_is_a_chips_bytes_over_its_time_in_flight():
    red = load_by_path("layer_metrics", "ring_collective_exposed_pct")
    assert red.in_flight(HIDDEN) == [(0.0, 4.05)]  # start to its own done
    assert red.in_flight(EXPOSED) == [(0.0, 5.0)]
    assert read("ring_wire_gbps", record()) == pytest.approx(
        (10.0 / 4.05 + 10.0 / 5.0) / 2)


def test_ring_roofline_is_the_worst_chips():
    # busy 10 s on the first chip, 9 s on the second: the first is worst
    assert read("ring_tile_roofline", record()) == pytest.approx(18.0)


@pytest.mark.parametrize("name", ["ring_collective_exposed_pct",
                                  "ring_wire_gbps", "ring_tile_roofline"])
def test_readers_return_nothing_where_there_is_nothing(name):
    assert read(name, {}) is None
    assert read(name, {"ring": None}) is None
    assert read(name, record(events=[])) is None
    # the parent's program: a trace, no counters, no named permutes to read
    bare = record(counters_delta=None, chip_least_s=None, events=[
        [("%fusion.3 fusion f32[8,8]", 0.0, 1.0)]])
    assert read(name, bare) is None


def test_a_chips_share_of_the_work():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    q, calls, chips, rows, dim, k = 5 * 16384, 5, 4, 4194304, 784, 10
    assert opcount_ring.chip_flops(q, chips, rows, dim) == pytest.approx(
        opcount.knn_flops(q, rows, dim) / chips)
    # the corpus streams past every chip once a call, whole
    assert opcount_ring.chip_bytes(q, calls, chips, rows, dim, k) > (
        calls * rows * dim * 4)
    least, bound = opcount_ring.chip_least_seconds(
        q, calls, chips, rows, dim, k, peaks)
    assert bound == "compute"
    assert least == pytest.approx(2.0 * q / chips * rows * dim / 197e12)


def test_breakdown_by_the_programs_scopes():
    """``ring_scopes`` on the recorded v5e trace (one matmul program, no
    ``knn.*`` scope in it) and ``scope_key`` on op_names as the ring's
    program writes them."""
    driver = load_by_path("drivers", "allknn_ring")
    ring = "jit(_ring_knn_sharded)/shard_map/while/body/closed_call/knn.ring"
    for op_name, key in [
        (f"{ring}/permute/ppermute:", "knn.ring/permute"),
        (f"{ring}/round/while/body/closed_call/knn.select/bins/pallas_call:",
         "knn.select/bins"),
        (f"{ring}/round/while/body/closed_call/knn.dist/dot_general:",
         "knn.dist"),
        (f"{ring}/round/vmap(knn.norms)/reduce_sum:", "knn.norms"),
        (f"{ring}/round/while:", "knn.ring/round"),
        ("jit(subtract)/sub:", driver.NO_SCOPE),
    ]:
        assert driver.scope_key(op_name) == key
    recorded = os.path.join(os.path.dirname(__file__), "data",
                            "tiny_v5e.xplane.pb")
    (key, seconds), = driver.ring_scopes(recorded, 0.0, 1e9)
    assert key == driver.NO_SCOPE and seconds == pytest.approx(2.728e-4, rel=1e-3)
    assert driver.ring_scopes(recorded, 0.0, 1e-9) is None  # nothing inside
