"""The clustered (IVF-Flat) serving cell on the CPU: ``drivers/serve_ivf.py``
and ``serve_launcher_ivf.py`` through ``run.py --allow-cpu`` in a temporary
copy at a few thousand rows (the width as published; 16 lists, 4 probed),
the five faults planted and each seen as not correct by the number that is
there to catch it, the comparison on hand-made answers, the five ``ivf_*``
readers on a hand-built ``run`` record, and ``opcount_ivf``."""

import json
import os

import numpy as np
import pytest

from benchmark import opcount_ivf
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "serve-bigann10m-ivf-bulk"
CONFIG = "bigann10m-128-l2-ivf4096"
READERS = ("ivf_probe_roofline", "ivf_score_us_per_row",
           "ivf_gather_us_per_row", "ivf_rerank_us_per_row",
           "ivf_slots_per_live_row")
LIMITS = {"recall_min": 0.9, "tie_rtol": 1e-5,
          "returned_dist_rel_err_max": 8e-6, "corpus_rows": 1000}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("ivf")))
    b = os.path.join(root, "benchmark")

    def cut(c):
        c["rows"] = 16384
        c["data"]["centres"] = 8  # two lists a class, as 4096 over 256
        c["knn"].update(partitions=16, nprobe=4, kmeans_sample=4096,
                        kmeans_iters=4)
        c["slo"].update(max_batch_rows=256)

    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"), cut)
    small_copy.edit_json(
        os.path.join(b, "traffic", "bulk-saturated-ivf.json"),
        lambda t: t.update(
            trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[256],
            rows_per_request={"law": "fixed", "rows": 256}))
    return root


def test_ivf_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    for name in ("recall_at_k", "returned_dist_rel_err_max",
                 "impossible_distances", "duplicate_or_dead_ids",
                 "degraded_batches", "compiled_in_window",
                 "answers_misshapen_or_failed"):
        assert f"check {name}: " in out, name
    assert "FAILED" not in out
    assert "launcher: reference (100 nearest) for 256 probe rows" in out
    about = json.loads(out.split("launcher: index ", 1)[1].splitlines()[0])
    assert about["partitions"] == 16 and about["nprobe"] == 4
    assert set(about["phases_s"]) == {"index-build", "ivf-train",
                                      "ivf-assign", "ivf-fill"}
    assert 0 < about["fill_pct"] <= 100


def test_ivf_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    assert allowed >= {"device_idle_pct.tput", "server_empty_pct",
                       "dispatch_lag_ms.tput", "request_edge_ms.tput",
                       *READERS}
    assert "tile_roofline" not in allowed  # it would count a full scan
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    # what needs no trace is there: the program's own counter
    assert last["metrics"]["ivf_slots_per_live_row"]["value"] >= 1
    assert last["correct"] is True


@pytest.mark.parametrize("fault,caught_by", [
    ("nprobe", "recall_at_k"),
    ("rerank_default", "returned_dist_rel_err_max"),
    ("empty_partition", "recall_at_k"),
    ("duplicate_row", "duplicate_or_dead_ids"),
    ("degraded_batch", "degraded_batches"),
])
def test_planted_fault_is_not_correct(copy, fault, caught_by, monkeypatch):
    monkeypatch.setenv("IVF_CONTROL", fault)
    rc, last, out = small_copy.run_cell(copy, CELL, "--control",
                                        seconds=1.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    failed = [ln.split()[1].rstrip(":") for ln in out.splitlines()
              if ln.startswith("check ") and ln.endswith("FAILED")]
    assert caught_by in failed, out[-3000:]


# ---- the comparison, on hand-made answers --------------------------------

def answers(n=4, k=10, r=100):
    """A reference of ``r`` neighbours a row at distances 1000, 1010, ...
    and the answers that are its first ``k``."""
    ref_ids = np.arange(n * r, dtype=np.int64).reshape(n, r) % 1000
    ref_d = np.tile(1000.0 + 10.0 * np.arange(r), (n, 1))
    return ref_ids[:, :k].copy(), ref_d[:, :k].copy(), ref_ids, ref_d


def verdict(ids, d, ref_ids, ref_d, **limits):
    return load_by_path("drivers", "serve_ivf").compare_ivf(
        ids, d, ref_ids, ref_d, {**LIMITS, **limits})


def test_exact_answers_pass():
    v = verdict(*answers())
    assert v["ok"], v["numbers"]
    assert v["numbers"]["recall_at_k"][0] == 1.0


def test_missed_neighbours_cost_recall_and_nothing_else():
    ids, d, ref_ids, ref_d = answers()
    # every row misses its nearest two and returns the 11th and 12th
    ids = ref_ids[:, 2:12].copy()
    d = ref_d[:, 2:12].copy()
    v = verdict(ids, d, ref_ids, ref_d)
    assert v["numbers"]["recall_at_k"][0] == pytest.approx(0.8)
    assert not v["numbers"]["recall_at_k"][2]
    assert v["numbers"]["returned_dist_rel_err_max"][0] == 0.0
    assert v["numbers"]["impossible_distances"][0] == 0
    assert verdict(ids, d, ref_ids, ref_d, recall_min=0.8)["ok"]


def test_a_tie_at_the_kth_distance_is_a_hit():
    ids, d, ref_ids, ref_d = answers()
    ref_d[:, 10] = ref_d[:, 9]  # the 11th ties the 10th
    ids[:, 9] = ref_ids[:, 10]
    assert verdict(ids, d, ref_ids, ref_d)["numbers"]["recall_at_k"][0] == 1.0


def test_a_distance_of_another_row_is_caught_though_the_id_is_right():
    ids, d, ref_ids, ref_d = answers()
    d[0, 3] *= 1.0 + 5e-5  # still ascending, still the right id
    v = verdict(ids, d, ref_ids, ref_d)
    assert v["numbers"]["recall_at_k"][2]
    assert not v["numbers"]["returned_dist_rel_err_max"][2]
    assert v["numbers"]["returned_dist_rel_err_max"][0] == pytest.approx(
        5e-5, rel=1e-6)


def test_a_row_claimed_nearer_than_it_can_be_is_caught():
    ids, d, ref_ids, ref_d = answers()
    ids[1, 9] = 777 if 777 not in ref_ids[1] else 778
    v = verdict(ids, d, ref_ids, ref_d)
    assert v["numbers"]["impossible_distances"][0] == 1
    assert not v["ok"]
    # at or beyond the reference's last it is merely a miss
    d[1, 9] = ref_d[1, -1] + 1.0
    v = verdict(ids, d, ref_ids, ref_d)
    assert v["numbers"]["impossible_distances"][0] == 0


def test_duplicates_dead_ids_and_disorder_are_counted():
    ids, d, ref_ids, ref_d = answers()
    ids[0, 1] = ids[0, 0]
    ids[2, 5] = -1
    ids[3, 5] = 1000  # the corpus has rows 0..999
    d[1, [4, 5]] = d[1, [5, 4]]
    v = verdict(ids, d, ref_ids, ref_d)
    assert v["numbers"]["duplicate_or_dead_ids"][0] == 3
    assert v["numbers"]["not_finite_or_not_ascending"][0] == 1
    v = load_by_path("drivers", "serve_ivf").compare_ivf(
        *answers(), LIMITS, counted=lambda: 2)
    assert v["numbers"]["duplicate_or_dead_ids"] == [2, 0, False]


def test_misshapen_answers_are_refused():
    ids, d, ref_ids, ref_d = answers()
    assert not verdict(ids[:, :5], d, ref_ids, ref_d)["ok"]
    assert not verdict(ids, d, ref_ids[:, :5], ref_d[:, :5])["ok"]


def test_the_two_names_say_whether_a_run_went_through_them(tmp_path):
    """``drive`` finds this cell's comparison and log by module attribute;
    ``run`` reads ``went`` and prints ``"correct": false`` for a run that
    went past either."""
    from benchmark import compare, loadgen

    ivf = load_by_path("drivers", "serve_ivf")
    theirs = (loadgen.Log, compare.compare_answers)
    with open(tmp_path / "final.json", "w") as f:
        json.dump({"degradations": 0, "rung": "full"}, f)
    config = {"limits": LIMITS, "rows": 1000}
    with ivf.clustered_checks(config, str(tmp_path)) as went:
        assert not went["logged"] and not went["compared"]
        loadgen.Log(0, 4096, 10).record(
            due=0.0, sent=0.0, done=0.1, status=503, rows=4, offset=0,
            tenant=0, doc=None)
        assert went["logged"] and not went["compared"]
        v = compare.compare_answers(*answers(), {"anything": 1})
        assert went["compared"] and v["ok"]
        assert v["numbers"]["degraded_batches"] == [0, 0, True]
    assert (loadgen.Log, compare.compare_answers) == theirs


# ---- the readers, on a hand-built record ----------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def record(**over):
    run = {
        "trace": {"busy_s": 9.0, "window_s": 10.0},
        "peaks": PEAKS,
        "ivf": {"rows": 5505024, "dim": 128, "partitions": 4096,
                "nprobe": 16, "bucket_cap": 3000},
        "traced_metrics_delta": {
            "serve_queries_total": 10240.0,
            "ivf_probe_live_rows_total": 10240.0 * 22000.0,
            "ivf_probe_distinct_live_rows_total": 10 * 5.4e6,
        },
        "window_metrics_delta": {
            "ivf_probe_slots_total": 3.0e9,
            "ivf_probe_live_rows_total": 1.2e9,
        },
        "scopes": {"knn.ivf/score": 0.1024, "knn.ivf/gather": 5.12,
                   "knn.rerank": 3.072, "knn.ids": 0.01},
    }
    run.update(over)
    return run


def test_readers_read_a_recorded_run():
    got = {n: load_by_path("layer_metrics", n).read(record())
           for n in READERS}
    assert got["ivf_score_us_per_row"] == pytest.approx(10.0)
    assert got["ivf_gather_us_per_row"] == pytest.approx(500.0)
    assert got["ivf_rerank_us_per_row"] == pytest.approx(300.0)
    assert got["ivf_slots_per_live_row"] == pytest.approx(2.5)
    by_bytes = 10 * 5.4e6 * 520 / 819e9
    by_flops = 2 * 10240.0 * 22000.0 * 128 / 197e12
    assert by_bytes > by_flops
    assert got["ivf_probe_roofline"] == pytest.approx(
        100 * by_bytes / 9.0)
    assert 0 < got["ivf_probe_roofline"] < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_on_a_parent(name):
    """A program without the scopes and counters (the parent of PR 41, a
    run with no trace): the reader returns None and does not raise."""
    read = load_by_path("layer_metrics", name).read
    bare = {"trace": {"busy_s": 9.0, "window_s": 10.0}, "peaks": PEAKS,
            "traced_metrics_delta": {"serve_queries_total": 10240.0},
            "window_metrics_delta": {"serve_batches_total": 10.0},
            "scopes": {"knn.dist_onepass": 4.0}}
    assert read(bare) is None
    assert read({}) is None
    assert read({k: None for k in record()}) is None


def test_opcount_names_the_bound():
    least, bound = opcount_ivf.least_seconds(1024 * 40000, 5.0e6, 128, PEAKS)
    assert bound == "memory" and least == pytest.approx(5.0e6 * 520 / 819e9)
    # one query row's probe is compute's: its rows are read for it alone
    least, bound = opcount_ivf.least_seconds(1e12, 4.0e4, 128, PEAKS)
    assert bound == "compute" and least == pytest.approx(
        2 * 1e12 * 128 / 197e12)


def test_the_law_is_the_configurations_and_the_rows_the_seeds():
    """``data.law_seed`` fixes the class centres and the class of every
    pool row (the geometry a batch's work items go with); the rows drawn
    about the centres still follow the seed.
    A configuration without it keeps centres that follow the seed: the
    exact cells' files, which this one shares its generator with."""
    from benchmark import harness

    with open(os.path.join(harness.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    spec, dim = config["data"], config["dim"]
    gen = harness.datagen_for(config)
    assert "law_seed" in spec
    a, b = (gen.centres(s, spec, dim) for s in (11, 3000000012))
    assert a.shape == (spec["centres"], dim) and np.array_equal(a, b)
    assert np.array_equal(a, gen.centres(spec["law_seed"], {
        k: v for k, v in spec.items() if k != "law_seed"}, dim))
    pools = [harness.query_pool(config, s, 64) for s in (11, 3000000012, 11)]
    assert not np.array_equal(pools[0], pools[1])
    assert np.array_equal(pools[0], pools[2])

    def classes(rows):  # the nearest centre: the noise is half the spacing
        d = ((rows[:, None, :] - a[None, :, :]) ** 2).sum(axis=2)
        return d.argmin(axis=1)

    assert np.array_equal(classes(pools[0]), classes(pools[1]))
    assert len(set(classes(pools[0]))) > 32
    free = {k: v for k, v in spec.items() if k != "law_seed"}
    assert not np.array_equal(gen.centres(11, free, dim),
                              gen.centres(12, free, dim))
