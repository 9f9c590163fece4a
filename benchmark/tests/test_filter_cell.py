"""The filtered serving cell on the CPU: ``drivers/serve_filter.py`` and
``serve_launcher_filter.py`` through ``run.py --allow-cpu`` in a temporary
copy at a few thousand rows (the width and the vocabulary as published),
the control ``drop_filters`` seen as not correct, the five ``filter_*``
readers on a hand-built ``run`` record, the generator's law, the driver's
own checks (an answer that ends in empty slots, a returned id that fails
its predicate) and ``reference_filter`` against numpy in float64."""

import json
import os

import numpy as np
import pytest

from benchmark import reference_filter
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "serve-yfcc10m-filter-bulk"
CONFIG = "yfcc10m-192-l2-filter"
ROWS = 16384
READERS = ("filter_mask_us_per_step", "filter_gather_roofline",
           "filter_plan_ms_per_batch", "filter_slots_per_candidate",
           "filter_scan_roofline")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("filter")))
    b = os.path.join(root, "benchmark")

    def cut(c):
        c["rows"] = ROWS
        c["knn"].update(corpus_tile=2048)
        c["slo"].update(max_batch_rows=256)

    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"), cut)
    small_copy.edit_json(
        os.path.join(b, "traffic", "bulk-saturated-filter.json"),
        lambda t: t.update(
            trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[64, 128, 256],
            rows_per_request={"law": "fixed", "rows": 256}))
    return root


def test_filter_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    assert "check recall_at_k" in out and "check dist_rel_err_max" in out
    assert "check predicate_failures: value=0 limit=0 ok" in out
    assert "check compiled_in_window" in out
    assert "launcher: reference for 256 probe rows" in out


def test_filter_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    # at least these: a later PR may add a reader to the cell
    assert allowed >= {"device_idle_pct.tput", "server_empty_pct",
                       "dispatch_lag_ms.tput", "request_edge_ms.tput",
                       *READERS}
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    # what needs no trace is there: the program's own spans and counters
    assert last["metrics"]["filter_plan_ms_per_batch"]["value"] > 0
    assert last["metrics"]["filter_slots_per_candidate"]["value"] >= 1
    assert last["correct"] is True


def test_dropped_filters_are_not_correct(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, "--control",
                                        seconds=1.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    failed = [ln for ln in out.splitlines()
              if ln.startswith("check ") and ln.endswith("FAILED")]
    assert any("predicate_failures" in ln for ln in failed), out[-3000:]
    assert any("recall_at_k" in ln for ln in failed), out[-3000:]


# ---- the readers, on a hand-built record ---------------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def record(**over):
    run = {
        "trace": {"busy_s": 9.0, "window_s": 10.0},
        "peaks": PEAKS,
        "filter": {"rows": 6291456, "dim": 192, "tags": {}},
        "traced_metrics_delta": {
            'knn_dist_tile_steps_total{path="onepass"}': 90000.0,
            'knn_dist_tile_steps_total{path="multipass"}': 10000.0,
            "filter_gather_slots_total": 1.0e9,
            'filter_rows_total{regime="scan"}': 50000.0,
            'filter_rows_total{regime="none"}': 1000.0,
            'filter_rows_total{regime="gather"}': 60000.0,
            'filter_dispatches_total{regime="scan"}': 120.0,
            'filter_dispatches_total{regime="gather"}': 300.0,
        },
        "window_metrics_delta": {
            "filter_plan_seconds_total": 2.5,
            "serve_batches_total": 500.0,
            "filter_candidates_total": 2.0e8,
            "filter_gather_slots_total": 5.0e8,
        },
        "scopes": {"knn.filter_mask": 1.0, "knn.filter_gather": 3.0,
                   "knn.dist_onepass": 4.0},
    }
    run.update(over)
    return run


def test_readers_read_a_recorded_run():
    got = {n: load_by_path("layer_metrics", n).read(record())
           for n in READERS}
    assert got["filter_mask_us_per_step"] == pytest.approx(10.0)
    # 1e9 slots x 776 B over 819 GB/s = 0.9475 s of the scope's 3 s
    assert got["filter_gather_roofline"] == pytest.approx(
        100 * 1e9 * 776 / 819e9 / 3.0)
    assert got["filter_plan_ms_per_batch"] == pytest.approx(5.0)
    assert got["filter_slots_per_candidate"] == pytest.approx(2.5)
    # 51000 rows x 6291456 x 192 x 2 over 197e12 = 0.625 s against the 120
    # dispatches' corpus reads, 120 x 4.83 GB over 819 GB/s = 0.708 s: the
    # bytes bound; over the 9 - 3 s outside the gather
    assert got["filter_scan_roofline"] == pytest.approx(
        100 * (120 * 6291456 * 192 * 4 + 51000 * (192 * 4 + 80)) / 819e9
        / 6.0)
    assert all(0 < got[n] < 100 for n in READERS if n.endswith("roofline"))


@pytest.mark.parametrize("missing", [
    {"scopes": None},  # a trace that names no scope (the CPU)
    {"scopes": {"knn.dist": 8.0}},  # a program without the scopes
    {"traced_metrics_delta": None, "window_metrics_delta": None},
    {"traced_metrics_delta": {"serve_batches_total": 65.0},
     "window_metrics_delta": {"serve_batches_total": 65.0}},  # the parent
    {"trace": None, "filter": None},
], ids=lambda m: next(iter(m)) + "=" + str(next(iter(m.values())))[:24])
def test_readers_return_nothing_where_there_is_nothing_to_read(missing):
    """The parent commit has no such counter, span or scope: no number, no
    raise, for any reader that needs what is missing."""
    got = {n: load_by_path("layer_metrics", n).read(record(**missing))
           for n in READERS}
    first = next(iter(missing))
    gone = {"scopes": ("filter_mask_us_per_step", "filter_gather_roofline")
            + (("filter_scan_roofline",) if missing.get("scopes", 1) is None
               else ()),  # no gather scope: the scan took all the busy time
            "traced_metrics_delta": READERS,
            "trace": ("filter_scan_roofline", "filter_gather_roofline")}
    for name in READERS:
        if name in gone[first]:
            assert got[name] is None, name
        else:
            assert got[name] is not None and got[name] > 0, name


# ---- the driver's own checks ---------------------------------------------


def test_an_answer_may_end_in_empty_slots_and_nothing_else():
    drv = load_by_path("drivers", "serve_filter")
    inf = float("inf")
    ok = {"ids": [[3, 5, -1], [1, 2, 4]], "dists": [[1.0, 2.0, inf],
                                                     [0.5, 0.5, 9.0]]}
    ids, dists = drv.whole_answer(ok, 2, 3)
    assert ids.tolist() == ok["ids"] and np.isinf(dists[0, 2])
    for bad in (
        {"ids": [[3, 5, 7]], "dists": [[1.0, 2.0, inf]]},  # an id, no row
        {"ids": [[3, -1, 7]], "dists": [[1.0, inf, 2.0]]},  # a gap
        {"ids": [[3, 5, 7]], "dists": [[2.0, 1.0, 3.0]]},  # falling
        {"ids": [[3, 5, -1]], "dists": [[1.0, 2.0, 3.0]]},  # -1 with a row
        {"ids": [[3, 5, 7]], "dists": [[1.0, float("nan"), 3.0]]},
        {"ids": [[3, 5]], "dists": [[1.0, 2.0]]},  # the wrong shape
    ):
        assert drv.whole_answer(bad, 1, 3) is None, bad


def test_predicate_failures_are_counted_from_the_bags():
    drv = load_by_path("drivers", "serve_filter")
    bags = np.array([[1, 4, 9], [4, 9, 99], [2, 99, 99], [1, 2, 4]])
    ids = np.array([[0, 3, -1], [1, 2, 0], [2, 3, 1]])
    tags = np.array([[1, 4], [9, -1], [-1, -1]])
    # row 0: both hold 1 and 4, the empty slot names no row; row 1: id 2
    # lacks 9; row 2 asks nothing
    assert drv.predicate_failures(bags, ids, tags) == 1
    assert drv.predicate_failures(bags, ids, np.array(
        [[1, 4], [9, 4], [7, -1]])) == 1 + 3


def test_the_comparison_takes_empty_slots_on_both_sides_only():
    from benchmark import compare

    drv = load_by_path("drivers", "serve_filter")
    limits = {"recall_min": 0.999, "tie_rtol": 1e-5,
              "dist_rel_err_max": 8e-6}
    inf = float("inf")
    ref_i = np.array([[4, 7, -1], [1, 2, 3]])
    ref_d = np.array([[1.0, 2.0, inf], [1.0, 2.0, 3.0]])
    cmp = drv.compare_filtered(compare.compare_answers, lambda: 0)
    assert cmp(ref_i, ref_d, ref_i, ref_d, limits)["ok"]
    # a row returned where the reference has none
    bad = cmp(np.array([[4, 7, 9], [1, 2, 3]]),
              np.array([[1.0, 2.0, 5.0], [1.0, 2.0, 3.0]]), ref_i, ref_d,
              limits)
    assert not bad["ok"]
    # an empty slot where the reference has a row
    bad = cmp(np.array([[4, 7, -1], [1, 2, -1]]),
              np.array([[1.0, 2.0, inf], [1.0, 2.0, inf]]), ref_i, ref_d,
              limits)
    assert not bad["ok"]
    # one id anywhere that fails its predicate
    bad = drv.compare_filtered(compare.compare_answers, lambda: 1)(
        ref_i, ref_d, ref_i, ref_d, limits)
    assert not bad["ok"] and bad["numbers"]["predicate_failures"] == [
        1, 0, False]


# ---- the data's law ------------------------------------------------------

SPEC = {"centres": 16, "centre_scale": 140.0, "sigma": 30.0,
        "vocabulary": 200386, "bag_draws": 13, "draw_keep": 0.9,
        "own_share": 0.3, "own_tags": 32, "own_from": 64, "shift": 3.0,
        "block_rows": 4096, "one_tag_share": 0.5, "popular_share": 0.6,
        "law_seed": 2023}


def test_bags_follow_the_laws_seed_whatever_the_threads():
    gen = load_by_path("datagen", "clustered_u8_tags")
    a = gen.bags(20000, SPEC, threads=1)
    b = gen.bags(20000, SPEC, threads=5)
    c = gen.bags(20000, {**SPEC, "law_seed": 2024})
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[2], c[2])
    which, indptr, indices, matrix = a
    assert len(which) == 20000 and indptr[-1] == len(indices)
    sizes = np.diff(indptr)
    assert sizes.min() >= 1 and 10.5 < sizes.mean() < 12.5
    # a bag's ids ascend, none twice; the matrix holds the same bags
    row = np.repeat(np.arange(20000), sizes)
    assert (np.diff(indices)[np.diff(row) == 0] > 0).all()
    assert (np.sort(matrix[matrix < 200386]) == np.sort(indices)).all()
    assert ((matrix < 200386).sum(1) == sizes).all()
    # heavy-tailed: the most frequent tag on 10-25 % of the rows, most
    # tags on one row or none
    counts = np.bincount(indices, minlength=200386)
    assert 0.10 < counts[0] / 20000 < 0.25
    assert (counts <= 1).mean() > 0.8
    # a centre's own tags are its rows': they correlate with the vectors
    own = gen.own_tag_sets(SPEC)
    mine = np.isin(indices, own[3])
    assert (which[row[mine]] == 3).mean() > 0.9


def test_queries_take_their_tags_from_a_corpus_rows_bag():
    gen = load_by_path("datagen", "clustered_u8_tags")
    which, indptr, indices, _ = gen.bags(20000, SPEC)
    q, f = gen.query_pool(7, 2000, SPEC, 192, which, indptr, indices)
    assert q.shape == (2000, 192) and f.shape == (2000, 2)
    assert (q == np.rint(q)).all() and q.min() >= 0 and q.max() <= 255
    assert (f[:, 0] >= 0).all() and 0.4 < (f[:, 1] >= 0).mean() < 0.6
    assert (f[:, 0] != f[:, 1]).all()
    # every query matches at least the row its tags came from
    assert gen.match_counts(indptr, indices, f[:200]).min() >= 1
    q2, f2 = gen.query_pool(7, 2000, SPEC, 192, which, indptr, indices)
    assert np.array_equal(q, q2) and np.array_equal(f, f2)
    # another seed: other vectors, the same tags (the work does not move)
    q3, f3 = gen.query_pool(8, 2000, SPEC, 192, which, indptr, indices)
    assert not np.array_equal(q, q3) and np.array_equal(f, f3)


def test_device_corpus_follows_the_seed_and_its_rows_centres():
    gen = load_by_path("datagen", "clustered_u8_tags")
    which = gen.bags(8192, SPEC)[0]
    a = np.asarray(gen.device_corpus(2**31 + 9, 8192, 192, SPEC, which, 2048))
    b = np.asarray(gen.device_corpus(2**31 + 9, 8192, 192, SPEC, which, 2048))
    assert a.shape == (8192, 192) and np.array_equal(a, b)
    assert (a == np.rint(a)).all() and a.min() >= 0 and a.max() <= 255
    cen = gen.centres(2**31 + 9, SPEC, 192)
    near = np.argmin(((a[:256, None, :] - cen[None]) ** 2).sum(-1), axis=1)
    assert (near == which[:256]).mean() > 0.99


# ---- the reference -------------------------------------------------------


def test_reference_filter_against_numpy_float64():
    gen = load_by_path("datagen", "clustered_u8_tags")
    which, indptr, indices, matrix = gen.bags(4096, SPEC)
    cen = gen.centres(11, SPEC, 64)
    rng = np.random.default_rng(11)
    corpus = gen.host_rows(rng, which, cen, SPEC)
    q, f = gen.query_pool(11, 37, SPEC, 64, which, indptr, indices)
    f[5] = (-1, -1)  # no tag: against all rows
    f[6] = (200385, 0)  # the vocabulary's last id: on no row here
    d, i = reference_filter.exact_knn_filtered(corpus, matrix, q, f, 10,
                                               block_rows=1024)
    c64, q64 = corpus.astype(np.float64), q.astype(np.float64)
    d2 = ((q64[:, None, :] - c64[None]) ** 2).sum(-1)
    member = np.zeros((4096, 200387), dtype=bool)
    member[np.repeat(np.arange(4096), np.diff(indptr)), indices] = True
    ok = np.ones(d2.shape, dtype=bool)
    for j in range(2):
        ok &= np.where((f[:, j] < 0)[:, None], True,
                       member[:, np.maximum(f[:, j], 0)].T)
    d2 = np.where(ok & (d2 > 0), d2, np.inf)
    order = np.argsort(d2, axis=1, kind="stable")[:, :10]
    want = np.take_along_axis(d2, order, axis=1)
    np.testing.assert_array_equal(d, want)  # whole numbers: exact
    assert (i == np.where(np.isinf(want), -1, order)).all()  # ties by id
    assert np.isinf(d[6]).all() and (i[6] == -1).all()
    assert np.isfinite(d[5]).all()
    assert (np.isinf(d).sum(1) > 0).any()  # some query has fewer than 10
    # the reference holds no program code and no matmul
    src = open(reference_filter.__file__).read().split('"""', 2)[2]
    assert "mpi_knn_tpu" not in src
    src = src.replace("default_matmul_precision", "")  # a setting, no op
    for word in ("dot_general", "matmul", "einsum", " @ ", "jnp.dot"):
        assert word not in src
