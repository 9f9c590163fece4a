"""The range-search serving cell on the CPU: ``drivers/serve_range.py`` and
``serve_launcher_range.py`` through ``run.py --allow-cpu`` in a temporary
copy at a few ten thousand rows (the width, the element type and the radius
as published), plain, traced and under ``--control``; a program that
cannot answer a radius (the parent commit) ending the run at once with
code 4; wrong answers planted — a pair missing, a pair outside the radius,
a row out of order, ``lims`` that do not add up — each caught by
``compare_range.py`` or the driver's check of every answer;
``reference_range`` against an int64 sum; the generator as a function of
(seed, block) whose LAW does not move with the seed; ``opcount_range``
against hand-reckoned numbers; every new reader on a recorded ``run``."""

import json
import os

import numpy as np
import pytest

from benchmark import compare_range, opcount_range, reference_range
from benchmark.datagen import dupgroups_u8_blocks as gen
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "serve-ssnpp100m-range-bulk"
CONFIG = "ssnpp100m-256-l2-range"
ROWS = 32768 + 1000  # a last partial tile
RADIUS = 96237.0


def _cut_config(c):
    c["rows"] = ROWS
    c["knn"].update(corpus_tile=2048, query_tile=256, query_bucket=64)
    c["slo"].update(max_batch_rows=256)
    c["data"].update(block_rows=8192, planted_share=0.5, group_max=1024)


def _cut_traffic(t):
    t.update(trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[256],
             query_pool_rows=512,
             rows_per_request={"law": "fixed", "rows": 256})
    t["pool"].update(per_period=[10, 4, 2, 0])


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("range")))
    b = os.path.join(root, "benchmark")
    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"),
                         _cut_config)
    small_copy.edit_json(
        os.path.join(b, "traffic", "bulk-saturated-range.json"), _cut_traffic)
    return root


def test_range_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=3.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    assert "check completeness: value=1.0 limit=1.0 ok" in out
    assert "check foreign_pairs: value=0 limit=0 ok" in out
    assert "check dist_rel_err_max: value=0.0 limit=0.0 ok" in out
    assert "launcher: reference for 256 probe rows over 5 blocks" in out
    assert f"launcher: {ROWS} x 256 in 5 blocks built" in out


def test_range_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=3.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    assert allowed >= {
        "device_idle_pct.tput", "server_empty_pct", "dispatch_lag_ms.tput",
        "request_edge_ms.tput", "overrun_ms.tput", "gc_pause_ms.tput",
        "range_scan_roofline", "range_scan_us_per_step",
        "range_overflow_pct", "range_results_per_row",
        "range_encode_ms_per_batch"}
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    # the counters' readers read on any platform
    assert last["metrics"]["range_results_per_row"]["value"] > 1.0
    assert last["metrics"]["range_encode_ms_per_batch"]["value"] > 0.0
    assert last["correct"] is True


def test_the_control_loses_a_bit_and_is_not_correct(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, "--control", seconds=1.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    assert "control: every row reaches the build without bit 128" in out
    assert "check completeness" in out and "FAILED" in out


def test_a_program_without_range_search_refuses_the_cell_with_code_4(copy):
    """The parent commit's case: its ``KNNConfig`` knows no ``range_cap``.
    The launcher asks the program before it asks for the chip: code 4, no
    result line, nothing allocated."""
    b = os.path.join(copy, "benchmark")
    own = os.path.join(b, "serve_launcher_range.py")
    real = os.path.join(b, "serve_launcher_range_real.py")
    os.rename(own, real)
    with open(own, "w") as f:
        f.write(
            "import dataclasses, os, sys\n"
            "ROOT = os.path.dirname(os.path.dirname(os.path.abspath("
            "__file__)))\n"
            "sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]\n"
            "import mpi_knn_tpu.config as config\n"
            "fields = [(f.name, f.type, f) for f in dataclasses.fields("
            "config.KNNConfig) if f.name != 'range_cap']\n"
            "config.KNNConfig = dataclasses.make_dataclass("
            "'KNNConfig', fields, frozen=True)\n"
            "from benchmark import harness\n"
            "harness.find_chip = lambda *a: sys.exit('asked for the chip')\n"
            "from benchmark import serve_launcher_range_real as real\n"
            "sys.exit(real.main())\n")
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=1.0,
                                            timeout=300)
    finally:
        os.replace(real, own)
    assert rc == 4 and last is None, out[-2000:]
    assert "this checkout cannot run the cell" in out
    assert "asked for the chip" not in out


# ---- the comparison: wrong answers planted ---------------------------------


def _answers():
    """Three probe rows' reference lists and a faithful copy as answers."""
    ref = [(np.array([10.0, 20.0, 20.0, 96236.0]), np.array([7, 3, 9, 1])),
           (np.zeros(0), np.zeros(0, np.int64)),
           (np.array([5.0]), np.array([4]))]
    return [(d.copy(), i.copy()) for d, i in ref], ref


LIMITS = {"completeness_min": 1.0, "foreign_pairs_max": 0,
          "dist_rel_err_max": 0.0}


def test_faithful_answers_compare_as_correct():
    ans, ref = _answers()
    verdict = compare_range.compare_ranges(ans, ref, RADIUS, LIMITS)
    assert verdict["ok"], verdict["numbers"]
    assert verdict["numbers"]["completeness"][0] == 1.0
    assert verdict["info"]["reference_pairs"] == 5


@pytest.mark.parametrize("fault, number", [
    ("pair_missing", "completeness"),
    ("pair_outside", "foreign_pairs"),
    ("row_out_of_order", "rows_out_of_order"),
    ("distance_off_by_one", "dist_rel_err_max"),
    ("at_the_radius", "rows_out_of_order"),
])
def test_a_planted_wrong_answer_is_caught(fault, number):
    ans, ref = _answers()
    d, i = ans[0]
    if fault == "pair_missing":
        ans[0] = (d[:-1], i[:-1])
    elif fault == "pair_outside":
        ans[2] = (np.array([5.0, 96000.0]), np.array([4, 77]))
    elif fault == "row_out_of_order":
        ans[0] = (d[[0, 2, 1, 3]], i[[0, 2, 1, 3]])  # ties by the lower id
    elif fault == "distance_off_by_one":
        ans[2] = (np.array([6.0]), np.array([4]))
    elif fault == "at_the_radius":
        ans[0] = (np.append(d, RADIUS), np.append(i, 99))  # `<` is strict
    verdict = compare_range.compare_ranges(ans, ref, RADIUS, LIMITS)
    assert not verdict["ok"]
    assert not verdict["numbers"][number][2], verdict["numbers"]


def test_lims_that_do_not_add_up_are_no_answer():
    serve_range = load_by_path("drivers", "serve_range")
    good = {"lims": [0, 2, 2, 3], "dists": [1.0, 2.0, 3.0],
            "ids": [5, 6, 7]}
    assert serve_range.whole_answer(good, 3, RADIUS) is not None
    for bad in ({**good, "lims": [0, 2, 2, 4]},  # past the lists' end
                {**good, "lims": [0, 2, 1, 3]},  # falling
                {**good, "lims": [1, 2, 2, 3]},  # not from 0
                {**good, "lims": [0, 2, 3]},  # a row short
                {**good, "dists": [2.0, 1.0, 3.0]},  # a row out of order
                {**good, "dists": [1.0, 2.0, RADIUS]},  # not under it
                {"dists": [], "ids": []}):  # k-NN's keys alone
        assert serve_range.whole_answer(bad, 3, RADIUS) is None, bad
    assert compare_range.rows_of([0, 1], [1.0], [1, 2]) is None
    verdict = compare_range.compare_ranges([], [], RADIUS, LIMITS,
                                           misshapen=1)
    assert not verdict["numbers"]["lims_do_not_add_up"][2]


# ---- the reference and the generator ---------------------------------------

SPEC = {"law_seed": 54, "centres": 16, "centre_scale": 140.0, "sigma": 30.0,
        "block_rows": 3000, "planted_share": 0.2, "group_min": 2,
        "group_max": 256, "group_exponent": 2.0, "member_sigma": [4.0, 20.0]}


def _corpus(seed, rows=7000, dim=256):
    sizes = gen.block_rows_of(rows, SPEC)
    return sizes, [np.asarray(gen.device_block(seed, b, n, dim, SPEC))
                   for b, n in enumerate(sizes)]


def test_the_reference_equals_an_int64_sum_and_keeps_every_hit():
    sizes, blocks = _corpus(5)
    corpus = np.concatenate(blocks).astype(np.int64)
    _, _, centre, _ = gen.block_law(0, sizes[0], 256, SPEC)
    rng = np.random.default_rng(1)
    q = np.clip(np.rint(centre[:6] + rng.standard_normal((6, 256)) * 6),
                0, 255).astype(np.float32)
    q = np.concatenate([q, corpus[:2].astype(np.float32)])  # two copies
    lims, d, i = reference_range.range_search_blocks(
        lambda b: blocks[b], sizes, q, RADIUS)
    assert lims[-1] == len(d) == len(i) > 0
    for r in range(len(q)):
        exact = ((corpus - q[r].astype(np.int64)) ** 2).sum(axis=1)
        want = np.nonzero((exact < RADIUS) & (exact > 0))[0]
        want = want[np.lexsort((want, exact[want]))]
        got = slice(lims[r], lims[r + 1])
        assert (i[got] == want).all() and (d[got] == exact[want]).all()
    # the copy's own row is left out (exclude_zero), kept without it
    kept = reference_range.range_search_blocks(
        lambda b: blocks[b], sizes, q[-2:], RADIUS, exclude_zero=False)
    assert 0 in kept[2][kept[0][0]:kept[0][1]]
    assert 0 not in i[lims[-3]:lims[-2]]


def test_the_reference_states_its_exactness_and_refuses_past_it():
    reference_range.check_exact(256)
    assert 256 * 255 ** 2 < 2 ** 24 <= 259 * 255 ** 2
    with pytest.raises(ValueError, match="2\\*\\*24"):
        reference_range.check_exact(259)
    # rows of extreme bytes at the published width: the largest distance
    # there is, and one under the radius by 1 / at it
    zeros, full = np.zeros((1, 256), np.uint8), np.full((1, 256), 255,
                                                        np.uint8)
    lims, d, i = reference_range.range_search_blocks(
        lambda b: full, [1], zeros.astype(np.float32), 256 * 255 ** 2 + 1)
    assert d.tolist() == [256.0 * 255 ** 2] and i.tolist() == [0]
    lims, d, i = reference_range.range_search_blocks(
        lambda b: full, [1], zeros.astype(np.float32), 256 * 255 ** 2)
    assert lims.tolist() == [0, 0]  # AT the radius: out


def test_the_generator_is_a_function_of_seed_and_block_under_a_fixed_law():
    sizes, a = _corpus(5)
    _, again = _corpus(5)
    _, other = _corpus(6)
    assert all((x == y).all() for x, y in zip(a, again))
    assert not (a[0] == other[0]).all()
    # the law does not move with the seed: the same rows are planted
    group, sigma, centre, size = gen.block_law(0, sizes[0], 256, SPEC)
    assert a[0].dtype == np.uint8 and a[0].shape == (3000, 256)
    assert (group >= 0).sum() == int(3000 * 0.2) == size.sum()
    assert size.min() >= 2 and size.max() <= 256
    planted = np.nonzero(group >= 0)[0]
    for block in (a[0], other[0]):
        # a member lies about 256 x sigma_m^2 from its group's centre
        near = ((block[planted].astype(np.float64)
                 - centre[group[planted]]) ** 2).sum(axis=1)
        assert np.allclose(near / (256 * sigma[planted] ** 2), 1.0,
                           atol=0.5)
    # background rows are far from everything
    free = np.nonzero(group < 0)[0][:50]
    far = ((a[0][free, None, :].astype(np.float64)
            - a[0][None, free, :]) ** 2).sum(axis=2)
    assert far[~np.eye(50, dtype=bool)].min() > 2 * RADIUS


def test_the_pool_holds_every_stratum_in_every_period():
    config = {"rows": 4 * 8192, "dim": 256, "radius": RADIUS,
              "range_cap": 8192,
              "data": {**SPEC, "block_rows": 8192, "planted_share": 0.5,
                       "group_max": 1024}}
    mix = {"pool": {"period": 256, "per_period": [10, 4, 2, 0],
                    "sigma_q": 6.0}}
    pool, stratum = gen.query_pool(7, 512, config, mix)
    other, same = gen.query_pool(8, 512, config, mix)
    assert pool.shape == (512, 256) and (stratum == same).all()
    assert (stratum[:256] == stratum[256:]).all()
    assert [int((stratum[:256] == s).sum()) for s in (-1, 0, 1, 2, 3)] == [
        240, 10, 4, 2, 0]
    assert (pool == np.rint(pool)).all() and pool.min() >= 0 \
        and pool.max() <= 255
    assert not (pool == other).all()


# ---- the operation count and the readers -----------------------------------


def test_opcount_range_against_hand_reckoned_numbers():
    peaks = {"hbm_bytes_per_s": 819e9}
    # one 1024-row batch against the cell's half: 2 x 1024 x 50 003 968 x
    # 256 = 2.6216e13 operations, 66.7 ms at 393e12/s; the stack once,
    # 12.8e9 B + queries + 28 000 answers, 15.6 ms at 819e9 B/s
    ops = 2.0 * 1024 * 50_003_968 * 256
    least, bound = opcount_range.least_seconds(
        1024, 1, 28_000, 50_003_968, 256, peaks)
    assert bound == "compute" and least == ops / 393e12
    assert abs(least - 0.0667) < 1e-4
    nbytes = opcount_range.range_bytes(1024, 1, 28_000, 50_003_968, 256)
    assert nbytes == (50_003_968 * 256 + 1024 * (256 * 4 + 4)
                      + 28_000 * 8 + 1024 * 4)
    # few rows a batch: the stack's bytes bound
    assert opcount_range.least_seconds(
        8, 1, 0, 50_003_968, 256, peaks)[1] == "memory"


RUN = {
    "device": {"kind": "TPU v5 lite"},
    "peaks": {"hbm_bytes_per_s": 819e9},
    "about": {"rows": 50_003_968, "dim": 256},
    "trace": {"busy_s": 9.5, "window_s": 10.0},
    "range": {"scan_s": 8.0, "overflow_s": 1.0, "finish_s": 0.2},
    "traced_metrics_delta": {
        "serve_queries_total": 40960.0, "serve_batches_total": 40.0,
        "knn_range_results_total": 1_120_000.0,
        'knn_dist_tile_steps_total{path="range"}': 40 * 6104.0},
    "window_metrics_delta": {
        "knn_range_rows_total": 204800.0,
        "knn_range_results_total": 5_600_000.0,
        "frontend_request_seconds_count": 200.0,
        'frontend_request_phase_seconds_total{phase="encode",'
        'route="query"}': 1.5},
}


def test_the_new_readers_on_a_recorded_run():
    def read(name, run=RUN):
        return load_by_path("layer_metrics", name).read(run)

    least = 2.0 * 40960 * 50_003_968 * 256 / 393e12
    assert read("range_scan_roofline") == pytest.approx(100 * least / 9.0)
    assert read("range_scan_roofline") < 100.0
    assert read("range_scan_us_per_step") == pytest.approx(
        8.0e6 / (40 * 6104))
    assert read("range_overflow_pct") == pytest.approx(100 / 9.5)
    assert read("range_results_per_row") == pytest.approx(5.6e6 / 204800)
    assert read("range_encode_ms_per_batch") == pytest.approx(7.5)
    # the parent commit: no scope, no counter — nothing to read, no raise
    bare = {**RUN, "range": None, "traced_metrics_delta": {
        "serve_queries_total": 1.0}, "window_metrics_delta": {
            "frontend_request_seconds_count": 200.0}}
    for name in ("range_scan_roofline", "range_scan_us_per_step",
                 "range_overflow_pct", "range_results_per_row",
                 "range_encode_ms_per_batch"):
        assert read(name, bare) is None
        assert read(name, {}) is None
