"""The byte-stack serving cell on the CPU: ``drivers/serve_u8.py`` and
``serve_launcher_u8.py`` through ``run.py --allow-cpu`` in a temporary copy
at a few thousand rows (the width and the element type as published),
plain, traced and under ``--control``; three faults planted — the lost top
bit (the control), a signed cast without the shift, a float32 stack whose
distances are one unit off — each seen as not correct; a program that
cannot hold a byte stack (the parent commit) ending the run at once;
``reference_u8`` against ``reference.py``; the generator as a function of
(seed, block) with ``clustered_u8``'s law; the three readers on a
hand-built ``run`` record; ``opcount_u8``'s share never over 100 %."""

import json
import os

import numpy as np
import pytest

from benchmark import opcount_u8, reference, reference_u8
from benchmark.datagen import clustered_u8, clustered_u8_blocks
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "serve-bigann100m-u8-bulk"
CONFIG = "bigann100m-128-l2-u8"
ROWS = 16384 + 1000  # a last partial tile, blocks off the tile grid

# a launcher of the copy only: the program altered in the child that holds
# the device, then the cell's own launcher (kept beside it as *_real.py)
PLANTED = '''"""serve_launcher_u8 with a fault planted in the program."""
import os
import sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # the copy first
import jax.numpy as jnp
import numpy as np
import mpi_knn_tpu.serve as serve
from benchmark import serve_launcher_u8_real as real
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.ops import distance
from mpi_knn_tpu.serve import index as ix

FAULT = "{fault}"
if FAULT == "signed_cast":
    # the rows narrowed to a signed byte with no shift by 128, and widened
    # from it: 200 comes back as -56
    widen = serial.widen_rows

    def through_int8(rows, offset):
        if rows.dtype == jnp.uint8:
            rows = rows.astype(jnp.int8).astype(jnp.int32) & 0xFF
            rows = jnp.where(rows > 127, rows - 256, rows).astype(
                jnp.float32)
            return rows if offset is None else rows - offset
        return widen(rows, offset)

    serial.widen_rows = through_int8
elif FAULT == "one_unit_off":
    # a float32 stack answering in place of the bytes, its distances one
    # unit too large: what the equality of distances is there to see
    build = serve.build_index_blocks

    def float_stack(shape, blocks, cfg, **kw):
        rows = np.concatenate([np.asarray(b) for b in ix._each_block(blocks)])
        return serve.build_index(rows.astype(np.float32),
                                 cfg.replace(dtype="float32"))

    serve.build_index_blocks = float_stack
    sound = serial.pairwise_sq_l2

    def off_by_one(*a, **kw):
        return sound(*a, **kw) + 1.0

    serial.pairwise_sq_l2 = distance.pairwise_sq_l2 = off_by_one
else:
    raise SystemExit("no such fault " + FAULT)
sys.exit(real.main())
'''

FAULTS = {
    # the fault: a number that fails for it
    "signed_cast": "recall_at_k",
    "one_unit_off": "dist_rel_err_max",
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("u8")))
    b = os.path.join(root, "benchmark")

    def cut(c):
        c["rows"] = ROWS
        c["knn"].update(corpus_tile=2048)
        c["slo"].update(max_batch_rows=256)
        c["data"]["block_rows"] = 5000

    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"), cut)
    small_copy.edit_json(
        os.path.join(b, "traffic", "bulk-saturated-u8.json"),
        lambda t: t.update(
            trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[256],
            rows_per_request={"law": "fixed", "rows": 256}))
    return root


def test_u8_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    assert "check dist_rel_err_max: value=0.0 limit=0.0 ok" in out
    assert "check recall_at_k: value=1.0" in out
    assert "launcher: reference for 256 probe rows over 4 blocks" in out
    assert f"launcher: {ROWS} x 128 in 4 blocks built" in out
    assert "launcher: phases " in out


def test_u8_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    # at least these: a later PR may add a metric to the cell's list
    assert allowed >= {
        "device_idle_pct.tput", "server_empty_pct", "dispatch_lag_ms.tput",
        "request_edge_ms.tput", "u8_scan_roofline", "u8_scan_us_per_step",
        "u8_rest_bytes_per_row"}
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    assert last["metrics"]["u8_rest_bytes_per_row"]["value"] == 136.0
    assert last["correct"] is True


def test_the_control_loses_a_bit_and_is_not_correct(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, "--control", seconds=1.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    assert "control: every row reaches the build without bit 128" in out
    assert "check dist_rel_err_max" in out and "FAILED" in out


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(copy, fault):
    b = os.path.join(copy, "benchmark")
    own = os.path.join(b, "serve_launcher_u8.py")
    real = os.path.join(b, "serve_launcher_u8_real.py")
    os.rename(own, real)
    with open(own, "w") as f:
        f.write(PLANTED.format(fault=fault))
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=1.0)
    finally:
        os.replace(real, own)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    failed = [ln for ln in out.splitlines()
              if ln.startswith("check ") and ln.endswith("FAILED")]
    assert any(FAULTS[fault] in ln for ln in failed), out[-3000:]


def test_a_program_without_a_byte_stack_refuses_the_cell_at_once(copy):
    """The parent commit's case: its ``mpi_knn_tpu.serve`` has no block
    entry point. The launcher asks the program before it asks for the
    chip: code other than 0, no result line, nothing allocated."""
    b = os.path.join(copy, "benchmark")
    own = os.path.join(b, "serve_launcher_u8.py")
    real = os.path.join(b, "serve_launcher_u8_real.py")
    os.rename(own, real)
    with open(own, "w") as f:
        f.write(
            "import os, sys\n"
            "ROOT = os.path.dirname(os.path.dirname(os.path.abspath("
            "__file__)))\n"
            "sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]\n"
            "import mpi_knn_tpu.serve as serve\n"
            "del serve.build_index_blocks\n"
            "from benchmark import harness\n"
            "harness.find_chip = lambda *a: sys.exit('asked for the chip')\n"
            "from benchmark import serve_launcher_u8_real as real\n"
            "sys.exit(real.main())\n")
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=1.0,
                                            timeout=300)
    finally:
        os.replace(real, own)
    assert rc != 0 and last is None
    assert "this checkout cannot run the cell" in out
    assert "asked for the chip" not in out


# ---- the reference and the generator --------------------------------------

SPEC = {"centres": 16, "centre_scale": 140.0, "sigma": 30.0,
        "block_rows": 3000}


def _blocks(seed, rows, dim=128):
    sizes = clustered_u8_blocks.block_rows_of(rows, SPEC)
    return sizes, [np.asarray(clustered_u8_blocks.device_block(
        seed, b, n, dim, SPEC)) for b, n in enumerate(sizes)]


@pytest.mark.parametrize("exclude_zero", [True, False])
def test_reference_u8_equals_the_reference_on_a_corpus_both_hold(
        exclude_zero):
    seed = 2**31 + 5
    sizes, blocks = _blocks(seed, 10000)
    assert sizes == [3000, 3000, 3000, 1000]
    corpus = np.concatenate(blocks)
    rng = np.random.default_rng(1)
    q = clustered_u8_blocks.host_rows(
        rng, 40, clustered_u8_blocks.centres(seed, SPEC, 128), SPEC)
    corpus[77] = q[3]  # a zero distance
    corpus[9001] = corpus[5]  # a tie across blocks: the lower id first
    blocks = np.split(corpus, np.cumsum(sizes)[:-1])
    want = reference.exact_knn(corpus.astype(np.float32), q, 10,
                               exclude_zero=exclude_zero)
    got = reference_u8.exact_knn_blocks(
        lambda b: blocks[b], sizes, q, 10, exclude_zero=exclude_zero)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert (got[0] == np.rint(got[0])).all()
    assert (got[0][3, 0] == 0.0) == (not exclude_zero)


def test_device_block_is_a_function_of_seed_and_block_alone():
    seed = 2**31 + 5
    _, blocks = _blocks(seed, 10000)
    again = np.asarray(clustered_u8_blocks.device_block(
        seed, 2, 3000, 128, SPEC))
    np.testing.assert_array_equal(again, blocks[2])
    assert blocks[0].dtype == np.uint8
    assert not np.array_equal(blocks[0], blocks[1])
    other = np.asarray(clustered_u8_blocks.device_block(
        seed + 1, 2, 3000, 128, SPEC))
    assert not np.array_equal(other, blocks[2])


def test_the_blocks_have_clustered_u8_s_law():
    seed = 11
    _, blocks = _blocks(seed, 30000)
    mine = np.concatenate(blocks).astype(np.float64)
    theirs = np.asarray(clustered_u8.device_corpus(
        seed, 32768, 128, SPEC)).astype(np.float64)
    assert abs(mine.mean() - theirs.mean()) < 0.5
    assert abs(mine.std() - theirs.std()) < 0.5
    for edge in (0.0, 255.0):  # the clipped shares
        assert abs((mine == edge).mean() - (theirs == edge).mean()) < 2e-3
    # every row sits at a centre of the same table, sigma away
    cen = clustered_u8_blocks.centres(seed, SPEC, 128)
    np.testing.assert_array_equal(cen, clustered_u8.centres(seed, SPEC, 128))
    near = ((mine[:2000, None, :] - cen[None]) ** 2).sum(-1).min(1)
    assert 0.8 < np.sqrt(near.mean() / 128) / SPEC["sigma"] < 1.05


# ---- the readers, on a hand-built record ---------------------------------

STEPS = 'knn_dist_tile_steps_total{path="u8"}'
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 17179869184}
ABOUT = {"rows": 100007936, "dim": 128, "k": 10}


def record(**over):
    run = {
        "device": {"kind": "TPU v5 lite"}, "peaks": PEAKS, "about": ABOUT,
        "trace": {"busy_s": 10.0, "window_s": 10.0},
        "traced_metrics_delta": {
            STEPS: 27 * 12208.0, "serve_queries_total": 27 * 1024.0,
            "serve_batches_total": 27.0},
        "u8": {"scan_s": 9.9, "rest_bytes_per_row": 136.0},
    }
    run.update(over)
    return run


def test_readers_on_a_recorded_run():
    roofline = load_by_path("layer_metrics", "u8_scan_roofline")
    per_step = load_by_path("layer_metrics", "u8_scan_us_per_step")
    rest = load_by_path("layer_metrics", "u8_rest_bytes_per_row")
    # 27 batches of 1024 x 100 007 936 x 128: 66.7 ms each at the 8-bit peak
    least = 27 * 2 * 1024 * 100007936 * 128 / 393e12
    assert roofline.read(record()) == pytest.approx(100 * least / 9.9)
    assert 15 < roofline.read(record()) < 20
    assert per_step.read(record()) == pytest.approx(9.9e6 / (27 * 12208))
    assert rest.read(record()) == 136.0


@pytest.mark.parametrize("missing", [
    {"u8": None},  # the parent commit's run record
    {"u8": {"scan_s": None, "rest_bytes_per_row": None}},  # no such scope
    {"u8": {"scan_s": 0.0, "rest_bytes_per_row": None}},
    {"traced_metrics_delta": None},
    {"traced_metrics_delta": {"serve_batches_total": 27.0}},  # no counter
    {"traced_metrics_delta": {STEPS: 0.0, "serve_queries_total": 0.0,
                              "serve_batches_total": 0.0}},  # none moved
], ids=lambda m: next(iter(m)) + "=" + str(next(iter(m.values())))[:24])
def test_readers_return_none_on_an_empty_run(missing):
    for name in ("u8_scan_roofline", "u8_scan_us_per_step"):
        assert load_by_path("layer_metrics", name).read(
            record(**missing)) is None
    if "u8" in missing:
        assert load_by_path("layer_metrics", "u8_rest_bytes_per_row").read(
            record(**missing)) is None


@pytest.mark.parametrize("rows,batches", [(1024, 1), (27 * 1024, 27),
                                          (64, 1), (1, 1), (4096, 1)])
def test_the_share_never_passes_100_for_a_time_at_or_over_the_least(
        rows, batches):
    """Whatever the program feeds its matrix unit: a busy time at or above
    ``opcount_u8``'s own least time reads at most 100 %, and the bf16
    program's own floor (twice the 8-bit one, or the bytes) at most that."""
    roofline = load_by_path("layer_metrics", "u8_scan_roofline")
    least, bound = opcount_u8.least_seconds(
        rows, batches, ABOUT["rows"], 128, 10, PEAKS)
    assert bound == ("compute" if rows >= 1024 else "memory")
    delta = {"serve_queries_total": float(rows),
             "serve_batches_total": float(batches), STEPS: 1.0}
    for scan_s in (least, 1.5 * least, 2 * rows * ABOUT["rows"] * 128
                   / 197e12 + least):
        share = roofline.read(record(
            traced_metrics_delta=delta,
            u8={"scan_s": scan_s, "rest_bytes_per_row": 136.0}))
        assert 0 < share <= 100.0 + 1e-9
