"""The streaming cell on the CPU: ``drivers/serve_stream.py`` and
``serve_launcher_stream.py`` through ``run.py --allow-cpu`` in a temporary
copy at 32 k rows (the width as published); three faults planted in the
program, each of which must print ``"correct": false`` — a server that
acknowledges and drops inserts, a delete that tombstones nothing, an upsert
whose rows land and whose norms do not — and the blind-cell guard (probes
far from every touched range); the runbook's arithmetic, the readers on a
hand-built record, and ``reference_stream`` against numpy in float64."""

import json
import os

import numpy as np
import pytest

from benchmark import opcount_stream, reference_stream, runbook
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "stream-msturing10m-runbook"
CONFIG = "msturing10m-100-l2-stream"

# a launcher of the copy only: the program altered in the child that holds
# the device, then the cell's own launcher (kept beside it as *_real.py)
PLANTED = '''"""serve_launcher_stream with a fault planted in the program."""
import os
import sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # the copy first
from benchmark import serve_launcher_stream_real as real
from mpi_knn_tpu.serve import mutate
from mpi_knn_tpu.serve.engine import ServeSession

FAULT = "{fault}"
if FAULT == "inserts_acknowledged_and_dropped":
    ServeSession.upsert = lambda self, ids, rows, tenant=None: {{
        "upserted": len(ids)}}
elif FAULT == "delete_tombstones_nothing":
    ServeSession.delete = lambda self, ids, tenant=None: {{
        "deleted": len(ids), "missing": 0}}
elif FAULT == "upsert_leaves_norms_stale":
    import jax

    def rows_land_norms_do_not(rows, new_ids, tpos, spos, clear_t, clear_s,
                               tiles, tile_ids, tile_sqs, cfg, by_tile):
        tiles, tile_ids, _ = mutate.serial_upsert_chunk(
            rows, new_ids, tpos, spos, clear_t, clear_s, tiles, tile_ids,
            tile_sqs, cfg, by_tile)
        return tiles, tile_ids, tile_sqs

    mutate.serial_upsert_jit = jax.jit(
        rows_land_norms_do_not, static_argnames=("cfg", "by_tile"),
        donate_argnums=(6, 7, 8))
sys.exit(real.main())
'''


def cut(root: str) -> None:
    b = os.path.join(root, "benchmark")

    def config(c):
        c["rows"] = 32768
        c["knn"].update(corpus_tile=2048, bucket_headroom=0.1,
                        mutation_bucket=128)
        c["slo"].update(max_batch_rows=256)
        c["data"].update(block_rows=128)

    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"), config)
    small_copy.edit_json(
        os.path.join(b, "traffic", "runbook-steady.json"),
        lambda t: t.update(
            range_rows=256, write_rows_per_request=128, rows_per_request=256,
            query_pool_rows=1024, warm_cycles=1, max_cycles=30,
            checkpoints=[1, 3, 5], warm_sizes=[256], trace_seconds=0.5))


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("stream")))
    cut(root)
    return root


def checks(out: str) -> dict:
    return {ln.split()[1].rstrip(":"): ln.endswith("ok")
            for ln in out.splitlines() if ln.startswith("check ")
            and not ln.startswith("check info")}


def test_stream_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=4.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0, out[-3000:]
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    seen = checks(out)
    assert {"recall_at_k", "dist_rel_err_max", "deleted_id_returned",
            "compiled_in_window", "answers_misshapen_or_failed",
            "checkpoints_reached", "probe_touched_by_inserts_share",
            "probe_touched_by_deletes_share"} <= set(seen)
    assert all(seen.values())
    assert "launcher: host mirror of" in out


def test_stream_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=4.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    assert allowed == {"device_idle_pct.tput", "tile_roofline",
                       "write_ms_per_krow", "write_lock_wait_ms",
                       "write_step_share_pct", "mutate_scatter_roofline"}
    # no device trace on the CPU: the host's and the program's own remain
    assert {"write_ms_per_krow", "write_lock_wait_ms",
            "write_step_share_pct"} <= set(last["metrics"]) <= allowed
    assert last["correct"] is True, out[-3000:]


@pytest.mark.parametrize("fault,number", [
    ("inserts_acknowledged_and_dropped", "recall_at_k"),
    ("delete_tombstones_nothing", "deleted_id_returned"),
    ("upsert_leaves_norms_stale", "dist_rel_err_max"),
])
def test_a_planted_fault_is_not_correct(copy, fault, number):
    b = os.path.join(copy, "benchmark")
    own = os.path.join(b, "serve_launcher_stream.py")
    real = os.path.join(b, "serve_launcher_stream_real.py")
    os.rename(own, real)
    with open(own, "w") as f:
        f.write(PLANTED.format(fault=fault))
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=4.0)
    finally:
        os.replace(real, own)
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    assert checks(out)[number] is False, out[-3000:]


def test_probes_far_from_every_touched_range_are_not_correct(copy):
    """The blind-cell guard: with every probe row drawn near an untouched
    block the answers are right and say nothing about the writes."""
    b = os.path.join(copy, "benchmark")
    path = os.path.join(b, "runbook.py")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace("kind, turn = j % 8, j // 8",
                             "kind, turn = 7, j // 8"))
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=4.0)
    finally:
        with open(path, "w") as f:
            f.write(text)
    assert rc == 0, out[-3000:]
    seen = checks(out)
    assert last["correct"] is False
    assert seen["recall_at_k"] and seen["deleted_id_returned"]
    assert not seen["probe_touched_by_inserts_share"]
    assert not seen["probe_touched_by_deletes_share"]


# ---- the runbook's arithmetic ---------------------------------------------


def small():
    config = {"rows": 4096, "data": {"clusters": 4, "block_rows": 64}}
    mix = {"range_rows": 128, "max_cycles": 12, "warm_cycles": 1,
           "checkpoints": [1, 3], "query_pool_rows": 64}
    return config, mix


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_ranges_are_one_cluster_s_and_never_overlap(seed):
    config, mix = small()
    book = runbook.plan(config, mix, seed)
    per = config["rows"] // 4
    seen = np.zeros(book["ids"], dtype=int)
    for i, c in enumerate(book["cycles"]):
        lo, hi = c["delete"]
        assert lo // per == (hi - 1) // per == c["delete_cluster"]
        seen[lo:hi] += 1
        lo, hi = c["insert"]
        assert lo == config["rows"] + i * 128
        blocks = runbook.blocks_of(c["insert"], 64)
        assert (book["cluster_of_block"][blocks] == c["insert_cluster"]).all()
        assert c["insert_cluster"] != c["delete_cluster"]
        seen[lo:hi] += 1
    assert seen.max() == 1  # no id written twice
    kinds = [s["operation"] for s in runbook.steps(book["cycles"][0])]
    assert kinds == ["insert", "search", "delete"]


def test_pool_mix_holds_in_every_eight_rows():
    config, mix = small()
    book = runbook.plan(config, mix, 3)
    t = runbook.pool_targets(book, mix, 3)
    base = config["rows"] // 64
    assert (t.reshape(-1, 8)[:, :4] >= base).all()  # near inserted blocks
    assert (t.reshape(-1, 8)[:, 4:] < base).all()
    gone = {b for c in book["cycles"][:3] for b in
            runbook.blocks_of(c["delete"], 64)}
    assert set(t.reshape(-1, 8)[:, 4:7].ravel().tolist()) <= gone
    assert not set(t.reshape(-1, 8)[:, 7].tolist()) & gone


# ---- the reference against numpy in float64 -------------------------------


def test_stream_model_against_float64():
    rng = np.random.default_rng(5)
    base = rng.standard_normal((512, 100)).astype(np.float32) * 0.3
    more = rng.standard_normal((64, 100)).astype(np.float32) * 0.3
    q = rng.standard_normal((9, 100)).astype(np.float32) * 0.3
    model = reference_stream.StreamModel([(0, base), (512, more)], 512)
    model.apply({"operation": "insert", "start": 512, "end": 544})
    model.apply({"operation": "search"})
    model.apply({"operation": "delete", "start": 100, "end": 300})
    d, i = model.exact_knn_live(q, 10, block_rows=64)
    allrows = np.concatenate([base, more]).astype(np.float64)
    d2 = ((q[:, None, :].astype(np.float64) - allrows[None]) ** 2).sum(-1)
    d2[:, ~model.live] = np.inf
    want = np.argsort(d2, axis=1, kind="stable")[:, :10]
    assert (i == want).all()
    assert np.allclose(d, np.take_along_axis(d2, want, 1), rtol=1e-5)
    assert not ((i >= 100) & (i < 300)).any() and not (i >= 544).any()
    gone = np.zeros(model.ids, bool)
    gone[100:300] = True
    _, stayed = model.exact_knn_live(q, 10, also_live=gone, block_rows=64)
    d2 = ((q[:, None, :].astype(np.float64) - allrows[None]) ** 2).sum(-1)
    d2[:, 544:] = np.inf
    assert (stayed == np.argsort(d2, axis=1, kind="stable")[:, :10]).all()


def test_ties_go_to_the_lower_id():
    rows = np.zeros((128, 100), np.float32)
    rows[64:] = 1.0
    model = reference_stream.StreamModel([(0, rows[:64]), (64, rows[64:])],
                                         128)
    _, i = model.exact_knn_live(np.zeros((1, 100), np.float32), 4,
                                block_rows=32)
    assert i.tolist() == [[0, 1, 2, 3]]


# ---- the readers, on a hand-built record ----------------------------------


def record(**over):
    run = {
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "scopes": {"knn.mutate/upsert": 2e-3, "knn.mutate/delete": 1e-3,
                   "knn.dist_multipass": 5.0},
        "traced_metrics_delta": {"mutation_upserts_total": 81920.0,
                                 "mutation_deletes_total": 81920.0},
        "window_metrics_delta": {
            "mutation_upserts_total": 409600.0,
            "mutation_deletes_total": 409600.0,
            "mutation_latency_seconds_sum": 4.096,
            'mutation_lock_wait_seconds_total{side="batch"}': 0.3,
            'mutation_lock_wait_seconds_total{side="mutation"}': 0.1,
            'mutation_lock_waits_total{side="batch"}': 200.0,
            'mutation_lock_waits_total{side="mutation"}': 800.0,
        },
        "stream": {"window_s": 50.0, "dim": 100,
                   "step_s": {"insert": 10.0, "search": 35.0,
                              "delete": 5.0}},
    }
    run.update(over)
    return run


def test_the_four_readers():
    read = {n: load_by_path("layer_metrics", n).read for n in (
        "write_ms_per_krow", "write_lock_wait_ms", "write_step_share_pct",
        "mutate_scatter_roofline")}
    run = record()
    assert read["write_ms_per_krow"](run) == pytest.approx(5.0)
    assert read["write_lock_wait_ms"](run) == pytest.approx(0.4)
    assert read["write_step_share_pct"](run) == pytest.approx(30.0)
    least = 2 * (81920 * 408 + 81920 * 4) / 819e9
    assert opcount_stream.least_seconds(81920, 81920, 100, run[
        "peaks"]) == pytest.approx(least)
    assert read["mutate_scatter_roofline"](run) == pytest.approx(
        100 * least / 3e-3)


@pytest.mark.parametrize("name", [
    "write_ms_per_krow", "write_lock_wait_ms", "write_step_share_pct",
    "mutate_scatter_roofline"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    """A program without the spans, counters and scopes (the parent's)."""
    read = load_by_path("layer_metrics", name).read
    assert read({}) is None
    assert read(record(scopes=None, stream=None,
                       window_metrics_delta={"serve_batches_total": 9.0},
                       traced_metrics_delta={})) is None
