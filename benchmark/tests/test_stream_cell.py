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
    assert set(last["metrics"]) == {"stream_rows_per_s", "setup_s"}
    seen = checks(out)
    assert {"recall_at_k", "dist_rel_err_max", "deleted_id_returned",
            "compiled_in_window", "answers_misshapen_or_failed",
            "checkpoints_reached", "probe_touched_by_inserts_share",
            "probe_touched_by_deletes_share"} <= set(seen)
    assert all(seen.values())
    assert "launcher: host mirror of" in out
    # the walker's own record: whole cycles that add up to the window,
    # each the sum of its steps, and every answer checked before `correct`
    said = {ln.split()[0]: json.loads(ln.split(" ", 1)[1])
            for ln in out.splitlines()
            if ln.startswith(("window {", "cycles {", "bodies {"))}
    window, cycles = said["window"], said["cycles"]
    assert len(cycles["s"]) == window["cycles"] >= 3
    assert sum(cycles["s"]) == pytest.approx(window["window_s"], abs=1e-3)
    for i, s in enumerate(cycles["s"]):
        steps = cycles["insert"][i] + cycles["search"][i] + cycles["delete"][i]
        assert steps == pytest.approx(s, abs=1e-4)
        assert 0 <= cycles["empty"][i] <= s and cycles["turn"][i] >= 0
    per_cycle = 1024 // 256 + 2 * (256 // 128)  # the cut mix's requests
    assert window["attempted"] == per_cycle * window["cycles"]
    assert window["answers_checked"] == 4 * window["cycles"]
    assert f"checked {4 * window['cycles']} answers of" in out
    assert said["bodies"]["cycles"] == 30  # all of the book's, up front


def test_stream_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=4.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    # at least these: a later PR may add a metric to the cell's list
    assert allowed >= {"device_idle_pct.stream", "stream_tile_roofline",
                       "write_ms_per_krow", "write_lock_wait_ms",
                       "write_step_share_pct", "mutate_scatter_roofline",
                       "cycle_stall_pct", "cycle_median_ms"}
    # no device trace on the CPU: the host's and the program's own remain
    assert {"write_ms_per_krow", "write_lock_wait_ms", "cycle_stall_pct",
            "cycle_median_ms", "write_step_share_pct"} <= set(
        last["metrics"]) <= allowed
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


# ---- the walker against a stub server: what it does inside the window ------


class Stub:
    """A server that acknowledges every write and answers every query
    with ids ``600 + row * k + j`` (the upper half of a cluster's base ids
    is out of every delete's reach), or what ``answer`` makes of that."""

    def __init__(self, dim, k, answer=None):
        import http.server
        import threading

        stub = self
        self.dim, self.k, self.answer, self.queries = dim, k, answer, 0

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                if self.path == "/upsert":
                    doc = {"upserted": len(body) // (4 + 4 * stub.dim)}
                elif self.path == "/delete":
                    doc = {"deleted": len(body) // 4}
                else:
                    rows = len(body) // (4 * stub.dim)
                    ids = np.arange(rows * stub.k).reshape(rows, stub.k)
                    doc = {"ids": (600 + ids).tolist(),
                           "dists": (ids % stub.k + 1.0).tolist()}
                    stub.queries += 1
                    if stub.answer:
                        doc = stub.answer(stub.queries, doc)
                data = json.dumps(doc).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        self.server = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                      Handler)
        self.server.daemon_threads = True
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        self.url = "http://127.0.0.1:%d" % self.server.server_address[1]

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


def stub_walk(answer=None, cycles=4, in_flight=2):
    """A walk of ``cycles`` window cycles (one warm before) against the
    stub; returns (driver module, walk, book, mix, what happened between
    the window's first request and its last answer)."""
    import threading

    drv = load_by_path("drivers", "serve_stream")
    gen = load_by_path("datagen", "clustered_f32_stream")
    config = {"rows": 4096, "dim": 100, "k": 10,
              "data": {"clusters": 4, "block_rows": 64, "cluster_sigma": 0.25,
                       "sub_sigma": 0.15, "sigma": 0.08}}
    mix = {"range_rows": 128, "max_cycles": 12, "warm_cycles": 1,
           "checkpoints": [1, 3], "query_pool_rows": 64, "probe_rows": 16,
           "write_rows_per_request": 64, "rows_per_request": 16,
           "search_in_flight": in_flight}
    book = runbook.plan(config, mix, 3)
    subs = gen.sub_centres(3, config["data"], 100, book["cluster_of_block"])
    pool = gen.query_rows(3, config["data"],
                          runbook.pool_targets(book, mix, 3), subs)
    seen = {"bodies": 0, "threads": [], "window": False}
    made, started = gen.host_block, threading.Thread.start

    def host_block(*a, **kw):
        seen["bodies"] += seen["window"]
        return made(*a, **kw)

    def start(thread):
        if seen["window"]:
            seen["threads"].append(thread.name)
        return started(thread)

    gen.host_block = host_block
    bodies = drv.write_bodies(config, mix, book, subs, gen, 3)
    stub = Stub(100, 10, answer)
    threading.Thread.start = start
    try:
        walk = drv.Walk(stub.url, mix, book, bodies, pool, 10.0)
        t = walk.cycle(0, 0, False, 0.0)
        seen["window"] = True
        for w in range(1, cycles + 1):
            t = walk.cycle(w, w, True, t)
        seen["window"] = False
        walk.close()
    finally:
        threading.Thread.start = started
        stub.stop()
    return drv, walk, book, mix, seen


def test_nothing_is_made_and_no_thread_started_inside_the_window():
    drv, walk, book, mix, seen = stub_walk()
    assert seen["bodies"] == 0 and seen["threads"] == []
    assert len(walk.cycles) == 4 and len(walk.answers) == 4 * 4
    assert len(walk.requests) == 4 * (2 + 2) and all(
        ok for _, ok, _ in walk.requests)
    # an answer is kept as the bytes that came: nothing parsed on the way
    assert all(isinstance(a[4], bytes) and a[3] == 200 for a in walk.answers)
    # the deletions acknowledged before a step's first request: the cycles
    # before it, the warm one among them
    assert sorted({(w, gone) for w, gone, *_ in walk.answers}) == [
        (1, 1), (2, 2), (3, 3), (4, 4)]


def test_every_answer_of_the_window_reaches_the_checker():
    drv, walk, book, mix, _ = stub_walk()
    checked = drv.check_answers(walk.answers, book, mix, 10, probe_lo=16)
    assert len(checked["requests"]) == len(walk.answers) == 16
    assert all(ok for _, ok, _ in checked["requests"])
    assert checked["deleted_returned"] == 0
    assert sorted(checked["probe"]) == [1, 3]  # the mix's checkpoints
    ids, dists = checked["probe"][3]
    assert ids.shape == dists.shape == (16, 10)


@pytest.mark.parametrize("fault, number", [
    ("deleted_id_in_the_last_answer", "deleted_returned"),
    ("an_answer_not_ascending", "requests"),
    ("an_answer_cut_short", "requests"),
])
def test_a_wrong_answer_anywhere_in_the_window_is_caught(fault, number):
    """Planted where the answer is produced, in the window's LAST search
    step and off the probe block: the check put off to the window's end
    still sees every answer, each against the deletes acknowledged before
    its own step."""
    def answer(n, doc):
        if n == 4 + 16:  # the warm cycle's four, then the window's last
            if fault == "deleted_id_in_the_last_answer":
                doc["ids"][5][9] = gone_id[0]
            elif fault == "an_answer_not_ascending":
                doc["dists"][3][2] = 0.5
            else:
                doc["ids"] = doc["ids"][:-1]
        return doc

    config, mix = {"rows": 4096, "data": {"clusters": 4, "block_rows": 64}}, {
        "range_rows": 128, "max_cycles": 12}
    # an id of the range window cycle 3 deletes: live until then
    gone_id = [runbook.plan(config, mix, 3)["cycles"][3]["delete"][0] + 7]
    drv, walk, book, mix, _ = stub_walk(answer)
    checked = drv.check_answers(walk.answers, book, mix, 10, probe_lo=16)
    if number == "deleted_returned":
        assert checked["deleted_returned"] == 1
        # the same id in an answer BEFORE its delete was acknowledged is
        # no fault: the check follows each step's own acknowledgements
        early = [(w, min(gone, 3), lo, st, data)
                 for w, gone, lo, st, data in walk.answers]
        assert drv.check_answers(early, book, mix, 10, 16)[
            "deleted_returned"] == 0
    else:
        assert [ok for _, ok, _ in checked["requests"]].count(False) == 1


def test_cycle_seconds_partition_a_cycle():
    drv = load_by_path("drivers", "serve_stream")
    spans = [[(0.0, 1.0), (1.1, 2.0)],  # the writer: a turn-round of 0.1
             [(2.2, 3.0), (3.05, 4.0)], [(2.2, 3.5)],  # two readers
             [(4.2, 5.0)]]
    c = drv.cycle_seconds(0.0, 5.0, {"insert": 2.0, "search": 2.1,
                                     "delete": 0.9}, spans)
    assert c["s"] == 5.0 and c["turn"] == pytest.approx(0.1 + 0.05)
    # no request in flight: 1.0-1.1, 2.0-2.2, 4.0-4.2
    assert c["empty"] == pytest.approx(0.5)


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
