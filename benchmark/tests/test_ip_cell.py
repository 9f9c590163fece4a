"""The inner-product serving cell on the CPU: ``drivers/serve_ip.py`` and
``serve_launcher_ip.py`` through ``run.py --allow-cpu`` in a temporary copy
at a few thousand rows (the width as published), five faults planted in the
program — one that normalises the corpus rows (cosine), one that measures
L2, one that centres the queries by the corpus mean, one that clamps the
dissimilarity at zero, one that returns the ten SMALLEST inner products —
each seen as not correct, the two ``ip_*`` readers on a hand-built ``run``
record, the data's two laws, and ``reference_ip`` against numpy in
float64."""

import json
import os

import numpy as np
import pytest

from benchmark import reference_ip
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "serve-text2image10m-ip-bulk"
CONFIG = "text2image10m-200-ip"
ROWS = 8192

# a launcher of the copy only: the program altered in the child that holds
# the device, then the cell's own launcher (kept beside it as *_real.py)
PLANTED = '''"""serve_launcher_ip with a fault planted in the program."""
import os
import sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # the copy first
import jax.numpy as jnp
import numpy as np
import mpi_knn_tpu.serve as serve
from benchmark import serve_launcher_ip_real as real
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.ops import distance

FAULT = "{fault}"
sound = serial.pairwise_dist


def faulty(x, y, metric, x_sq=None, y_sq=None, precision=None):
    assert metric == "ip"
    if FAULT == "corpus_rows_normalised":  # cosine similarity, unclamped
        unit = y / jnp.linalg.norm(y, axis=-1, keepdims=True)
        return sound(x, unit, metric, precision=precision)
    if FAULT == "l2_returned":
        return distance.pairwise_sq_l2(x, y, precision=precision)
    if FAULT == "clamped_at_zero":
        return jnp.maximum(sound(x, y, metric, precision=precision), 0.0)
    if FAULT == "smallest_products":  # +<q, c> ascending
        return -sound(x, y, metric, precision=precision)
    raise SystemExit("no such fault " + FAULT)


if FAULT == "queries_centred":
    # what the engine does for L2, done where it must not be
    build, submit = serve.build_index, serve.ServeSession.submit
    mean = []

    def build_and_keep_mean(X, cfg):
        mean.append(np.asarray(X, dtype=np.float64).mean(axis=0))
        return build(X, cfg)

    def submit_centred(self, queries, *a, **kw):
        q = (np.asarray(queries) - mean[0]).astype(np.float32)
        return submit(self, q, *a, **kw)

    serve.build_index = build_and_keep_mean
    serve.ServeSession.submit = submit_centred
else:
    serial.pairwise_dist = faulty
sys.exit(real.main())
'''

FAULTS = {
    # the fault: a number that fails for it (every fault moves both)
    "corpus_rows_normalised": "recall_at_k",
    "l2_returned": "recall_at_k",
    "queries_centred": "recall_at_k",
    "clamped_at_zero": "dist_rel_err_max",
    "smallest_products": "recall_at_k",
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("ip")))
    b = os.path.join(root, "benchmark")

    def cut(c):
        c["rows"] = ROWS
        c["knn"].update(corpus_tile=2048)
        c["slo"].update(max_batch_rows=256)
        c["data"]["centres"] = 16  # 512 rows a class, as 9256 at full size

    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"), cut)
    small_copy.edit_json(
        os.path.join(b, "traffic", "bulk-saturated-ip.json"),
        lambda t: t.update(
            trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[256],
            rows_per_request={"law": "fixed", "rows": 256}))
    return root


def test_ip_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    assert "check recall_at_k" in out and "check dist_rel_err_max" in out
    assert "check compiled_in_window" in out
    assert "launcher: reference for 256 probe rows" in out


def test_ip_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    assert allowed >= {
        "device_idle_pct.tput", "tile_roofline", "server_empty_pct",
        "dispatch_lag_ms.tput", "request_edge_ms.tput",
        "ip_dist_us_per_step", "ip_rest_us_per_step"}
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    assert last["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(copy, fault):
    b = os.path.join(copy, "benchmark")
    own = os.path.join(b, "serve_launcher_ip.py")
    real = os.path.join(b, "serve_launcher_ip_real.py")
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


def test_the_parent_refuses_the_cell_at_once(copy):
    """A program whose ``KNNConfig`` knows no ``"ip"`` (the parent commit)
    ends the run with a code other than 0 and no result line, soon."""
    b = os.path.join(copy, "benchmark")
    path = os.path.join(b, "configs", CONFIG + ".json")
    before = open(path).read()
    small_copy.edit_json(path, lambda c: c["knn"].update(metric="no-such"))
    try:
        rc, last, out = small_copy.run_cell(copy, CELL, seconds=1.0,
                                            timeout=300)
    finally:
        with open(path, "w") as f:
            f.write(before)
    assert rc != 0 and last is None
    assert "metric must be one of" in out


# ---- the readers, on a hand-built record ---------------------------------

STEPS = 'knn_dist_tile_steps_total{path="ip"}'


def record(**over):
    run = {
        "trace": {"busy_s": 10.0, "window_s": 10.0},
        "traced_metrics_delta": {STEPS: 80000.0, "serve_batches_total": 69.0},
        "scopes": {"knn.dist_ip": 8.0, "knn.select/bins": 1.2,
                   "knn.merge": 0.001},
    }
    run.update(over)
    return run


def test_readers_split_a_step_between_the_dot_and_the_rest():
    dist = load_by_path("layer_metrics", "ip_dist_us_per_step")
    rest = load_by_path("layer_metrics", "ip_rest_us_per_step")
    assert dist.read(record()) == pytest.approx(100.0)  # 8 s / 80000 steps
    assert rest.read(record()) == pytest.approx(25.0)  # (10 - 8) s / 80000
    assert dist.read(record()) + rest.read(record()) == pytest.approx(
        1e6 * 10.0 / 80000)  # the two add up to the step


@pytest.mark.parametrize("missing", [
    {"scopes": None},  # a trace that names no scope (the CPU; a cached program)
    {"scopes": {"knn.dist_cosine": 8.0}},  # a program without the ip scope
    {"traced_metrics_delta": None},
    {"traced_metrics_delta": {"serve_batches_total": 69.0}},  # no counter
    {"traced_metrics_delta": {STEPS: 0.0}},  # none moved
    {"trace": None},
], ids=lambda m: next(iter(m)) + "=" + str(next(iter(m.values())))[:24])
def test_readers_return_nothing_where_there_is_nothing_to_read(missing):
    """The parent commit has no such counter or scope: no number, no raise."""
    for name in ("ip_dist_us_per_step", "ip_rest_us_per_step"):
        reader = load_by_path("layer_metrics", name)
        if name == "ip_dist_us_per_step" and "trace" in missing:
            continue  # the dot's time needs no busy time
        assert reader.read(record(**missing)) is None


# ---- the data's laws -----------------------------------------------------

SPEC = {"centres": 64, "offset": 0.5, "sigma": 0.03536, "scale_min": 0.4,
        "scale_max": 2.0,
        "queries": {"mix": [0.6, 0.3, 0.15], "topics": 256, "topic": 0.4,
                    "shift": 0.3, "q_sigma": 0.05, "norm_sigma": 0.25}}
DIM = 200


def laws(seed=2**31 + 3, rows=20000, nq=256):
    gen = load_by_path("datagen", "crossmodal_f32_ip")
    cen = gen.centres(seed, SPEC, DIM)
    corpus = gen.host_corpus_rows(np.random.default_rng(5), rows, cen, SPEC)
    q = gen.host_rows(np.random.default_rng([seed, 0x71]), nq, cen, SPEC)
    return cen, corpus, q


def top10(score):
    return np.argsort(-score, axis=1, kind="stable")[:, :10]


def test_corpus_rows_are_fractional_uncentred_and_vary_in_length():
    cen, x, _ = laws()
    np.testing.assert_allclose(np.linalg.norm(cen[:-1], axis=1), 1.0,
                               rtol=1e-5)
    assert np.linalg.norm(cen[-1]) == pytest.approx(SPEC["offset"], rel=1e-5)
    assert x.dtype == np.float32 and (x != np.rint(x)).mean() > 0.99
    norms = np.linalg.norm(x.astype(np.float64), axis=1)
    assert norms.max() / norms.min() > 4.0  # the issue's "factor of 4"
    # the corpus mean is visibly not zero: about E[s] * o, as long as the
    # shortest rows
    mean = x.astype(np.float64).mean(axis=0)
    assert np.linalg.norm(mean) > 0.4
    cos = mean @ cen[-1] / (np.linalg.norm(mean) * SPEC["offset"])
    assert cos > 0.95


def test_queries_are_of_another_law_than_the_corpus():
    cen, x, q = laws()
    x, q = x.astype(np.float64), q.astype(np.float64)
    # another norm law: log-normal about 1.1, not log-uniform in [0.5, 2.7]
    qn, xn = np.linalg.norm(q, axis=1), np.linalg.norm(x, axis=1)
    assert 0.95 < np.median(qn) < 1.25 and np.std(np.log(qn)) < 0.3
    assert np.std(np.log(xn)) > 0.4
    # another centre: no class direction explains a query as it explains a
    # corpus row (a row's own class: cosine ~0.8; a query's best: ~0.55,
    # more where two of its three classes are one)
    unit = cen[:-1].astype(np.float64)
    best_q = ((q / qn[:, None]) @ unit.T).max(axis=1)
    best_x = ((x / xn[:, None]) @ unit.T).max(axis=1)
    assert np.percentile(best_q, 95) < 0.65 < 0.7 < best_x.min()
    # a component the corpus does not have: the queries' common part (their
    # mean direction) is nearly orthogonal to the corpus's (its offset)
    q_mean = (q / qn[:, None]).mean(axis=0)
    x_mean = (x / xn[:, None]).mean(axis=0)
    cos = q_mean @ x_mean / (np.linalg.norm(q_mean) * np.linalg.norm(x_mean))
    assert abs(cos) < 0.35
    # another spread about the mixture's centre: q_sigma**2 d = 0.5 where
    # the corpus has sigma**2 d = 0.25
    assert SPEC["queries"]["q_sigma"] ** 2 * DIM == pytest.approx(0.5)
    assert SPEC["sigma"] ** 2 * DIM == pytest.approx(0.25, rel=1e-3)


def test_inner_product_ranks_apart_from_cosine_l2_and_centred():
    """The shares the planted faults rest on: the ten rows of largest
    inner product are another set than the ten by cosine, by L2, and by the
    inner product of queries centred by the corpus mean, for the great
    majority of query rows; and the scores are a fair share of |q| |c|."""
    _, x, q = laws()
    x, q = x.astype(np.float64), q.astype(np.float64)
    s = q @ x.T
    xn = np.linalg.norm(x, axis=1)
    ip = top10(s)

    def differs(other):
        return np.mean([set(a) != set(b) for a, b in zip(ip, other)])

    assert differs(top10(s / xn[None, :])) > 0.99  # cosine
    assert differs(top10(2 * s - (xn ** 2)[None, :])) > 0.99  # L2
    assert differs(top10((q - x.mean(axis=0)) @ x.T)) > 0.9  # centred
    assert differs(top10(-s)) == 1.0  # the ten smallest
    kth = np.take_along_axis(s, ip, axis=1)
    share = kth / (np.linalg.norm(q, axis=1)[:, None] * xn[ip])
    assert share.min() > 0.25  # the 10th largest, of |q| |c|
    assert kth.min() > 0  # so a clamp at zero erases every answer


def test_device_corpus_follows_the_seed_and_the_law():
    gen = load_by_path("datagen", "crossmodal_f32_ip")
    a = np.asarray(gen.device_corpus(2**31 + 9, 4096, DIM, SPEC, 1024))
    b = np.asarray(gen.device_corpus(2**31 + 9, 4096, DIM, SPEC, 1024))
    c = np.asarray(gen.device_corpus(2**31 + 10, 4096, DIM, SPEC, 1024))
    assert a.shape == (4096, DIM) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    norms = np.linalg.norm(a.astype(np.float64), axis=1)
    # |a_j + o + sigma g| = sqrt(1 + 0.25 + 0.25) = 1.22 times the scale
    assert 0.4 < norms.min() < 0.6 and 2.2 < norms.max() < 2.9
    cen = gen.centres(2**31 + 9, SPEC, DIM)
    mean = a.astype(np.float64).mean(axis=0)
    assert mean @ cen[-1] / (np.linalg.norm(mean) * 0.5) > 0.9


# ---- the reference -------------------------------------------------------


def float64_ip_knn(corpus, q, k):
    s = q.astype(np.float64) @ corpus.astype(np.float64).T
    ids = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return -np.take_along_axis(s, ids, axis=1), ids


def test_reference_ip_against_numpy_float64():
    _, corpus, q = laws(rows=4096, nq=37)  # 37: not a multiple of q_chunk
    d, i = reference_ip.exact_knn_ip(corpus, q, 10, block_rows=1024)
    want_d, want_i = float64_ip_knn(corpus, q, 10)
    assert d.shape == (37, 10) and i.dtype == np.int32
    assert np.all(np.diff(d, axis=1) >= 0) and np.all(d < 0)
    # a 200-term float32 sum against float64: a few 1e-7 of the score
    assert (np.abs(d - want_d) / np.abs(want_d)).max() < 1e-6
    assert (i == want_i).mean() > 0.98  # near-ties may swap


def test_reference_ip_signs_zero_rows_and_ties():
    rng = np.random.default_rng(3)
    corpus = rng.standard_normal((512, 64)).astype(np.float32)
    corpus[7] = 0.0  # a zero row scores 0 against everything: kept, no test
    corpus[300] = corpus[5]  # a duplicate: the lower id first
    q = np.concatenate([corpus[5:6], -corpus[5:6],
                        np.zeros((1, 64), np.float32)])
    d, i = reference_ip.exact_knn_ip(corpus, q, 4, block_rows=128)
    assert list(i[0, :2]) == [5, 300] and d[0, 0] == d[0, 1] < 0
    assert not {5, 300} & set(i[1])  # the mirrored query: its own row last
    # a zero query scores 0 everywhere: ids by position, -0.0 == 0.0
    np.testing.assert_array_equal(d[2], np.zeros(4, np.float32))
    np.testing.assert_array_equal(i[2], [0, 1, 2, 3])
    # all-negative scores: positive "distances", the zero row's 0 first
    d_neg, i_neg = reference_ip.exact_knn_ip(
        -np.abs(corpus), np.abs(q[:1]), 3)
    assert i_neg[0, 0] == 7 and d_neg[0, 0] == 0
    assert np.all(d_neg[:, 1:] > 0) and np.all(np.diff(d_neg, axis=1) >= 0)
    # the reference holds no program code and no matmul
    src = open(reference_ip.__file__).read()
    assert "mpi_knn_tpu" not in src.split('"""', 2)[2]
    for word in ("dot_general", "matmul", "einsum", " @ ", "jnp.dot"):
        assert word not in src.split('"""', 2)[2]
