"""The cosine serving cell on the CPU: ``drivers/serve_cos.py`` and
``serve_launcher_cos.py`` through ``run.py --allow-cpu`` in a temporary copy
at a few thousand rows (the width as published), two faults planted in the
program — the corpus rows' norms left at one, the query side's
normalisation skipped — seen as not correct, the two ``cos_*`` readers on a
hand-built ``run`` record, the generator's law, and ``reference_cosine``
against numpy in float64."""

import json
import os

import numpy as np
import pytest

from benchmark import reference_cosine
from benchmark.harness import load_by_path
from benchmark.tests import small_copy

CELL = "serve-dbpedia1m-cos-bulk"
CONFIG = "dbpedia-openai1m-1536-cos"
ROWS = 8192

# a launcher of the copy only: the program altered in the child that holds
# the device, then the cell's own launcher (kept beside it as *_real.py)
PLANTED = '''"""serve_launcher_cos with a fault planted in the program."""
import os
import sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # the copy first
import jax.numpy as jnp
from benchmark import serve_launcher_cos_real as real
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.serve import index as serve_index

FAULT = "{fault}"
if FAULT == "corpus_norms_left_at_one":
    def ones(tiles, metric):
        return jnp.ones(tiles.shape[:2], jnp.float32)
    serial._stack_norms = serve_index._stack_norms = ones
elif FAULT == "query_normalisation_skipped":
    serial.unit_rows = lambda x: x
sys.exit(real.main())
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = small_copy.make(str(tmp_path_factory.mktemp("cos")))
    b = os.path.join(root, "benchmark")

    def cut(c):
        c["rows"] = ROWS
        c["knn"].update(corpus_tile=2048)
        c["slo"].update(max_batch_rows=256)

    small_copy.edit_json(os.path.join(b, "configs", CONFIG + ".json"), cut)
    small_copy.edit_json(
        os.path.join(b, "traffic", "bulk-saturated-cos.json"),
        lambda t: t.update(
            trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[256],
            rows_per_request={"law": "fixed", "rows": 256}))
    return root


def test_cosine_cell_end_to_end_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"rows_per_s", "setup_s"}
    assert "check recall_at_k" in out and "check dist_rel_err_max" in out
    assert "check compiled_in_window" in out
    assert "launcher: reference for 256 probe rows" in out


def test_cosine_cell_traced_line(copy):
    rc, last, out = small_copy.run_cell(copy, CELL, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    allowed = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    # at least these: a later PR may add a metric to the cell's list
    assert allowed >= {"device_idle_pct.tput", "tile_roofline",
                       "cos_dist_us_per_step", "cos_rest_us_per_step"}
    assert set(last["metrics"]) <= allowed  # no device trace on the CPU
    assert last["correct"] is True


@pytest.mark.parametrize("fault", ["corpus_norms_left_at_one",
                                   "query_normalisation_skipped"])
def test_a_planted_fault_is_not_correct(copy, fault):
    b = os.path.join(copy, "benchmark")
    own = os.path.join(b, "serve_launcher_cos.py")
    real = os.path.join(b, "serve_launcher_cos_real.py")
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
    # unscaled corpus rows change who is nearest; an unscaled query row
    # scales all its distances alike, so only the distances say so
    number = {"corpus_norms_left_at_one": "recall_at_k",
              "query_normalisation_skipped": "dist_rel_err_max"}[fault]
    assert any(number in ln for ln in failed), out[-3000:]


# ---- the readers, on a hand-built record ---------------------------------

STEPS = 'knn_dist_tile_steps_total{path="cosine"}'


def record(**over):
    run = {
        "trace": {"busy_s": 10.0, "window_s": 10.0},
        "traced_metrics_delta": {STEPS: 8000.0, "serve_batches_total": 65.0},
        "scopes": {"knn.dist_cosine": 8.0, "knn.select/bins": 1.2,
                   "knn.qunit": 0.001},
    }
    run.update(over)
    return run


def test_readers_split_a_step_between_the_dot_and_the_rest():
    dist = load_by_path("layer_metrics", "cos_dist_us_per_step")
    rest = load_by_path("layer_metrics", "cos_rest_us_per_step")
    assert dist.read(record()) == pytest.approx(1000.0)  # 8 s / 8000 steps
    assert rest.read(record()) == pytest.approx(250.0)  # (10 - 8) s / 8000
    assert dist.read(record()) + rest.read(record()) == pytest.approx(
        1e6 * 10.0 / 8000)  # the two add up to the step


@pytest.mark.parametrize("missing", [
    {"scopes": None},  # a trace that names no scope (the CPU; a cached program)
    {"scopes": {"knn.dist": 8.0}},  # a program without the cosine scope
    {"traced_metrics_delta": None},
    {"traced_metrics_delta": {"serve_batches_total": 65.0}},  # no counter
    {"traced_metrics_delta": {STEPS: 0.0}},  # none moved
    {"trace": None},
], ids=lambda m: next(iter(m)) + "=" + str(next(iter(m.values())))[:24])
def test_readers_return_nothing_where_there_is_nothing_to_read(missing):
    """The parent commit has no such counter or scope: no number, no raise."""
    for name in ("cos_dist_us_per_step", "cos_rest_us_per_step"):
        reader = load_by_path("layer_metrics", name)
        if name == "cos_dist_us_per_step" and "trace" in missing:
            continue  # the dot's time needs no busy time
        assert reader.read(record(**missing)) is None


# ---- the data's law ------------------------------------------------------

SPEC = {"centres": 64, "sigma": 0.0128, "scale_min": 0.5, "scale_max": 2.0}


def test_rows_are_fractional_scaled_and_cluster_by_cosine():
    gen = load_by_path("datagen", "clustered_f32_embed")
    dim = 1536
    cen = gen.centres(2**31 + 3, SPEC, dim)
    np.testing.assert_allclose(np.linalg.norm(cen, axis=1), 1.0, rtol=1e-5)
    x = gen.host_rows(np.random.default_rng(5), 2000, cen, SPEC)
    assert x.dtype == np.float32 and (x != np.rint(x)).mean() > 0.99
    norms = np.linalg.norm(x.astype(np.float64), axis=1)
    # |c + sigma g| = sqrt(1.25) times a scale in [0.5, 2]: far from unit
    assert norms.min() < 0.7 and norms.max() > 1.9
    unit = x / norms[:, None]
    cos = unit @ cen.T.astype(np.float64)
    own = cos.max(axis=1)
    assert np.all(own > 0.85) and np.all(own < 0.94)  # 1 / sqrt(1.25) = 0.894
    other = np.sort(cos, axis=1)[:, -2]
    assert other.max() < 0.25  # another class is ~orthogonal


def test_device_corpus_follows_the_seed_and_the_law():
    gen = load_by_path("datagen", "clustered_f32_embed")
    a = np.asarray(gen.device_corpus(2**31 + 9, 4096, 256, SPEC, 1024))
    b = np.asarray(gen.device_corpus(2**31 + 9, 4096, 256, SPEC, 1024))
    c = np.asarray(gen.device_corpus(2**31 + 10, 4096, 256, SPEC, 1024))
    assert a.shape == (4096, 256) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    norms = np.linalg.norm(a.astype(np.float64), axis=1)
    # sigma^2 d = 0.042 at this width: |row| = scale * sqrt(1.042)
    assert 0.45 < norms.min() < 0.6 and 1.9 < norms.max() < 2.2
    logs = np.log(norms / np.sqrt(1 + SPEC["sigma"] ** 2 * 256))
    assert abs(logs.mean()) < 0.05  # log-uniform about 1


# ---- the reference -------------------------------------------------------


def float64_cosine_knn(corpus, q, k):
    c64, q64 = corpus.astype(np.float64), q.astype(np.float64)
    cn = np.sqrt(np.maximum((c64 * c64).sum(1), reference_cosine.NORM_EPS))
    qn = np.sqrt(np.maximum((q64 * q64).sum(1), reference_cosine.NORM_EPS))
    d = np.maximum(1.0 - (q64 @ c64.T) / (qn[:, None] * cn[None, :]), 0.0)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, axis=1), ids


def test_reference_cosine_against_numpy_float64():
    gen = load_by_path("datagen", "clustered_f32_embed")
    rng = np.random.default_rng(11)
    cen = gen.centres(11, SPEC, 384)
    corpus = gen.host_rows(rng, 4096, cen, SPEC)
    q = gen.host_rows(rng, 37, cen, SPEC)  # not a multiple of q_chunk
    d, i = reference_cosine.exact_knn_cosine(corpus, q, 10, block_rows=1024)
    want_d, want_i = float64_cosine_knn(corpus, q, 10)
    assert d.shape == (37, 10) and i.dtype == np.int32
    assert np.all(np.diff(d, axis=1) >= 0)
    # a 384-term float32 sum against float64: a few 1e-7 of the similarity
    np.testing.assert_allclose(d, want_d, rtol=2e-5, atol=1e-6)
    assert (i == want_i).mean() > 0.98  # near-ties may swap


def test_reference_cosine_zero_rows_scale_and_ties():
    rng = np.random.default_rng(3)
    corpus = rng.standard_normal((512, 64)).astype(np.float32)
    corpus[7] = 0.0  # no direction: distance 1 from everything
    corpus[300] = corpus[5] * 3.0  # same direction, another length
    q = np.concatenate([corpus[5:6] * 0.25, np.zeros((1, 64), np.float32)])
    d, i = reference_cosine.exact_knn_cosine(corpus, q, 4, block_rows=128)
    # the query's own direction twice, at distance ~0, the lower id first
    # when the two round alike
    assert set(i[0, :2]) == {5, 300} and np.all(d[0, :2] < 1e-6)
    # a zero query is at distance 1 from every row: ids by position
    np.testing.assert_array_equal(d[1], np.ones(4, np.float32))
    np.testing.assert_array_equal(i[1], [0, 1, 2, 3])
    d7, _ = reference_cosine.exact_knn_cosine(corpus[7:8], corpus[:3], 1)
    np.testing.assert_array_equal(d7[:, 0], np.ones(3, np.float32))
    # the reference holds no program code and no matmul
    src = open(reference_cosine.__file__).read()
    assert "mpi_knn_tpu" not in src.split('"""', 2)[2]
    for word in ("dot_general", "matmul", "einsum", " @ ", "jnp.dot"):
        assert word not in src.split('"""', 2)[2]
