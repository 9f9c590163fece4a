"""The corpus side of cosine is prepared once (ISSUE 32): a tile stack keeps
its rows' inverse norms where an L2 stack keeps squared norms
(``backends.serial.stack_norms``), the query side's unit rows are made once
a query tile (``ops.distance.unit_rows``), and a tile step scales its dot
and normalises nothing. Checked on seeded fractional rows that are NOT unit
length — with unit rows ``1 - q.c`` is right whatever a program skips —
against the benchmark's plain reference (``benchmark/reference_cosine.py``:
the direct form, no matmul).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_cosine
from mpi_knn_tpu import KNNConfig, all_knn, api, build_index, query_knn
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops import distance
from mpi_knn_tpu.ops.topk import init_topk_tiles
from mpi_knn_tpu.serve import ServeSession

M, NQ, DIM, K = 600, 40, 48, 6
# both sides sum DIM float32 products: each dot is off by a few 2^-24 of
# |q||c|, the similarity (~0.8) by a few 1e-7, and a distance of ~0.2 sees
# that four times larger; 2e-5 leaves an order of magnitude over it and is
# far under what a skipped normalisation does (tens of per cent)
DIST_RTOL = 2e-5


def embed_rows(seed: int, n: int, dim: int = DIM, classes: int = 8):
    """``s * (c + sigma g)``: the benchmark generator's law at a small
    size. Fractional float32, row norms spread over [0.5, 2.3]."""
    rng = np.random.default_rng(seed)
    cen = np.random.default_rng(99).standard_normal((classes, dim))
    cen /= np.linalg.norm(cen, axis=1, keepdims=True)
    x = cen[rng.integers(0, classes, n)] + rng.standard_normal(
        (n, dim)) * (0.5 / np.sqrt(dim))
    scale = np.exp(rng.uniform(np.log(0.5), np.log(2.0), n))
    return (x * scale[:, None]).astype(np.float32)


def cfg_for(**kw) -> KNNConfig:
    base = dict(k=K, backend="serial", metric="cosine", query_tile=16,
                corpus_tile=128, query_bucket=16, exclude_zero=False)
    return KNNConfig(**{**base, **kw})


def steps_counted() -> dict:
    reg = obs_metrics.get_registry()
    return {p: reg.counter(obs_metrics.DIST_STEPS, labels={"path": p}).value
            for p in obs_metrics.DIST_PATHS}


@pytest.fixture(autouse=True)
def nothing_remembered():
    api._remembered.clear()
    yield
    api._remembered.clear()


# ---------------------------------------------------------------------------
# the answers, against the plain reference


def answer(path: str, X, Q, cfg):
    if path == "all_knn-host":
        res = all_knn(X, queries=Q, config=cfg)
    elif path == "all_knn-device":
        res = all_knn(jnp.asarray(X), queries=jnp.asarray(Q), config=cfg)
    elif path == "query_knn":
        res = query_knn(Q, build_index(X, cfg))
    else:  # the serving session: dispatch depth 2, retire after sync
        session = ServeSession(build_index(X, cfg))
        session.submit(Q)
        (res,) = session.drain()
    return np.asarray(res.dists), np.asarray(res.ids)


@pytest.mark.parametrize(
    "path", ["all_knn-host", "all_knn-device", "query_knn", "session"])
def test_cosine_answers_match_the_direct_form(path):
    X, Q = embed_rows(1, M), embed_rows(2, NQ)
    assert abs(np.linalg.norm(X, axis=1) - 1).mean() > 0.3  # not unit rows
    want_d, want_i = reference_cosine.exact_knn_cosine(X, Q, K)
    got_d, got_i = answer(path, X, Q, cfg_for())
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=DIST_RTOL)


@pytest.mark.parametrize("path", ["all_knn-device", "query_knn"])
def test_a_zero_corpus_row_is_at_distance_one_from_everything(path):
    X, Q = embed_rows(3, 40), embed_rows(4, 8)
    X[7] = 0.0
    cfg = cfg_for(k=40)  # every row of the corpus answers, the zero row too
    want_d, want_i = reference_cosine.exact_knn_cosine(
        X, Q, 40, block_rows=40)
    got_d, got_i = answer(path, X, Q, cfg)
    np.testing.assert_array_equal(got_d[got_i == 7], np.ones(8, np.float32))
    np.testing.assert_array_equal(want_d[want_i == 7], np.ones(8, np.float32))
    np.testing.assert_allclose(got_d, want_d, rtol=DIST_RTOL)
    assert np.isfinite(got_d).all()


# ---------------------------------------------------------------------------
# the prepared state against the form that normalises at every step


def test_stack_norms_are_the_rows_inverse_norms():
    tiles = jnp.asarray(embed_rows(5, 256).reshape(2, 128, DIM))
    got = np.asarray(serial._stack_norms(tiles, "cosine"))
    want = 1.0 / np.linalg.norm(np.asarray(tiles, np.float64), axis=-1)
    assert got.shape == (2, 128) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # L2 keeps what it kept: the squared norms
    np.testing.assert_allclose(
        np.asarray(serial._stack_norms(tiles, "l2")),
        (np.asarray(tiles, np.float64) ** 2).sum(-1), rtol=1e-6)


def test_prepared_state_answers_as_per_step_normalisation():
    """One tile step both ways: unit query rows and the stack's inverse
    norms (what ``serve_chunk`` hands down) against raw operands normalised
    inside the step (what the ring's rounds hand down)."""
    cfg = cfg_for()
    q, blk = jnp.asarray(embed_rows(6, 16)), jnp.asarray(embed_rows(7, 128))
    q_ids = jnp.full((16,), -1, jnp.int32)
    blk_ids = jnp.arange(128, dtype=jnp.int32)
    inv = serial.stack_norms(blk[None], "cosine")[0]
    prepared = serial.masked_dist_tile(
        distance.unit_rows(q), q_ids, None, blk, blk_ids, inv, cfg)
    per_step = serial.masked_dist_tile(q, q_ids, None, blk, blk_ids, None, cfg)
    # two roundings of one number: a unit row times a raw row, scaled,
    # against a unit row times a unit row
    np.testing.assert_allclose(
        np.asarray(prepared), np.asarray(per_step), atol=1e-6)
    for form in (prepared, per_step):
        np.testing.assert_array_equal(
            np.argsort(np.asarray(form), axis=1)[:, :K],
            np.argsort(np.asarray(per_step), axis=1)[:, :K])
    # and the public kernel, called with no state, is the old one
    np.testing.assert_array_equal(
        np.asarray(distance.pairwise_dist(q, blk, "cosine")),
        np.asarray(distance.pairwise_cosine(q, blk)))


def test_a_remembered_cosine_corpus_equals_the_per_call_passes_bit_for_bit():
    """A hit runs the query side and the tile program over the kept stack
    and inverse norms; ``knn_chunk_update`` norms its chunk inside the call,
    as every call did before there was anything to keep."""
    cfg = cfg_for(query_tile=32)
    X, Q = jnp.asarray(embed_rows(8, M)), jnp.asarray(embed_rows(9, 64))
    reg = obs_metrics.get_registry()
    hits = reg.counter("knn_corpus_prepare_total", labels={"result": "hit"})
    miss = all_knn(X, queries=Q, config=cfg)
    before = hits.value
    hit = all_knn(X, queries=Q, config=cfg)
    assert hits.value - before == 1
    q_tile, c_tile = serial.effective_tiles(cfg, M, 64)
    q_tiles, qid_tiles, c_tiles, c_ids, q_pad = serial.prepare_tiles(
        X, Q, np.full(64, -1, np.int32), cfg, q_tile, c_tile)
    carry = init_topk_tiles(q_pad // q_tile, q_tile, K, dtype=jnp.float32)
    d, i = serial.knn_chunk_update(
        q_tiles, qid_tiles, c_tiles, c_ids, *carry, cfg)
    for got in (miss, hit):
        np.testing.assert_array_equal(
            np.asarray(got.ids), np.asarray(i).reshape(q_pad, K)[:64])
        np.testing.assert_array_equal(
            np.asarray(got.dists), np.asarray(d).reshape(q_pad, K)[:64])


# ---------------------------------------------------------------------------
# no tile step normalises anything; L2 programs hold none of this


def primitives(jaxpr) -> set:
    """Names of every primitive in ``jaxpr`` and the jaxprs nested in it."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= primitives(sub)
    return found


def scans(jaxpr) -> list:
    """Every ``scan`` equation of ``jaxpr``, outermost first."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += scans(sub)
    return out


NORMALISING = {"div", "sqrt", "rsqrt"}


def chunk_jaxpr(metric: str):
    cfg = cfg_for(metric=metric, query_tile=16, corpus_tile=128)
    args = (jnp.zeros((2, 16, DIM)), jnp.zeros((2, 16), jnp.int32),
            *init_topk_tiles(2, 16, K, dtype=jnp.float32),
            jnp.zeros((3, 128, DIM)), jnp.zeros((3, 128), jnp.int32),
            jnp.zeros((3, 128)))
    return jax.make_jaxpr(
        lambda *a: serial.serve_chunk(*a, cfg=cfg))(*args).jaxpr


def test_no_tile_step_of_a_cosine_program_normalises():
    jaxpr = chunk_jaxpr("cosine")
    # the query side's unit rows: in the program, once a query tile ...
    assert NORMALISING & primitives(jaxpr)
    # ... and not in the scan over the corpus tiles (the innermost scan:
    # the outer one walks the query tiles)
    tile_scan = scans(jaxpr)[-1]
    assert tile_scan.params["length"] == 3
    body = tile_scan.params["jaxpr"].jaxpr
    assert "dot_general" in primitives(body)
    assert not NORMALISING & primitives(body)


def test_l2_programs_hold_nothing_of_the_cosine_path():
    """The L2 tile program neither divides nor takes a root, anywhere: the
    four L2 cells run what they ran (checked cell by cell against the
    parent's lowered text with ``scripts/lowered_hashes.py``)."""
    assert not NORMALISING & primitives(chunk_jaxpr("l2"))


# ---------------------------------------------------------------------------
# the instruments


def test_cosine_tile_steps_count_on_a_path_of_their_own():
    cfg = cfg_for()
    X, Q = embed_rows(10, M), embed_rows(11, NQ)
    tiles = -(-M // 128)
    one_shot = all_knn(X, queries=Q, config=cfg)
    q_tiles = -(-NQ // 16)
    np.testing.assert_array_equal(
        np.asarray(one_shot.dist_steps), [0, 0, q_tiles * tiles])

    session = ServeSession(build_index(X, cfg))
    before = steps_counted()
    session.submit(Q[:16])  # one 16-row bucket: one query tile
    session.drain()
    after = steps_counted()
    assert after["cosine"] - before["cosine"] == tiles
    assert after["onepass"] == before["onepass"]
    assert after["multipass"] == before["multipass"]

    # an L2 count still lands on its two paths, a ring's rows summed
    reg = obs_metrics.MetricsRegistry()
    reg.count_dist_steps(np.array([[3, 1], [2, 0]]))
    reg.count_dist_steps(np.array([0, 0, 7]))
    got = {p: reg.counter(obs_metrics.DIST_STEPS, labels={"path": p}).value
           for p in obs_metrics.DIST_PATHS}
    assert got == {"onepass": 5, "multipass": 1, "cosine": 7, "fused": 0,
                   "ip": 0, "u8": 0, "fused_screen": 0}


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_index_gauge_and_build_span_name_the_metric(metric, tmp_path):
    X = embed_rows(12, 256)
    path = str(tmp_path / "flight.jsonl")
    obs_spans.set_recorder(obs_spans.FlightRecorder(path))
    try:
        build_index(X, cfg_for(metric=metric, exclude_zero=True))
        all_knn(jnp.asarray(X), queries=jnp.asarray(X[:16]),
                config=cfg_for(metric=metric, exclude_zero=True))
    finally:
        obs_spans.set_recorder(None)
    gauge = obs_metrics.get_registry().gauge("serve_index_cosine")
    assert gauge.value == float(metric == "cosine")
    spans, _ = obs_spans.reconstruct_spans(obs_spans.read_flight(path))
    by_name = {(s["cat"], s["name"]): s for s in spans}
    for key in (("index", "index-build"), ("api", "prepare")):
        attrs = by_name[key]["attrs"]
        assert attrs["metric"] == metric
        assert attrs["rows"] == 256 and attrs["bytes"] == X.nbytes


# ---------------------------------------------------------------------------
# the entry point: `mpi-knn serve --metric cosine`


def test_serve_cli_serves_a_cosine_index(tmp_path):
    """The serving command builds the index the flag names and answers
    over HTTP what ``all_knn`` answers for the same corpus; the layouts
    that cannot hold a cosine index refuse it with exit 2."""
    import json
    import os
    import subprocess
    import sys
    import time
    import urllib.request

    from mpi_knn_tpu.cli import load_corpus
    from mpi_knn_tpu.frontend.cli import serve_main

    data = "synthetic:512x32c4"
    assert serve_main(["--data", data, "--metric", "cosine",
                       "--partitions", "4", "-q"]) == 2

    ready = tmp_path / "ready.url"
    child = subprocess.Popen(
        [sys.executable, "-m", "mpi_knn_tpu", "serve", "--data", data,
         "--metric", "cosine", "--k", "5", "--bucket", "16",
         "--corpus-tile", "128", "--port", "0", "--platform", "cpu",
         "--ready-file", str(ready), "-q"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        deadline = time.time() + 120
        while not ready.exists() and child.poll() is None \
                and time.time() < deadline:
            time.sleep(0.05)
        assert ready.exists(), f"serve did not come up (exit {child.poll()})"
        url = ready.read_text().strip()
        X, _, _ = load_corpus(data)
        q = embed_rows(13, 8, dim=32)
        body = json.dumps({"queries": q.tolist()}).encode()
        doc = None
        while time.time() < deadline:  # a bucket still warming answers 503
            req = urllib.request.Request(
                url + "/query", data=body, method="POST",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    doc = json.loads(resp.read())
                break
            except urllib.error.HTTPError as e:
                assert e.code == 503, e.code
                time.sleep(0.1)
        assert doc is not None and doc["rows"] == 8
        with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
            metrics = resp.read().decode()
    finally:
        child.terminate()
        child.wait(60)
    want = all_knn(X, queries=q, config=KNNConfig(
        k=5, metric="cosine", backend="serial", query_tile=1024,
        corpus_tile=128))
    assert doc["ids"] == np.asarray(want.ids).tolist()
    # another query-tile height than the server's bucket: the dot blocks
    # its sums otherwise, so the last bit may differ
    np.testing.assert_allclose(
        np.asarray(doc["dists"], np.float32), np.asarray(want.dists),
        rtol=1e-6)
    assert "serve_index_cosine 1" in metrics
    assert 'knn_dist_tile_steps_total{path="cosine"}' in metrics
