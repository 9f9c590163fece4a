"""Range search on the system's normal path (``backends/range_scan.py``,
``serve/engine.py``'s range family, ``frontend/server.py``): every corpus
row strictly under a radius, for every query row, against the benchmark's
plain reference (``benchmark/reference_range.py``: the direct form, no
matrix multiplication, nothing of the program) on seeded whole-number rows
at small sizes — both forms of the scan (the fused kernel's ranged form,
interpreted here, and the counting scan of the small buckets), a byte stack
and a float32 stack of the same rows."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from benchmark import reference_range
from mpi_knn_tpu import KNNConfig
from mpi_knn_tpu.backends import range_scan
from mpi_knn_tpu.config import RangeCapError
from mpi_knn_tpu.frontend.coalesce import Coalescer
from mpi_knn_tpu.frontend.scheduler import Rejection, SLOPolicy
from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.resilience import ResiliencePolicy
from mpi_knn_tpu.serve import ServeSession, build_index, build_index_blocks
from mpi_knn_tpu.serve import aotcache
from mpi_knn_tpu.serve import engine
from mpi_knn_tpu.serve.engine import query_knn, query_range

RADIUS = 1000.0
CAP = 512
# query rows of the world below, by what lies within RADIUS of each
EMPTY, ONE, HUNDREDS, OVER_CAP, AT_RADIUS, COPY = range(6)


def _cfg(q_tile, c_tile=1024, dtype="uint8", **kw):
    return KNNConfig(k=10, backend="serial", dtype=dtype, query_tile=q_tile,
                     corpus_tile=c_tile, query_bucket=q_tile,
                     exclude_self=False, range_cap=CAP, **kw)


def _near(rng, row, n, most):
    """``n`` rows within ``most`` of ``row``: a few columns moved by 1-3."""
    out = np.repeat(row[None, :].astype(np.int64), n, axis=0)
    for r in out:
        cols = rng.choice(row.size, size=rng.integers(1, most // 9), replace=False)
        step = rng.integers(1, 4, cols.size) * rng.choice((-1, 1), cols.size)
        r[cols] = np.clip(r[cols] + step, 0, 255)
    return out


@pytest.fixture(scope="module")
def world():
    """(corpus (m, 128) uint8, queries (6, 128) float32): random bytes (a
    million apart) with rows planted about the queries."""
    rng = np.random.default_rng(54)
    d, m = 128, 4096 + 100
    x = rng.integers(0, 256, (m, d)).astype(np.int64)
    q = rng.integers(40, 216, (6, d)).astype(np.int64)
    free = iter(rng.permutation(m))

    def plant(rows):
        for r in rows:
            x[next(free)] = r

    plant(_near(rng, q[ONE], 1, 100))
    plant(_near(rng, q[HUNDREDS], 300, 100))
    plant(_near(rng, q[OVER_CAP], CAP + 7, 100))
    at = q[AT_RADIUS].copy()
    at[:10] += 10  # 10 x 10^2: AT the radius, out
    under = q[AT_RADIUS].copy()
    under[:111] += 3  # 111 x 3^2 = 999: under it by 1, in
    plant([at, under])
    plant([q[COPY], q[COPY]])  # two exact copies: distance 0
    plant(_near(rng, q[COPY], 3, 100))
    return x.astype(np.uint8), q.astype(np.float32)


def _reference(x, q, radius, exclude_zero=True):
    return reference_range.range_search_blocks(
        lambda b: x, [x.shape[0]], q, radius, exclude_zero=exclude_zero)


def _same(got, want):
    lims, dists, ids = got
    assert (np.asarray(lims) == want[0]).all()
    assert (np.asarray(ids) == want[2]).all()
    assert (np.asarray(dists) == want[1]).all()  # whole numbers: equality


# (scan form, stack): the kernel's ranged form wants 256 rows a query tile
# (``ops/topk.py fused_scan_engages``), the small bucket counts alone
FORMS = [("counted", 64, "uint8"), ("counted", 64, "float32"),
         ("kernel", 256, "uint8"), ("kernel", 256, "float32")]


@pytest.fixture(scope="module")
def indexes(world):
    x, _ = world
    return {(q_tile, dtype): build_index(
        x if dtype == "uint8" else x.astype(np.float32), _cfg(q_tile, dtype=dtype))
        for _, q_tile, dtype in FORMS}


@pytest.mark.parametrize("form, q_tile, dtype", FORMS)
def test_every_row_within_the_radius_equals_the_reference(
        world, indexes, form, q_tile, dtype):
    x, q = world
    index = indexes[q_tile, dtype]
    assert range_scan.range_engages(
        q_tile, 1024, 128, index.tiles.dtype.itemsize) == (form == "kernel")
    rows = [EMPTY, ONE, HUNDREDS, AT_RADIUS, COPY]
    got = query_range(q[rows], RADIUS, index)
    want = _reference(x, q[rows], RADIUS)
    _same(got, want)
    per_row = np.diff(got[0])
    assert per_row[0] == 0 and per_row[1] == 1 and per_row[2] == 300
    # AT the radius is out, under it by 1 is in; the copies (0) are left
    # out, their near neighbours kept
    assert per_row[3] == 1 and got[1][got[0][3]] == 999.0
    assert per_row[4] == 3 and (got[1][got[0][4]:] > 0).all()


@pytest.mark.parametrize("q_tile", [64, 256])
def test_exact_zero_distances_are_kept_without_exclude_zero(world, q_tile):
    x, q = world
    index = build_index(x, _cfg(q_tile, exclude_zero=False))
    got = query_range(q[[COPY]], RADIUS, index)
    _same(got, _reference(x, q[[COPY]], RADIUS, exclude_zero=False))
    assert got[0][-1] == 5 and (got[1][:2] == 0).all()
    assert got[2][0] < got[2][1]  # ties by the lower id


def test_a_row_that_overflows_the_lists_takes_the_second_path(world, indexes):
    """300 results over 128 lanes of depth 4: some lane is past its depth,
    the lists lost a result, and the row is answered whole all the same."""
    x, q = world
    index = indexes[256, "uint8"]
    reg = obs_metrics.get_registry()

    def read(name, **labels):
        return reg.counter(name, labels=labels or None).value

    before = {n: read(n) for n in (
        "knn_range_overflow_rows_total", "knn_range_rows_total",
        "knn_range_results_total", "knn_range_refused_rows_total")}
    steps = read("knn_dist_tile_steps_total", path="range")
    _same(query_range(q[[ONE, HUNDREDS]], RADIUS, index),
          _reference(x, q[[ONE, HUNDREDS]], RADIUS))
    assert read("knn_range_overflow_rows_total") - before[
        "knn_range_overflow_rows_total"] == 1  # HUNDREDS; ONE sat in a list
    assert read("knn_range_rows_total") - before["knn_range_rows_total"] == 2
    assert read("knn_range_results_total") - before[
        "knn_range_results_total"] == 301
    assert read("knn_range_refused_rows_total") == before[
        "knn_range_refused_rows_total"]
    assert read("knn_dist_tile_steps_total", path="range") - steps == \
        index.tiles.shape[0]


@pytest.mark.parametrize("q_tile", [64, 256])
def test_a_row_over_the_cap_is_refused_by_name_never_cut(world, indexes,
                                                         q_tile):
    x, q = world
    with pytest.raises(RangeCapError) as e:
        query_range(q[[ONE, OVER_CAP, EMPTY]], RADIUS, indexes[q_tile, "uint8"])
    assert e.value.rows == [(1, CAP + 7)] and e.value.cap == CAP
    assert "row 1: 519" in str(e.value)


def test_radii_are_a_row_s_own(world, indexes):
    x, q = world
    radii = np.array([RADIUS, 30.0, 6.0], np.float32)
    rows = q[[HUNDREDS, HUNDREDS, HUNDREDS]]
    got = query_range(rows, radii, indexes[256, "uint8"])
    want = _reference(x, rows, radii)
    _same(got, want)
    assert np.diff(got[0])[0] > np.diff(got[0])[1] > np.diff(got[0])[2]


def test_results_past_the_first_flat_piece_come_from_the_second(
        world, monkeypatch):
    """The flat answers leave the device in two pieces and the host
    fetches the second only where a query tile's results pass the first:
    with a first piece of 128 results, 301 come from both."""
    x, q = world
    monkeypatch.setattr(range_scan, "HEAD_RESULTS", 128)
    index = build_index(x, _cfg(64).replace(range_cap=CAP - 1))  # a new trace
    rows = [ONE, HUNDREDS, EMPTY, COPY]
    _same(query_range(q[rows], RADIUS, index), _reference(x, q[rows], RADIUS))
    few = [ONE, COPY]  # under the first piece: the second is not read
    _same(query_range(q[few], RADIUS, index), _reference(x, q[few], RADIUS))


def test_range_bound_is_the_largest_float32_under_the_radius():
    under = range_scan.range_bound(np.array([96237.0, 1.0, 0.0, np.inf]))
    assert under[0] < 96237.0 and np.nextafter(
        under[0], np.float32(np.inf)) == np.float32(96237.0)
    assert 0 < under[1] < 1.0
    assert under[2] < 0 and under[3] < 0  # nothing is under such a radius


# ---- the width of the published rows: extreme bytes at d = 256 -------------


def _extreme(rng, m=2048, d=256):
    """Rows of 0 and 255 in every column, and rows that mix the two: the
    largest norms, dots and distances a byte stack can hold."""
    x = np.zeros((m, d), np.int64)
    x[1::4] = 255
    x[2::4] = rng.choice((0, 255), (len(x[2::4]), d))
    x[3::4] = rng.integers(0, 256, (len(x[3::4]), d))
    return x.astype(np.uint8)


@pytest.mark.parametrize("q_tile", [64, 256])
def test_extreme_bytes_at_256_columns_against_an_int64_sum(q_tile):
    """Every partial sum of ``x_sq - 2xy + y_sq`` on the CENTRED rows stays
    a whole number float32 holds (256 x 255^2 < 2^24; the dot's are even
    and under 2^25): range answers and k-NN distances EQUAL int64's."""
    rng = np.random.default_rng(7)
    x = _extreme(rng)
    q = np.stack([np.zeros(256), np.full(256, 255.0),
                  rng.choice((0.0, 255.0), 256),
                  x[3].astype(np.float64)]).astype(np.float32)
    index = build_index(x, _cfg(q_tile).replace(range_cap=4096))
    exact = ((x.astype(np.int64)[None] - q.astype(np.int64)[:, None]) ** 2
             ).sum(axis=2)
    assert exact.max() == 256 * 255 ** 2  # 16 646 400, 0.8 % under 2^24
    # a radius in the middle of the extreme distances, and one past them all
    for radius in (float(np.median(exact)), 256.0 * 255 ** 2 + 1):
        lims, dists, ids = query_range(q, radius, index)
        for r in range(len(q)):
            want = np.nonzero((exact[r] < radius) & (exact[r] > 0))[0]
            want = want[np.lexsort((want, exact[r][want]))]
            assert (ids[lims[r]:lims[r + 1]] == want).all()
            assert (dists[lims[r]:lims[r + 1]] == exact[r][want]).all()
    _same(query_range(q, float(np.median(exact)), index),
          _reference(x, q, float(np.median(exact))))
    # the k-NN program over the same byte stack: the same whole numbers
    res = query_knn(q, index)
    for r in range(len(q)):
        live = np.where(exact[r] > 0, exact[r], np.iinfo(np.int64).max)
        assert (np.asarray(res.dists[r]) == np.sort(live)[:10]).all()


# ---- the share and the deployment ------------------------------------------


def test_two_shares_concatenated_equal_the_uncut_reference(world):
    """The corpus split by rows into two shares, each served alone: the
    two answers concatenated and re-sorted are the reference's over the
    whole (range search needs no merge)."""
    x, q = world
    rows = [EMPTY, ONE, HUNDREDS, AT_RADIUS, COPY]
    cut = 2100  # off the tile grid
    shares = [(0, x[:cut]), (cut, x[cut:])]
    answers = [(lo, query_range(q[rows], RADIUS, build_index(part, _cfg(64))))
               for lo, part in shares]
    want = _reference(x, q[rows], RADIUS)
    for r in range(len(rows)):
        d = np.concatenate([a[1][a[0][r]:a[0][r + 1]] for _, a in answers])
        i = np.concatenate([lo + a[2][a[0][r]:a[0][r + 1]]
                            for lo, a in answers])
        order = np.lexsort((i, d))
        span = slice(want[0][r], want[0][r + 1])
        assert (i[order] == want[2][span]).all()
        assert (d[order] == want[1][span]).all()
    assert sum(int(a[0][-1]) for _, a in answers) == want[0][-1]


# ---- the front end: requests, coalescing, HTTP -----------------------------


@pytest.fixture(scope="module")
def frontend(world, indexes):
    session = ServeSession(indexes[64, "uint8"],
                           resilience=ResiliencePolicy())
    fe = Frontend(session, SLOPolicy(
        max_batch_rows=64, max_wait_s=0.2, max_queue_rows=512))
    fe.start(warm_sizes=[64], background=False)
    server = FrontendHTTPServer(fe, host="127.0.0.1", port=0,
                                request_timeout_s=60.0, quiet=True).start()
    yield fe, server
    server.stop()
    fe.stop()


def test_warm_reaches_the_range_family_keyed_apart(frontend, indexes):
    fe, _ = frontend
    index = indexes[64, "uint8"]
    kinds = {key[0] for key in index._cache if isinstance(key[0], str)}
    assert engine.RANGE_KIND in kinds
    cfg = fe.session.cfg
    assert (64, engine._fingerprint_cfg(cfg)) in index._cache
    assert (engine.RANGE_KIND, 64,
            engine._fingerprint_cfg(cfg)) in index._cache
    assert fe.session.warm_report["cells"] >= 2
    assert aotcache.fingerprint(index, cfg, 64, engine.RANGE_KIND) != \
        aotcache.fingerprint(index, cfg, 64)


def test_two_requests_of_different_radii_share_a_batch(world, frontend):
    x, q = world
    fe, _ = frontend
    batches = obs_metrics.get_registry().counter("serve_batches_total")
    before = batches.value
    a = fe.submit("tenant-a", q[[HUNDREDS, EMPTY]], radius=RADIUS)
    b = fe.submit("tenant-b", q[[HUNDREDS, ONE, COPY]], radius=300.0)
    got_a, got_b = a.result(60), b.result(60)
    assert batches.value - before == 1  # coalesced: one batch, two radii
    _same(got_a, _reference(x, q[[HUNDREDS, EMPTY]], RADIUS))
    _same(got_b, _reference(x, q[[HUNDREDS, ONE, COPY]], 300.0))
    assert got_b[0][0] == 0 and len(got_b[0]) == 4  # offsets of its own


def test_a_request_over_the_cap_fails_alone_in_its_batch(world, frontend):
    x, q = world
    fe, _ = frontend
    ok = fe.submit("tenant-a", q[[ONE]], radius=RADIUS)
    bad = fe.submit("tenant-b", q[[EMPTY, OVER_CAP]], radius=RADIUS)
    _same(ok.result(60), _reference(x, q[[ONE]], RADIUS))
    with pytest.raises(RangeCapError) as e:
        bad.result(60)
    assert e.value.rows == [(1, CAP + 7)]  # as the request numbers its rows


def test_range_and_knn_requests_never_share_a_batch():
    c = Coalescer(max_batch_rows=64, max_wait_s=0.0)
    c.admit("a", "k1", 4, 0.0)
    c.admit("b", "r1", 4, 0.0, radius=5.0)
    c.admit("a", "k2", 4, 0.0)
    c.admit("b", "r2", 4, 0.0, radius=7.0)
    kinds = []
    while (batch := c.pop_ready(1.0)) is not None:
        kinds.append([r.queries for r in batch.parts])
        radii = batch.radii
        assert (radii is None) == (batch.parts[0].radius is None)
        if radii is not None:
            assert radii.tolist() == [5.0] * 4 + [7.0] * 4
    assert sorted(map(sorted, kinds)) == [["k1", "k2"], ["r1", "r2"]]


def _post(url, body, headers):
    req = urllib.request.Request(url + "/query", data=body, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_round_trip_in_the_suite_s_range_format(world, frontend):
    x, q = world
    _, server = frontend
    rows = q[[HUNDREDS, EMPTY, ONE]]
    want = _reference(x, rows, RADIUS)
    raw = {"Content-Type": "application/octet-stream"}
    status, doc = _post(server.url, rows.astype("<f4").tobytes(),
                        {**raw, "X-Radius": "1000.0"})
    assert status == 200
    assert set(doc) == {"rows", "metric", "radius", "lims", "dists", "ids"}
    assert doc["rows"] == 3 and doc["metric"] == "l2"
    assert doc["radius"] == 1000.0
    _same((doc["lims"], doc["dists"], doc["ids"]), want)
    # the JSON form names its radius in the body
    status, again = _post(
        server.url, json.dumps({"queries": rows.tolist(),
                                "radius": 1000}).encode(),
        {"Content-Type": "application/json"})
    assert status == 200 and again == doc
    # without a radius: k nearest, as ever, (rows, k)
    status, knn = _post(server.url, rows.astype("<f4").tobytes(), raw)
    assert status == 200 and set(knn) == {"rows", "metric", "dists", "ids"}
    assert np.asarray(knn["ids"]).shape == (3, 10)
    # over the cap: 422, the row and its true count, nothing cut
    status, err = _post(server.url, q[[OVER_CAP]].astype("<f4").tobytes(),
                        {**raw, "X-Radius": "1000"})
    assert status == 422 and err["error"] == "range-cap"
    assert err["rows"] == [[0, CAP + 7]] and err["cap"] == CAP
    # what cannot be honoured is a 400 with its reason
    for headers, body in (
            ({**raw, "X-Radius": "-1"}, rows),
            ({**raw, "X-Radius": "nan"}, rows),
            ({**raw, "X-Radius": "wide"}, rows),
            ({**raw, "X-Radius": "1000"}, rows + 0.5)):
        status, err = _post(server.url, body.astype("<f4").tobytes(), headers)
        assert status == 400 and err["error"], (headers, err)


def test_knn_answers_do_not_move_on_an_index_that_also_answers_range(
        world, indexes):
    x, q = world
    plain = build_index(x, _cfg(64).replace(range_cap=0))
    a, b = query_knn(q, indexes[64, "uint8"]), query_knn(q, plain)
    assert (np.asarray(a.ids) == np.asarray(b.ids)).all()
    assert (np.asarray(a.dists) == np.asarray(b.dists)).all()


def test_a_range_batch_passes_the_sentinel_and_a_poisoned_one_trips(
        world, indexes, monkeypatch):
    """Rows with NO result are answers, not the all-inf rows the sentinel
    holds a k-NN batch to; a NaN among the results still trips it."""
    x, q = world
    session = ServeSession(indexes[64, "uint8"],
                           resilience=ResiliencePolicy())
    radii = np.full(2, RADIUS, np.float32)
    session.submit(q[[EMPTY, EMPTY]], radii=radii)
    (res,) = session.drain()
    assert res.range_answer[0].tolist() == [0, 0, 0]
    monkeypatch.setenv("TKNN_FAULTS", "serve-nan=nan")
    session.submit(q[[ONE, EMPTY]], radii=radii)
    with pytest.raises(engine.PoisonedResultError):
        session.drain()


# ---- what range search does not run on yet ---------------------------------


@pytest.mark.parametrize("change, why", [
    (dict(metric="cosine", dtype="float32"), "squared L2"),
    (dict(metric="ip", dtype="float32", exclude_zero=False), "squared L2"),
    (dict(backend="ring", dtype="float32"), "fixed k"),
    (dict(backend="ring-overlap", dtype="float32"), "fixed k"),
    (dict(partitions=8, dtype="float32"), "clustered"),
    (dict(precision_policy="mixed", dtype="float32"), "exact"),
    (dict(dtype="bfloat16"), "rounds its rows"),
    (dict(dtype="float32", bucket_headroom=0.25), "frozen"),
    (dict(range_cap=-1), ">= 0"),
])
def test_what_the_configuration_refuses_range_search_with(change, why):
    with pytest.raises(ValueError, match=why):
        _cfg(64).replace(**change)


def test_what_a_build_refuses_range_search_with(world):
    x, q = world
    # fractional float32 rows: the screened and six-pass forms
    with pytest.raises(ValueError, match="whole-number rows"):
        build_index(x.astype(np.float32) + 0.25, _cfg(64, dtype="float32"))
    # a predicate
    bags = (np.arange(x.shape[0] + 1), np.zeros(x.shape[0], np.int64))
    with pytest.raises(ValueError, match="no predicate"):
        build_index(x.astype(np.float32), _cfg(64, dtype="float32"), tags=bags)
    # a width at which a sum could pass 2^24
    wide = np.zeros((256, 264), np.uint8)
    with pytest.raises(ValueError, match="at most 256 wide"):
        build_index(wide, _cfg(64))
    # several devices under backend="auto" make a ring
    with pytest.raises(ValueError, match="serial"):
        build_index(x.astype(np.float32),
                    _cfg(64, dtype="float32").replace(backend="auto"))
    # rows in blocks reach the same checks
    with pytest.raises(ValueError, match="whole-number rows"):
        build_index_blocks(x.shape, [x.astype(np.float32) + 0.5],
                           _cfg(64, dtype="float32"))


def test_what_a_request_is_refused_with(world, indexes, frontend):
    x, q = world
    fe, _ = frontend
    index = indexes[64, "float32"]
    session = ServeSession(index)
    # writes: the index is frozen
    for write in (lambda: session.upsert([1], x[:1].astype(np.float32)),
                  lambda: session.delete([1])):
        with pytest.raises(ValueError, match="frozen"):
            write()
    # a radius against an index built without range_cap
    plain = build_index(x, _cfg(64).replace(range_cap=0))
    with pytest.raises(ValueError, match="k-NN alone"):
        query_range(q[:1], RADIUS, plain)
    with pytest.raises(ValueError, match="k-NN alone"):
        ServeSession(plain).submit(q[:1], radii=[RADIUS])
    # fractional query rows, a predicate beside the radius, radii that do
    # not number the rows
    with pytest.raises(ValueError, match="whole-number query rows"):
        fe.submit("t", q[:1] + 0.5, radius=RADIUS)
    with pytest.raises(ValueError, match="no predicate"):
        fe.submit("t", q[:1], filters=np.zeros((1, 1), np.int64),
                  radius=RADIUS)
    with pytest.raises(ValueError, match="one a row"):
        session.submit(q[:2], radii=[RADIUS])
    assert not isinstance(fe.submit("t", q[:1], radius=RADIUS), Rejection)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    names = {n.module or "" for n in ast.walk(ast.parse(
        inspect.getsource(reference_range))) if isinstance(n, ast.ImportFrom)}
    names |= {a.name for n in ast.walk(ast.parse(inspect.getsource(
        reference_range))) if isinstance(n, ast.Import) for a in n.names}
    assert not any(n.startswith("mpi_knn_tpu") for n in names), names
    src = inspect.getsource(reference_range)
    code = src.split('"""', 2)[2]  # past the module docstring
    assert not any(w in code for w in ("jnp.dot", "matmul", "einsum", " @ "))
