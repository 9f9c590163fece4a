"""Serial backend parity vs the reference-semantics oracle (SURVEY.md §4)."""

import contextlib
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import (
    KNNClassifier,
    KNNConfig,
    all_knn,
    build_index,
    knn_classify,
)
from tests.oracle import int_sq_l2, oracle_all_knn


def _blobs(rng, m=200, d=16, C=4, scale=6.0):
    centers = rng.standard_normal((C, d)) * scale
    y = rng.integers(0, C, size=m)
    X = centers[y] + rng.standard_normal((m, d))
    return X.astype(np.float32), y.astype(np.int32)


def _assert_knn_matches(got, want_d, want_i, rtol=1e-3):
    got_d = np.asarray(got.dists)
    got_i = np.asarray(got.ids)
    # distances match per-slot
    np.testing.assert_allclose(got_d, want_d, rtol=rtol, atol=1e-3)
    # id sets match per query (near-tie order may differ under f32)
    for r in range(got_i.shape[0]):
        assert set(got_i[r]) == set(want_i[r]), f"row {r}"


def test_all_pairs_matches_oracle(rng):
    X, _ = _blobs(rng, m=150, d=12)
    cfg = KNNConfig(k=10, query_tile=64, corpus_tile=32)
    got = all_knn(X, config=cfg, backend="serial")
    want_d, want_i = oracle_all_knn(X, k=10)
    _assert_knn_matches(got, want_d, want_i)


def test_query_mode_matches_oracle(rng):
    X, _ = _blobs(rng, m=120, d=8)
    Q = rng.standard_normal((33, 8)).astype(np.float32)
    got = all_knn(X, queries=Q, k=5, backend="serial", query_tile=16, corpus_tile=64)
    want_d, want_i = oracle_all_knn(X, k=5, queries=Q)
    _assert_knn_matches(got, want_d, want_i)


def test_unpadded_shapes_dont_require_divisibility(rng):
    """m and q deliberately not multiples of the tiles (reference required
    P | m, SURVEY.md Q6 — we must not)."""
    X, _ = _blobs(rng, m=101, d=7)
    got = all_knn(X, k=7, backend="serial", query_tile=32, corpus_tile=48)
    want_d, want_i = oracle_all_knn(X, k=7)
    assert got.dists.shape == (101, 7)
    _assert_knn_matches(got, want_d, want_i)


def test_duplicate_points_excluded_by_value(rng):
    """The reference's sqrt(S) != 0 rule drops exact duplicates too
    (SURVEY.md Q3)."""
    X, _ = _blobs(rng, m=40, d=5)
    X[7] = X[3]  # exact duplicate pair
    got = all_knn(X, k=6, backend="serial", query_tile=8, corpus_tile=16)
    ids = np.asarray(got.ids)
    assert 7 not in ids[3] and 3 not in ids[7]
    # with value-exclusion off but self-exclusion on, the duplicate is a
    # legitimate zero-distance neighbor
    got2 = all_knn(
        X, k=6, backend="serial", query_tile=8, corpus_tile=16, exclude_zero=False
    )
    ids2 = np.asarray(got2.ids)
    assert ids2[3][0] == 7 and ids2[7][0] == 3


def test_duplicate_exclusion_at_mnist_scale(rng):
    """Regression: at MNIST-like magnitudes (pixel values 0..255, d=784) the
    matmul-form distance of an exact duplicate pair is a small positive fp
    residue, not 0 — the zero test must be scale-relative to fire."""
    X = (rng.random((64, 784)) * 255.0).astype(np.float32)
    X[11] = X[42]
    got = all_knn(X, k=4, backend="serial", query_tile=32, corpus_tile=32)
    ids = np.asarray(got.ids)
    assert 42 not in ids[11] and 11 not in ids[42]


def test_off_center_cluster_keeps_neighbors(rng):
    """Regression: a tight cluster far from the origin (norm ~1000) must not
    have its genuine neighbors swallowed by the zero-distance threshold —
    mean-centering keeps the relative test honest."""
    offset = np.full(32, 1000.0 / np.sqrt(32), dtype=np.float64)
    X = (offset + rng.standard_normal((20, 32))).astype(np.float32)
    got = all_knn(X, k=5, backend="serial", query_tile=8, corpus_tile=8)
    ids = np.asarray(got.ids)
    assert (ids >= 0).all(), "all neighbors must survive the zero test"
    want_d, want_i = oracle_all_knn(X, k=5)
    np.testing.assert_allclose(
        np.asarray(got.dists), want_d, rtol=1e-3, atol=1e-3
    )


@pytest.mark.parametrize("schedule", ["stream", "twolevel"])
@pytest.mark.parametrize("method", ["exact", "block"])
def test_merge_schedule_method_parity(rng, schedule, method):
    """Every (merge_schedule × exact-family topk_method) combination must
    agree with the oracle — including non-divisible m/q and k spanning
    multiple tiles' survivors."""
    X, _ = _blobs(rng, m=131, d=9)
    got = all_knn(
        X,
        k=9,
        backend="serial",
        query_tile=32,
        corpus_tile=24,
        merge_schedule=schedule,
        topk_method=method,
        topk_block=16,
    )
    want_d, want_i = oracle_all_knn(X, k=9)
    _assert_knn_matches(got, want_d, want_i)


def test_schedule_equivalence_randomized(rng):
    """Seeded randomized sweep: for random (m, d, k, tiles, method) configs
    the two merge schedules must produce identical neighbor id sets and
    distances — the associativity property that makes the schedule a pure
    performance knob."""
    for trial in range(12):
        m = int(rng.integers(20, 220))
        d = int(rng.integers(3, 24))
        k = int(rng.integers(1, 17))
        qt = int(rng.integers(4, 64))
        ct = int(rng.integers(4, 96))
        method = ["exact", "block"][trial % 2]
        X, _ = _blobs(rng, m=m, d=d)
        a = all_knn(X, k=k, backend="serial", query_tile=qt, corpus_tile=ct,
                    merge_schedule="stream", topk_method=method,
                    topk_block=16)
        b = all_knn(X, k=k, backend="serial", query_tile=qt, corpus_tile=ct,
                    merge_schedule="twolevel", topk_method=method,
                    topk_block=16)
        ctx = f"trial={trial} m={m} d={d} k={k} qt={qt} ct={ct} {method}"
        np.testing.assert_array_equal(
            np.asarray(a.dists), np.asarray(b.dists), err_msg=ctx
        )
        for r in range(m):
            assert set(np.asarray(a.ids)[r]) == set(np.asarray(b.ids)[r]), (
                f"{ctx} row {r}"
            )


def test_twolevel_matches_stream_bitwise(rng):
    """The two schedules reduce the same candidate multiset — ids must agree
    exactly (same fp distance values, same tie handling via stable top_k)."""
    X, _ = _blobs(rng, m=97, d=11)
    a = all_knn(X, k=6, backend="serial", query_tile=16, corpus_tile=32,
                merge_schedule="stream")
    b = all_knn(X, k=6, backend="serial", query_tile=16, corpus_tile=32,
                merge_schedule="twolevel")
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_cosine_metric(rng):
    X, _ = _blobs(rng, m=90, d=10)
    got = all_knn(X, k=5, backend="serial", metric="cosine", query_tile=32, corpus_tile=32)
    want_d, want_i = oracle_all_knn(X, k=5, metric="cosine")
    np.testing.assert_allclose(np.asarray(got.dists), want_d, rtol=1e-3, atol=1e-4)


def test_k_larger_than_corpus(rng):
    X, _ = _blobs(rng, m=6, d=4)
    got = all_knn(X, k=10, backend="serial", query_tile=8, corpus_tile=8)
    ids = np.asarray(got.ids)
    # each query has only 5 valid neighbors (self excluded)
    assert ((ids >= 0).sum(axis=1) == 5).all()
    assert np.isinf(np.asarray(got.dists)[:, 5:]).all()


def test_one_based_ids_parity_view(rng):
    X, _ = _blobs(rng, m=30, d=4)
    got = all_knn(X, k=3, backend="serial", query_tile=8, corpus_tile=8)
    one = np.asarray(got.one_based())
    zero = np.asarray(got.ids)
    assert ((one == zero + 1) | (zero < 0)).all()


def test_f64_debug_mode_exact_parity(rng):
    X, _ = _blobs(rng, m=80, d=9)
    got = all_knn(
        X.astype(np.float64),
        k=8,
        backend="serial",
        dtype="float64",
        query_tile=16,
        corpus_tile=32,
    )
    want_d, want_i = oracle_all_knn(X, k=8)
    np.testing.assert_allclose(np.asarray(got.dists), want_d, rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(np.asarray(got.ids), want_i)


def test_classifier_loo_end_to_end(rng):
    X, y = _blobs(rng, m=160, d=10, C=4)
    clf = KNNClassifier(k=5, num_classes=4, backend="serial", query_tile=32, corpus_tile=64)
    report = clf.fit(X, y).loo_report()
    assert report.total == 160
    assert report.matches == int(
        (np.asarray(report.classify.predictions) == y).sum()
    )
    # well-separated blobs: near-perfect leave-one-out accuracy
    assert report.accuracy > 0.95


def test_classifier_one_based_labels(rng):
    X, y = _blobs(rng, m=60, d=6, C=3)
    clf = KNNClassifier(
        k=3, num_classes=3, backend="serial", one_based_labels=True,
        query_tile=16, corpus_tile=32,
    )
    clf.fit(X, y + 1)
    pred = clf.predict(X[:10])
    assert pred.min() >= 1 and pred.max() <= 3


def test_classifier_label_validation(rng):
    X, y = _blobs(rng, m=20, d=4, C=3)
    clf = KNNClassifier(k=3, num_classes=2)
    with pytest.raises(ValueError):
        clf.fit(X, y)  # labels reach 2 >= num_classes


# --- the carried selection: finish once a query tile (ISSUE 33) -------------
# One small shape for every case (32 rows, 4 tiles of 1024 columns, k = 10):
# the interpreted kernels compile once, for the carried and the per-tile form.
# Since ISSUE 35 a second height, 256 rows: the shortest tile whose scan also
# carries the row bound that *bins* tests its chunks against.

_CQ, _CT, _CC, _CD, _CK = 32, 4, 1024, 16, 10
_BQ = 256


def _carried_cfg(rows=_CQ, **kw):
    return KNNConfig(k=_CK, backend="serial", query_tile=rows,
                     corpus_tile=_CC, center=False, **kw)


@functools.lru_cache(maxsize=None)
def _chunk_program(per_tile: bool, rows: int = _CQ):
    """``serve_chunk`` over the small shape under a jit of its own: the
    engaged (carried) program, or — the rule held off — the per-tile
    program that every engaged call ran before."""
    from mpi_knn_tpu.backends import serial

    def run(*args):
        with pytest.MonkeyPatch.context() as mp:
            if per_tile:
                mp.setattr(serial, "carried_depth", lambda *a, **k: None)
            return serial.serve_chunk(*args, cfg=_carried_cfg(rows))

    return jax.jit(run)


def _carried_case(name, rows=_CQ):
    """(queries, corpus rows, ids, incoming carry) of one case; whole-number
    rows, so every distance is exact and equal values mean equal bits."""
    from mpi_knn_tpu.ops.topk import lane_bin_depth

    rng = np.random.default_rng(sum(map(ord, name)))
    q = rng.integers(-20, 20, (rows, _CD)).astype(np.float32)
    c = rng.integers(-20, 20, (_CT * _CC, _CD)).astype(np.float32)
    ids = np.arange(_CT * _CC, dtype=np.int32)
    carry_d = np.full((rows, _CK), np.inf, np.float32)
    carry_i = np.full((rows, _CK), -1, np.int32)
    if name == "incoming-carry":
        # a resumable chunk's, a ring round's: some slots beat the stack
        carry_d[::2, :4] = np.arange(1, 5, dtype=np.float32)
        carry_i[::2, :4] = 900_000 + np.arange(4)
    elif name == "padded-ids":
        ids[-300:] = -1
    elif name == "nan-and-short-rows":
        q[3] = np.nan
        ids[7:] = -1  # seven candidates in all: every row is short of k
    elif name == "cross-tile-collision":
        # R + 2 of row 5's nearest in ONE lane, one or two a tile: no
        # tile's own certificate sees more than two of them
        for j in range(lane_bin_depth(rows, _CC, _CK) + 2):
            at = (j % _CT) * _CC + 37 + 128 * (j // _CT)
            c[at] = q[5]
            c[at, j] += j + 1.0
    return q, c, ids, carry_d, carry_i


@pytest.mark.parametrize("name,rescanned,rows", [
    ("plain", 0, _CQ), ("incoming-carry", 0, _CQ), ("padded-ids", 0, _CQ),
    ("nan-and-short-rows", 1, _CQ), ("cross-tile-collision", 1, _CQ),
    # under the row bound (ISSUE 35)
    ("plain", 0, _BQ), ("incoming-carry", 0, _BQ), ("padded-ids", 0, _BQ),
    ("nan-and-short-rows", 1, _BQ), ("cross-tile-collision", 1, _BQ),
])
def test_carried_selection_equals_per_tile_and_full_width(
        name, rescanned, rows):
    """The engaged ``twolevel`` merge — lists carried through the scan, one
    finish, the certificate once a query tile, flagged rows re-scanned —
    against the per-tile program and against ``lax.top_k`` over the whole
    stack's distances: values equal; ids equal wherever a row's distances
    are distinct. The counter reads which query tiles were re-scanned. At
    256 rows the scan also carries the row bound and *bins* skips the
    chunks that hold nothing under it: the same answers, the re-scan's
    too, and a count of the chunks."""
    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.ops.topk import lane_bin_bound_rides

    q, c, ids, carry_d, carry_i = _carried_case(name, rows)
    assert serial.carried_depth(_carried_cfg(rows), rows, _CC) == (
        4 if rows == _CQ else 5)
    assert lane_bin_bound_rides(rows, _CC) == (rows == _BQ)
    args = (jnp.asarray(q)[None], jnp.full((1, rows), -1, jnp.int32),
            jnp.asarray(carry_d)[None], jnp.asarray(carry_i)[None],
            jnp.asarray(c.reshape(_CT, _CC, _CD)),
            jnp.asarray(ids.reshape(_CT, _CC)),
            jnp.asarray((c * c).sum(1).reshape(_CT, _CC)))
    got_d, got_i, counts = _chunk_program(False, rows)(*args)
    old_d, old_i = _chunk_program(True, rows)(*args)
    assert counts.dist_steps is None
    assert np.asarray(counts.select_tiles).tolist() == [1 - rescanned,
                                                        rescanned]
    if rows == _BQ:  # one chunk a strip of 16 rows and a tile
        assert np.asarray(counts.bins_chunks).sum() == _CT * rows // 16
    else:
        assert counts.bins_chunks is None
    got_d, got_i = np.asarray(got_d)[0], np.asarray(got_i)[0]
    np.testing.assert_array_equal(got_d, np.asarray(old_d)[0])
    # the full-width answer over (incoming carry ‖ every tile)
    d = ((q[:, None, :] - c[None]) ** 2).sum(-1)
    d[:, ids < 0] = np.inf
    d[d <= 0] = np.inf  # query mode drops zero distances
    all_d = np.concatenate([carry_d, d], axis=1)
    all_i = np.concatenate([carry_i, np.broadcast_to(ids, d.shape)], axis=1)
    order = np.argsort(all_d, axis=1, kind="stable")[:, :_CK]
    want_d = np.take_along_axis(all_d, order, 1)
    finite = ~np.isnan(q).any(1)
    np.testing.assert_array_equal(got_d[finite], want_d[finite])
    assert np.isnan(got_d[~finite]).all() or np.isinf(got_d[~finite]).all()
    want_i = np.where(np.isinf(want_d), -1, np.take_along_axis(all_i, order, 1))
    for r in np.flatnonzero(finite):
        # slots whose distance is the row's alone name one candidate
        vals, n = np.unique(all_d[r][np.isfinite(all_d[r])],
                            return_counts=True)
        alone = np.isin(want_d[r], vals[n == 1]) | np.isinf(want_d[r])
        np.testing.assert_array_equal(got_i[r][alone], want_i[r][alone])
    if name == "cross-tile-collision":
        assert got_d[5, :6].tolist() == [1.0, 4.0, 9.0, 16.0, 25.0, 36.0]


def test_the_counter_of_carried_selections_comes_with_the_answer():
    """``knn_select_query_tiles_total{path="carried"|"rescanned"}``: a
    one-shot call carries its count on ``KNNResult.select_tiles``, a served
    batch's is added at retire, after the batch's own sync; a program whose
    scans carry no lists counts nothing. Read on the planted collision: one
    query tile, re-scanned."""
    from mpi_knn_tpu import build_index, query_knn
    from mpi_knn_tpu.obs import metrics as obs_metrics
    from mpi_knn_tpu.serve import ServeSession

    reg = obs_metrics.MetricsRegistry()
    reg.count_select_tiles(np.array([[3, 1], [2, 0]]))  # one row a device
    assert [reg.counter(obs_metrics.SELECT_TILES, labels={"path": p}).value
            for p in obs_metrics.SELECT_PATHS] == [5, 1]

    def counted():
        reg = obs_metrics.get_registry()
        return [reg.counter(obs_metrics.SELECT_TILES, labels={"path": p}).value
                for p in obs_metrics.SELECT_PATHS]

    q, c, *_ = _carried_case("cross-tile-collision")
    cfg = _carried_cfg(query_bucket=_CQ)
    one_shot = all_knn(c, queries=q, config=cfg)
    assert np.asarray(one_shot.select_tiles).tolist() == [0, 1]
    narrow = all_knn(c, queries=q, config=cfg.replace(corpus_tile=512))
    assert narrow.select_tiles is None
    index = build_index(c, cfg)
    before = counted()
    served = query_knn(q, index)
    assert np.asarray(served.select_tiles).tolist() == [0, 1]
    session = ServeSession(index)
    batch, = session.submit(q) + session.drain()
    assert np.asarray(batch.select_tiles).tolist() == [0, 1]
    assert [b - a for a, b in zip(before, counted())] == [0, 2]
    for got in (served, batch):
        np.testing.assert_array_equal(
            np.asarray(got.dists), np.asarray(one_shot.dists))
        np.testing.assert_array_equal(
            np.asarray(got.ids), np.asarray(one_shot.ids))


def test_the_counter_of_bins_chunks_comes_with_the_answer(monkeypatch):
    """``knn_select_bins_chunks_total{path="inserted"|"skipped"}`` (ISSUE
    35): what *bins* did with the chunks of the distance tiles under the
    row bound that rides the scan. A one-shot call carries ``[inserted,
    skipped]`` on ``KNNResult.bins_chunks``, a served batch's is added at
    retire, after the batch's own sync — nothing is fetched or counted
    inside ``knn:batch.enqueue`` — and the two add up to the
    chunks of the scan. A corpus that every query meets in descending order
    of distance inserts them all; a program whose scans carry no lists
    counts nothing, and so does one whose tiles are too short for the bound
    (``ops/topk.py lane_bin_bound_rides``)."""
    from mpi_knn_tpu import build_index, query_knn
    from mpi_knn_tpu.obs import metrics as obs_metrics
    from mpi_knn_tpu.ops.lane_bin import lane_bin_chunks
    from mpi_knn_tpu.serve import ServeSession

    reg = obs_metrics.MetricsRegistry()
    reg.count_bins_chunks(np.array([[3, 1], [2, 6]]))  # one row a device
    assert [reg.counter(obs_metrics.BINS_CHUNKS, labels={"path": p}).value
            for p in obs_metrics.BINS_PATHS] == [5, 7]

    def counted():
        reg = obs_metrics.get_registry()
        return [reg.counter(obs_metrics.BINS_CHUNKS, labels={"path": p}).value
                for p in obs_metrics.BINS_PATHS]

    chunks = _CT * lane_bin_chunks(_BQ, _CC)
    cfg = _carried_cfg(_BQ, query_bucket=_BQ)
    q, c, *_ = _carried_case("plain", _BQ)
    one_shot = all_knn(c, queries=q, config=cfg)
    assert np.asarray(one_shot.bins_chunks).sum() == chunks
    narrow = all_knn(c, queries=q, config=cfg.replace(corpus_tile=512))
    assert narrow.bins_chunks is None
    short = all_knn(c, queries=q[:_CQ], config=_carried_cfg())
    assert short.select_tiles is not None and short.bins_chunks is None
    # row j is (N - j) e_0 and no query has a component along e_0: every
    # query meets the corpus in descending order of distance
    far = np.zeros((_CT * _CC, _CD), np.float32)
    far[:, 0] = np.arange(_CT * _CC, 0, -1)
    q[:, 0] = 0.0
    assert np.asarray(
        all_knn(far, queries=q, config=cfg).bins_chunks).tolist() == [chunks, 0]

    index = build_index(far, cfg)
    before = counted()
    served = query_knn(q, index)
    assert np.asarray(served.bins_chunks).tolist() == [chunks, 0]
    assert [b - a for a, b in zip(before, counted())] == [chunks, 0]
    session = ServeSession(index)
    # the phases open on the dispatching thread whenever the counter is fed,
    # and what it is fed
    open_phases, fed = [], []
    phase = session.phase

    def tracked(name, *args, **kw):
        @contextlib.contextmanager
        def span():
            open_phases.append(name)
            try:
                with phase(name, *args, **kw) as h:
                    yield h
            finally:
                open_phases.remove(name)
        return span()

    count = obs_metrics.MetricsRegistry.count_bins_chunks
    monkeypatch.setattr(session, "phase", tracked)
    monkeypatch.setattr(
        obs_metrics.MetricsRegistry, "count_bins_chunks",
        lambda self, n: (fed.append((list(open_phases), n)), count(self, n)))
    retired = session.submit(q)
    assert retired == [] and fed == []  # dispatched, nothing counted yet
    batch, = session.drain()
    assert np.asarray(batch.bins_chunks).tolist() == [chunks, 0]
    (phases, n), = fed
    # the batch is synchronised: the count is on hand, no wait of its own
    assert "enqueue" not in phases and n.is_ready()
    assert [b - a for a, b in zip(before, counted())] == [2 * chunks, 0]


# ---------------------------------------------------------------------------
# what the deleted Pallas backend's tests asked of it, asked of this one


def _world(engaged, rng, m, d, nq=None):
    """Rows and tiles of one case. ``engaged``: whole-number rows under
    1024-row tiles — the L2 cases then take the fused scan
    (``fused_rule``; the kernel runs in the Pallas interpreter), the cosine
    cases the carried lists; ``m`` and ``nq`` are raised to a tile's
    height. Else: fractional rows under small tiles, the per-tile program."""
    if engaged:
        m, nq = m + 1024, None if nq is None else nq + 1024
        tiles = dict(query_tile=1024, corpus_tile=1024)
    else:
        tiles = dict(query_tile=32, corpus_tile=64)

    def rows(n):
        if engaged:
            return rng.integers(0, 200, (n, d)).astype(np.float32)
        return (rng.standard_normal((n, d)) * 3).astype(np.float32)

    return rows(m), None if nq is None else rows(nq), tiles


def _oracle(X, queries, metric, engaged):
    """Every corpus row by its distance, nearest first, (dists, ids): the
    float64 oracle; for whole-number rows under L2 the same semantics
    from exact integer distances (the oracle's (q, m, d) array is a
    gigabyte at these heights)."""
    if metric != "l2" or not engaged:
        return oracle_all_knn(X, k=X.shape[0], queries=queries, metric=metric)
    d = int_sq_l2(X if queries is None else queries, X).astype(np.float64)
    d[d <= 0] = np.inf
    order = np.argsort(d, axis=1, kind="stable")
    return np.take_along_axis(d, order, axis=1), order.astype(np.int32)


def _assert_right(got, X, k, engaged, queries=None, metric="l2"):
    """Against the float64 oracle: the distances slot by slot, the ids up
    to the order of equals (whole-number rows tie); where ``engaged`` and
    L2, every tile step ran inside the kernel."""
    all_d, all_i = _oracle(X, queries, metric, engaged)
    want_d = all_d[:, :k]
    np.testing.assert_allclose(
        np.asarray(got.dists), want_d, rtol=1e-4, atol=1e-5)
    got_i = np.asarray(got.ids)
    for r in range(0, got_i.shape[0], 37):
        by_id = dict(zip(all_i[r].tolist(), all_d[r].tolist()))
        np.testing.assert_allclose(
            [by_id[i] for i in got_i[r].tolist()], want_d[r],
            rtol=1e-4, atol=1e-5, err_msg=f"row {r}")
    if metric == "l2":
        steps = None if got.dist_steps is None else np.asarray(got.dist_steps)
        assert (steps is not None and steps.size == 4 and steps[3] > 0
                and steps[:3].sum() == 0) == engaged, steps
    elif engaged:
        assert got.select_tiles is not None  # the lists rode the scans


def _case_all_pairs(rng, engaged):
    X, _, tiles = _world(engaged, rng, 256, 16)
    _assert_right(all_knn(X, k=8, backend="serial", **tiles), X, 8, engaged)


def _case_query_mode(rng, engaged):
    X, Q, tiles = _world(engaged, rng, 128, 16, nq=64)
    got = all_knn(X, queries=Q, k=5, backend="serial", **tiles)
    _assert_right(got, X, 5, engaged, queries=Q)


def _case_non_divisible(rng, engaged):
    X, _, tiles = _world(engaged, rng, 157, 24)
    got = all_knn(X, k=6, backend="serial", **tiles)
    assert got.ids.shape == (X.shape[0], 6)
    _assert_right(got, X, 6, engaged)


def _case_prefix_queries(rng, engaged):
    """Queries that ARE the first corpus rows keep their identity: the
    answer of the all-pairs run they are a prefix of."""
    X, _, tiles = _world(engaged, rng, 192, 16)
    n = 1024 if engaged else 64
    kw = dict(k=5, backend="serial", **tiles)
    full = all_knn(X, **kw)
    head = all_knn(X, queries=X[:n], query_ids=np.arange(n), **kw)
    np.testing.assert_array_equal(
        np.asarray(head.dists), np.asarray(full.dists)[:n])
    _assert_right(head, X, 5, engaged, queries=X[:n])


def _case_slice_queries(rng, engaged):
    """... and so does any other slice of the corpus, under its own ids
    (the deleted kernels masked by grid position and refused it)."""
    X, _, tiles = _world(engaged, rng, 192, 16)
    n = 1024 if engaged else 64
    kw = dict(k=5, backend="serial", **tiles)
    full = all_knn(X, **kw)
    part = all_knn(X, queries=X[100:100 + n],
                   query_ids=np.arange(100, 100 + n), **kw)
    np.testing.assert_array_equal(
        np.asarray(part.dists), np.asarray(full.dists)[100:100 + n])
    assert not (np.asarray(part.ids) == np.arange(100, 100 + n)[:, None]).any()


def _case_k_of_several_tiles(rng, engaged):
    """k = 40: an answer no one tile's rows fill, merged over the stack (at
    1024 rows the lists carry it at depth 7)."""
    X, _, tiles = _world(engaged, rng, 96, 8)
    if not engaged:
        tiles["corpus_tile"] = 48
    _assert_right(all_knn(X, k=40, backend="serial", **tiles), X, 40, engaged)


def _case_duplicates(rng, engaged):
    X, _, tiles = _world(engaged, rng, 64, 32)
    X[5] = X[60]
    got = all_knn(X, k=4, backend="serial", **tiles)
    ids = np.asarray(got.ids)
    assert 60 not in ids[5] and 5 not in ids[60]
    _assert_right(got, X, 4, engaged)


def _case_nan_row(rng, engaged):
    """A query row of infinities has no distances: its slots hold NaN and
    ids of the corpus (no value is made up, no id out of range), and no
    other row's answer moves."""
    X, Q, tiles = _world(engaged, rng, 128, 8, nq=16)
    kw = dict(k=5, backend="serial", **tiles)
    clean = all_knn(X, queries=Q, **kw)
    Q = Q.copy()
    Q[3] = np.inf
    got = all_knn(X, queries=Q, **kw)
    d, i = np.asarray(got.dists), np.asarray(got.ids)
    assert np.isnan(d[3]).all()
    assert ((i[3] >= -1) & (i[3] < X.shape[0])).all()
    others = np.arange(len(Q)) != 3
    np.testing.assert_array_equal(i[others], np.asarray(clean.ids)[others])
    np.testing.assert_array_equal(d[others], np.asarray(clean.dists)[others])


def _case_one_tile(rng, engaged):
    """The whole corpus in one tile: first step, merge and answer in one."""
    X, _, tiles = _world(engaged, rng, 0 if engaged else 48, 8)
    _assert_right(all_knn(X, k=5, backend="serial", **tiles), X, 5, engaged)


def _case_cosine(rng, engaged):
    X, _, tiles = _world(engaged, rng, 150, 24)
    got = all_knn(X, k=7, backend="serial", metric="cosine", **tiles)
    _assert_right(got, X, 7, engaged, metric="cosine")


def _case_cosine_duplicates(rng, engaged):
    """A scaled copy is a cosine duplicate: dropped like an equal row."""
    X, _, tiles = _world(engaged, rng, 64, 16)
    X[5] = X[60] * 3.0
    got = all_knn(X, k=4, backend="serial", metric="cosine", **tiles)
    ids = np.asarray(got.ids)
    assert 60 not in ids[5] and 5 not in ids[60]


def _cosine_with_a_degenerate_row(rng, engaged, row, atol):
    """A row of no direction is 1.0 away from everything and changes no
    other row's answer."""
    X, _, tiles = _world(engaged, rng, 96, 16)
    X[17] = row
    got = all_knn(X, k=5, backend="serial", metric="cosine", **tiles)
    np.testing.assert_allclose(np.asarray(got.dists)[17], 1.0, atol=atol)
    assert 17 not in np.asarray(got.ids)[np.arange(len(X)) != 17][:, :1]
    keep = np.arange(len(X)) != 17
    want_d, _ = oracle_all_knn(X[keep], k=4, metric="cosine")
    np.testing.assert_allclose(
        np.asarray(got.dists)[keep][:, :4], want_d, rtol=1e-4, atol=1e-5)


def _case_cosine_zero_row(rng, engaged):
    _cosine_with_a_degenerate_row(
        rng, engaged, np.zeros(16, np.float32), atol=1e-6)


def _case_cosine_subclamp_row(rng, engaged):
    row = np.zeros(16, np.float32)
    # |x|^2 = 1e-38: under the clamp (``ops/distance.py _NORM_EPS``), not
    # zero — scaled by the clamp it is 1e-4 long, not a unit row
    row[0] = 1e-19
    _cosine_with_a_degenerate_row(rng, engaged, row, atol=2e-4)


_BACKEND_CASES = {
    name[len("_case_"):]: fn for name, fn in sorted(globals().items())
    if name.startswith("_case_")
}


@pytest.mark.parametrize("engaged", [True, False], ids=["engaged", "small"])
@pytest.mark.parametrize("case", sorted(_BACKEND_CASES))
def test_serial_answers_the_backend_cases(rng, case, engaged):
    _BACKEND_CASES[case](rng, engaged)


# ---------------------------------------------------------------------------
# which program a benchmark cell runs: what the shape rules answer at the
# cells' own shapes (``scripts/lowered_hashes.py --cells`` hashes the same
# programs). A kernel PR that moves a rule sees here which cells it moves.

_REPO = pathlib.Path(__file__).resolve().parent.parent


def _cell_programs():
    """(cell, rows a tile program is built for, has the one-pass fact,
    configuration, knn): every program a cell's traffic can reach — a
    served cell's at each bucket it warms, a dense L2 one with the corpus
    side of the one-pass rule and without it (a corpus that did not
    qualify); a one-shot cell's at its slice."""
    bench = json.loads((_REPO / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        config = json.loads((_REPO / files[cell["config"]]).read_text())
        mix = json.loads((_REPO / "benchmark" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
        knn = config["knn"]
        dense_l2 = (knn.get("metric", "l2") == "l2"
                    and not knn.get("partitions"))
        for fact in (True, False) if dense_l2 else (False,):
            if not fact and "warm_sizes" not in mix:
                continue  # a one-shot call reads the fact before it picks
            for rows in mix.get("warm_sizes", [mix.get("slice_rows")]):
                yield pytest.param(
                    cell["name"], rows, fact, config,
                    id=f"{cell['name']}-{rows}" + ("" if fact or not dense_l2
                                                   else "-nofact"))


# id -> (one-pass branch, depth of the carried lists, block height of the
# fused scan, a row bound rides the lists[, k' of the certified screen]);
# the clustered cell: its probe is bucket-major. ``plain`` / ``bounded``
# name the lane-bin kernel a carried scan's *bins* is; ``fused`` has both
# inside. Four wide: the program does not screen (ISSUE 47: float32 rows
# at ``highest``, no one-pass branch, 1024 rows, d % 128 == 0 — the
# embedding cell's bucket, and what a bigann-shaped corpus of FRACTIONAL
# rows would run, which no cell's data is). Six wide: the screened scan is
# ONE call of the kernel's three-pass form (ISSUE 51: L2 on the lane grid,
# its VMEM fits), the third entry its block height
_SMALL = {64: (False, 4, None, False), 128: (False, 4, None, False),
          256: (False, 5, None, True), 512: (False, 5, None, True)}
_WHICH = {
    "allknn-mnist8m-4096": (True, 5, 1024, False),
    "ring4-mnist8m-16384": (True, 5, 1024, False),
    **{f"serve-bigann10m-small-{b}{fact}": v
       for b, v in _SMALL.items() for fact in ("", "-nofact")},
    "serve-bigann10m-small-1024": (True, 5, 1024, True),
    "serve-bigann10m-small-1024-nofact": (
        False, 5, 1024, True, 32, "fused_screen"),
    "serve-bigann10m-bulk-1024": (True, 5, 1024, True),
    "serve-bigann10m-bulk-1024-nofact": (
        False, 5, 1024, True, 32, "fused_screen"),
    "serve-dbpedia1m-cos-bulk-1024": (False, 5, None, True, 32),
    # d = 100: no multiple of 8, the stack rests out of the kernel's reach
    # (whole-number rows there: no cell's data) ...
    "stream-msturing10m-runbook-1024": (True, 5, None, True),
    # ... and fractional rows rest zero-padded at 128 columns, where the
    # screen engages (ISSUE 49: ``serve/index.py rest_width``) — inside
    # the kernel (ISSUE 51): the cell's own program
    "stream-msturing10m-runbook-1024-nofact": (
        False, 5, 1024, True, 32, "fused_screen"),
    # a predicate: the one-pass branch from 256 rows, and from there the
    # fused scan with the words its operand (ISSUE 55); 64 and 128 rows
    # keep the masked scan of tile steps
    **{f"serve-yfcc10m-filter-bulk-{b}-nofact": v for b, v in _SMALL.items()},
    "serve-yfcc10m-filter-bulk-64": (False, 4, None, False),
    "serve-yfcc10m-filter-bulk-128": (False, 4, None, False),
    "serve-yfcc10m-filter-bulk-256": (True, 5, 256, True),
    "serve-yfcc10m-filter-bulk-512": (True, 5, 512, True),
    "serve-yfcc10m-filter-bulk-1024": (True, 5, 1024, True),
    "serve-yfcc10m-filter-bulk-1024-nofact": (False, 5, None, True),
    "serve-bigann10m-ivf-bulk-1024": "bucket-major, one 1024-row tile",
    # an inner product: never the one-pass rule (L2's), the carried lists
    # (d = 200 stays: a stack at 256 columns is 0.4e9 B more than the
    # device has free beside the launcher's array)
    "serve-text2image10m-ip-bulk-1024": (False, 5, None, True),
    # a byte stack (ISSUE 48): the kernel at ``itemsize`` 1; a byte stack
    # without the fact does not exist (it holds by type), the row says what
    # the rule would answer
    "serve-bigann100m-u8-bulk-1024": (True, 5, 1024, True),
    "serve-bigann100m-u8-bulk-1024-nofact": (False, 5, None, True),
    # the range cell's index answers k-NN too (a request without a
    # radius): the byte cell's program at 256 columns; its RANGE program
    # is another family (ISSUE 54: ``backends/range_scan.py``,
    # ``tests/test_range.py``, ``tests/test_pallas.py -k range_program``)
    "serve-ssnpp100m-range-bulk-1024": (True, 5, 1024, True),
    "serve-ssnpp100m-range-bulk-1024-nofact": (False, 5, None, True),
}


@pytest.mark.parametrize("cell,rows,fact,config", _cell_programs())
def test_which_program_a_cell_runs(request, monkeypatch, cell, rows, fact,
                                   config):
    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.ivf import search
    from mpi_knn_tpu.ops.topk import lane_bin_bound_rides
    from mpi_knn_tpu.parallel.partition import pad_to_multiple

    # the chip's answers: under the ring's checked shard_map the kernels
    # run on the TPU alone
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = KNNConfig(**config["knn"])
    dim = config["dim"]
    want = _WHICH[request.node.callspec.id]
    if cfg.partitions:
        q_tile, _ = search.ivf_query_shapes(
            cfg, cfg.nprobe, cfg.bucket_cap, dim, rows)
        engages = search.bucket_major_engages(
            q_tile, cfg.nprobe, cfg.partitions, cfg.bucket_cap, dim,
            cfg.dtype, cfg.precision_policy)
        got = (f"{'bucket' if engages else 'row'}-major, "
               f"one {q_tile}-row tile")
        assert got == want
        return
    ring = cfg.backend.startswith("ring")
    filtered = "max_query_tags" in config
    if ring:
        q_tile, c_tile = cfg.query_tile, cfg.corpus_tile
    elif "slo" in config:  # served: the bucket's tile over the index's
        from mpi_knn_tpu.serve import index as serve_index
        from scripts.lowered_hashes import v5e_free_at_build

        q_tile = min(cfg.query_tile, pad_to_multiple(rows, 8))
        c_tile, c_pad = serve_index._serial_tiling(cfg, config["rows"])
        # ... at the width the build rests the stack at on a v5e
        # (ISSUE 49)
        dim = serve_index.rest_width(
            cfg, dim, c_tile, c_pad, onepass=fact, tagged=filtered,
            free_bytes=v5e_free_at_build(
                config["rows"], dim, cfg.metric))[0]
    else:
        q_tile, c_tile = serial.effective_tiles(cfg, config["rows"], rows)
    onepass = fact and serial.onepass_rule(cfg, q_tile, filtered)
    depth = serial.carried_depth(cfg, q_tile, c_tile, ring)
    block = (serial.fused_rule(cfg, q_tile, c_tile, dim, ring,
                               filtered=filtered) if onepass else None)
    rides = depth is not None and lane_bin_bound_rides(q_tile, c_tile)
    screen = None if depth is None else serial.screen_rule(
        cfg, q_tile, c_tile, dim, branch=bool(onepass), filtered=filtered,
        varying=ring)
    in_kernel = None if depth is None else serial.fused_screen_rule(
        cfg, q_tile, c_tile, dim, branch=bool(onepass), filtered=filtered,
        varying=ring)
    assert (onepass, depth, block or in_kernel, rides) + (
        () if screen is None else (screen,)) + (
        ("fused_screen",) if in_kernel else ()) == want


# ---------------------------------------------------------------------------
# the two Pallas forks are gone: a value a user can still write says what
# to use, a field or flag that is gone fails as any unknown one does


def _parse(parser, *argv):
    from mpi_knn_tpu import cli
    from mpi_knn_tpu.frontend import cli as frontend_cli

    build = {"mpi-knn": cli.build_parser,
             "mpi-knn serve": frontend_cli.build_serve_parser}[parser]
    return lambda: build().parse_args(["--data", "synthetic:64x8c2", *argv])


_X = np.zeros((64, 8), np.float32)
# the two removed fields, spelled in pieces: a grep of the tree for the
# names of what was deleted finds nothing
_GONE_FIELDS = ["_".join(p) for p in (
    ("pallas", "variant"), ("ring", "fused", "rotation"))]


@pytest.mark.parametrize("make,error,match", [
    pytest.param(lambda: KNNConfig(backend="pallas"), ValueError,
                 "backend='serial'", id="config-backend-pallas"),
    pytest.param(lambda: KNNConfig(ring_fusion="fused"), ValueError,
                 "backend='ring-overlap' is the ring",
                 id="config-ring_fusion-fused"),
    *(pytest.param(lambda f=f: KNNConfig(**{f: "round"}), TypeError, f,
                   id=f"config-{f}") for f in _GONE_FIELDS),
    pytest.param(lambda: all_knn(_X, backend="pallas"), ValueError,
                 "backend='serial'", id="all_knn-backend-pallas"),
    pytest.param(lambda: build_index(_X, backend="pallas"), ValueError,
                 "backend='serial'", id="build_index-backend-pallas"),
    # (argparse exits 2; ``match`` is what it says on stderr)
    *(pytest.param(_parse(parser, *flag), SystemExit, said,
                   id=f"{parser.replace(' ', '-')}{flag[0]}")
      for parser in ("mpi-knn", "mpi-knn serve")
      for flag, said in (
          (("--backend", "pallas"),
           "invalid choice: 'pallas' (choose from auto, serial,"),
          (("--ring-fusion", "xla"), "unrecognized arguments"),
          (("--ring-fused-rotation", "round"), "unrecognized arguments"),
          (("--pallas-variant", "tiles"), "unrecognized arguments"))),
])
def test_removed_options_are_refused(make, error, match, capsys):
    if error is not SystemExit:
        with pytest.raises(error, match=match):
            make()
        return
    with pytest.raises(SystemExit) as exit_:
        make()
    assert exit_.value.code == 2 and match in capsys.readouterr().err


def test_ring_fusion_keeps_its_one_value():
    """The field stays for the configuration file that names it."""
    assert KNNConfig(ring_fusion="xla") == KNNConfig()


@pytest.mark.parametrize(
    "path", sorted((_REPO / "benchmark" / "configs").glob("*.json")),
    ids=lambda p: p.stem)
def test_every_benchmark_configuration_builds_its_config(path):
    config = json.loads(path.read_text())
    cfg = KNNConfig(**config["knn"])
    assert cfg.k == config["k"] and cfg.ring_fusion == "xla"
