"""Serial backend parity vs the reference-semantics oracle (SURVEY.md §4)."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import KNNClassifier, KNNConfig, all_knn, knn_classify
from tests.oracle import oracle_all_knn


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
