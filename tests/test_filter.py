"""A predicate on every query row (ISSUE 39), at sizes the CPU holds: an
index built with tags (``serve/tags.py``) against a float64 oracle of its
own over a grid of predicates x widths x buckets, the two regimes at both
extremes of the threshold, a mixed batch of three tenants through the
front end, the plain reference of the cell
(``benchmark/reference_filter.py``), what a tagged index refuses, and an
index without tags lowering the programs it always lowered."""

import functools
import hashlib

import numpy as np
import pytest

from benchmark import reference_filter
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.frontend import Frontend, SLOPolicy
from mpi_knn_tpu.resilience import ResiliencePolicy
from mpi_knn_tpu.serve import ServeSession, build_index
from mpi_knn_tpu.serve import engine, tags as serve_tags
from mpi_knn_tpu.serve.engine import query_knn

ROWS, K, TILE = 8192, 10, 1024
# tag id -> share of the rows it lies on; ids past these lie on a handful
FREQUENT = {0: 0.30, 1: 0.20, 2: 0.10, 3: 0.05}
FEW = 40  # a tag on 5 rows: fewer than k matches
UNUSED = 45  # inside the vocabulary, on no row
VOCAB = 48
UNKNOWN = 10**6  # an id the index has never seen: matches nothing


def make_bags(seed: int):
    """(membership (ROWS, VOCAB) bool, CSR) of a small tagged corpus: four
    frequent tags, rare ones on 1 to 200 rows, one on five."""
    rng = np.random.default_rng(seed)
    member = np.zeros((ROWS, VOCAB), dtype=bool)
    for t, share in FREQUENT.items():
        member[:, t] = rng.random(ROWS) < share
    for t in range(4, 40):
        member[rng.choice(ROWS, size=1 + 6 * (t - 4), replace=False), t] = 1
    member[rng.choice(ROWS, size=5, replace=False), FEW] = True
    member[rng.integers(0, ROWS), 47] = True  # the vocabulary's last id
    rows, tag = np.nonzero(member)
    indptr = np.zeros(ROWS + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=ROWS), out=indptr[1:])
    return member, (indptr, tag.astype(np.int32))


def cases(member, rng):
    """{case: (tags a row as the requests carry them)} — one of each."""
    rare_a, rare_b = 20, 30
    both = np.flatnonzero(member[:, rare_a] & member[:, 0])
    assert both.size  # frequent + rare has a match
    # two rare tags with a row in common, and two with none
    common = np.flatnonzero(member[:, 4:40].sum(axis=1) >= 2)
    pair = 4 + np.flatnonzero(member[common[0], 4:40])[:2]
    apart = next((a, b) for a in range(4, 40) for b in range(a + 1, 40)
                 if not (member[:, a] & member[:, b]).any())
    return {
        "no_tag": (-1, -1),
        "one_frequent": (1, -1),
        "one_rare": (rare_b, -1),
        "frequent_frequent": (0, 2),
        "frequent_rare": (0, rare_a),
        "rare_rare": tuple(int(t) for t in pair),
        "unknown_tag": (UNKNOWN, 1),
        "empty_intersection": apart,
        "fewer_than_k": (FEW, -1),
        "unused_tag": (UNUSED, -1),
    }


CASES = ("no_tag", "one_frequent", "one_rare", "frequent_frequent",
         "frequent_rare", "rare_rare", "unknown_tag", "empty_intersection",
         "fewer_than_k", "unused_tag")


def oracle(X, member, Q, filters, exclude_zero=True):
    """Float64, straight from the membership matrix (whole-number rows:
    the matmul form is exact in float64)."""
    Q, X = Q.astype(np.float64), X.astype(np.float64)
    d2 = (Q * Q).sum(1)[:, None] - 2.0 * Q @ X.T + (X * X).sum(1)[None]
    ok = np.ones(d2.shape, dtype=bool)
    for j in range(filters.shape[1]):
        t = filters[:, j]
        has = np.zeros(d2.shape, dtype=bool)
        known = (t >= 0) & (t < member.shape[1])
        has[known] = member[:, t[known]].T
        ok &= np.where((t < 0)[:, None], True, has)
    if exclude_zero:
        ok &= d2 > 0
    d2 = np.where(ok, d2, np.inf)
    order = np.argsort(d2, axis=1, kind="stable")[:, :K]
    d = np.take_along_axis(d2, order, axis=1)
    return d, np.where(np.isinf(d), -1, order)


# the narrowest corpus tile whose words of a predicate are whole vectors
# (128 a row): from it up a filtered bucket of 256 rows or more takes the
# kernel that walks the stack, the words its operand (ISSUE 55)
KERNEL_TILE = 4096


@functools.lru_cache(maxsize=None)
def world(dim: int, tile: int = TILE):
    rng = np.random.default_rng(dim)
    member, csr = make_bags(dim)
    cen = rng.random((16, dim)) * 140
    X = np.clip(np.rint(cen[rng.integers(0, 16, ROWS)]
                        + rng.standard_normal((ROWS, dim)) * 30), 0, 255
                ).astype(np.float32)
    cfg = KNNConfig(k=K, backend="serial", query_tile=1024,
                    corpus_tile=tile, query_bucket=64, exclude_self=False,
                    exclude_zero=True, max_query_tags=2)
    index = build_index(X, cfg, tags=csr)
    return X, member, csr, index, cases(member, rng)


def tile_of(bucket: int) -> int:
    """The corpus tile of the index a ``bucket``-row batch is answered
    from: the 512-row batches meet two tiles of ``KERNEL_TILE``, where
    their scan part (three cases in ten: a 256-row dispatch) takes the
    kernel; the others the eight tiles of ``TILE``, the masked scan of
    tile steps at every height."""
    return KERNEL_TILE if bucket == 512 else TILE


@functools.lru_cache(maxsize=None)
def answered(dim: int, bucket: int):
    """A batch of ``bucket`` rows that cycles through every case, through
    ``query_knn``; (filters, the system's answer, the oracle's)."""
    X, member, _, index, by_case = world(dim, tile_of(bucket))
    rng = np.random.default_rng([dim, bucket])
    which = np.arange(bucket) % len(CASES)
    filters = np.array([by_case[CASES[c]] for c in which], dtype=np.int32)
    Q = X[rng.integers(0, ROWS, bucket)] + rng.integers(
        -3, 4, (bucket, dim)).astype(np.float32)
    res = query_knn(Q, index, filters=filters)
    return which, filters, res, oracle(X, member, Q, filters)


def holds(member, ids, filters):
    """Whether each returned id's bag holds its row's tags (empty slots
    hold anything)."""
    ok = np.ones(ids.shape, dtype=bool)
    for j in range(filters.shape[1]):
        t = filters[:, j]
        known = (t >= 0) & (t < member.shape[1])
        has = np.zeros(ids.shape, dtype=bool)
        has[known] = member[np.maximum(ids[known], 0), t[known][:, None]]
        ok &= np.where((t < 0)[:, None], True, has)
    return ok | (ids < 0)


@pytest.mark.parametrize("bucket", [64, 512, 1024])
@pytest.mark.parametrize("dim", [100, 192, 128])
@pytest.mark.parametrize("case", CASES)
def test_filtered_answers_match_the_oracle(case, dim, bucket):
    _, member, _, index, _ = world(dim, tile_of(bucket))
    which, filters, res, (ref_d, ref_i) = answered(dim, bucket)
    # which program the scan part ran: the kernel's steps count apart
    # (d = 100 is off the sublane grid: the scan of tile steps, as ever)
    fused = bucket == 512 and dim != 100
    assert np.asarray(res.dist_steps).tolist() == (
        [0, 0, 0, 2] if fused else [2, 0] if bucket == 512
        else [8, 0] if bucket == 1024 else [0, 8])
    rows = np.flatnonzero(which == CASES.index(case))
    assert rows.size
    d, i = res.dists[rows], res.ids[rows]
    np.testing.assert_allclose(d, ref_d[rows], rtol=1e-5)
    # whole-number rows tie: an id may differ where its distance does not
    same = (i == ref_i[rows]) | np.isclose(d, ref_d[rows, -1:], rtol=1e-6)
    assert same.all()
    assert holds(member, i, filters[rows]).all()
    assert ((i < 0) == np.isinf(d)).all()
    if case in ("unknown_tag", "empty_intersection", "unused_tag"):
        assert np.isinf(d).all() and (i == -1).all()
    if case == "fewer_than_k":
        assert (np.isfinite(d).sum(axis=1) == 5 - (d == 0).sum(axis=1)).all()
        assert np.isinf(d[:, 5:]).all()
    if case == "no_tag":
        assert np.isfinite(d).all()


@pytest.mark.parametrize("dim", [100, 192, 128])
def test_regimes_split_as_the_counts_say(dim):
    _, member, _, index, by_case = world(dim)
    ti = index.tags
    assert ti.threshold == 256 and ti.n_bitsets == len(FREQUENT)
    plan = ti.plan(np.array([by_case[c] for c in CASES], dtype=np.int32))
    want = {"no_tag": serve_tags.NONE, "one_frequent": serve_tags.SCAN,
            "one_rare": serve_tags.GATHER,
            "frequent_frequent": serve_tags.SCAN,
            "frequent_rare": serve_tags.GATHER,
            "rare_rare": serve_tags.GATHER, "unknown_tag": serve_tags.EMPTY,
            "empty_intersection": serve_tags.EMPTY,
            "fewer_than_k": serve_tags.GATHER,
            "unused_tag": serve_tags.EMPTY}
    assert [int(r) for r in plan.regime] == [want[c] for c in CASES]
    # the candidates are the matching rows, no more
    n = sum(int((member[:, [t for t in by_case[c] if t >= 0]]).all(1).sum())
            for c in CASES if want[c] == serve_tags.GATHER)
    assert plan.candidates == n
    parts = plan.parts()
    assert sum(p.cand.size for p in parts) >= n
    assert all(p.cand.shape in index.tags.gather_shapes() for p in parts)
    assert sum(int((p.cand >= 0).sum()) for p in parts) == n
    # requests' plans joined are the batch's plan
    f = np.array([by_case[c] for c in CASES], dtype=np.int32)
    joined = serve_tags.merge_plans([ti.plan(f[:4]), ti.plan(f[4:])])
    assert (joined.regime == plan.regime).all()
    assert (joined.scan_rows == plan.scan_rows).all()
    assert (joined.scan_tags == plan.scan_tags).all()
    assert joined.candidates == n
    for size, (rows, cand) in plan.segments.items():
        assert (joined.segments[size][0] == rows).all()
        assert (joined.segments[size][1] == cand).all()


@pytest.mark.parametrize("threshold", [0, 10**9], ids=["all-scan",
                                                        "all-gather"])
@pytest.mark.parametrize("dim", [100, 128])
def test_both_extremes_of_the_threshold_answer_alike(dim, threshold):
    X, member, csr, index, _ = world(dim)
    which, filters, res, _ = answered(dim, 64)
    other = build_index(X, index.cfg, tags=csr)
    other.tags = serve_tags.build_tag_index(other, csr, threshold=threshold)
    plan = other.tags.plan(filters)
    matched = plan.regime != serve_tags.EMPTY
    if threshold:
        assert (plan.regime[matched & (filters >= 0).any(1)]
                == serve_tags.GATHER).all()
    else:
        assert (plan.regime[matched] <= serve_tags.SCAN).all()
    got = query_knn(res_queries(dim, 64), other, filters=filters)
    np.testing.assert_allclose(got.dists, res.dists, rtol=1e-6)
    assert ((got.ids == res.ids)
            | np.isclose(got.dists, res.dists[:, -1:], rtol=1e-6)).all()


def res_queries(dim: int, bucket: int):
    """The query rows ``answered`` drew."""
    X = world(dim)[0]
    rng = np.random.default_rng([dim, bucket])
    return X[rng.integers(0, ROWS, bucket)] + rng.integers(
        -3, 4, (bucket, dim)).astype(np.float32)


@pytest.mark.parametrize("dim", [192])
def test_row_major_copy_answers_as_the_stack_does(dim):
    """The gather's packed copy (what a TPU keeps for a width off its lane
    grid), forced here where the stack is row-major anyway."""
    X, _, csr, index, _ = world(dim)
    _, filters, res, _ = answered(dim, 64)
    other = build_index(X, index.cfg, tags=csr)
    other.tags = serve_tags.build_tag_index(other, csr, row_major_copy=True)
    assert other.tags.pack == 2 and other.tags.src.shape == (ROWS // 2, 384)
    got = query_knn(res_queries(dim, 64), other, filters=filters)
    np.testing.assert_array_equal(got.dists, res.dists)
    assert serve_tags.gather_pack(100) == (1, 128)
    assert serve_tags.gather_pack(128) == (1, 128)


def test_three_tenants_come_back_in_request_order():
    dim = 100
    X, member, _, index, by_case = world(dim)
    fe = Frontend(ServeSession(index, resilience=ResiliencePolicy()),
                  SLOPolicy(max_batch_rows=256, max_wait_s=0.05,
                            max_queue_rows=8192)).start(warm_sizes=[64])
    rng = np.random.default_rng(3)
    try:
        asked = []
        for tenant, n, tagged in (("a", 40, True), ("b", 24, False),
                                  ("c", 56, True)):
            Q = X[rng.integers(0, ROWS, n)] + 1.0
            f = np.array([by_case[CASES[c % len(CASES)]]
                          for c in rng.integers(0, 99, n)], dtype=np.int32)
            ticket = fe.submit(tenant, Q, f if tagged else None)
            asked.append((ticket, Q, f if tagged else np.full((n, 2), -1)))
        for ticket, Q, f in asked:
            d, i = ticket.result(timeout=120)
            ref_d, ref_i = oracle(X, member, Q, np.asarray(f))
            np.testing.assert_allclose(d, ref_d, rtol=1e-5)
            assert holds(member, i, np.asarray(f)).all()
        # the three met in one batch, split by regime
        reg = engine.obs_metrics.get_registry().to_prometheus()
        assert 'filter_rows_total{regime="gather"}' in reg
        assert 'serve_batch_phase_seconds_total{phase="plan"}' in reg
        with pytest.raises(ValueError, match="max_query_tags=2"):
            fe.submit("a", X[:4], np.zeros((4, 3), dtype=np.int32))
        with pytest.raises(ValueError, match=">= 0"):
            fe.submit("a", X[:4], np.full((4, 1), -2, dtype=np.int32))
    finally:
        fe.stop()


def test_pump_phases_with_plan_among_them_add_up():
    """``tests/test_frontend_server.py``'s partition of the pump thread's
    wall time, over an index with tags: ``plan`` is one of the phases, it
    moves, and the phases still sum to the window (this module's one live
    pump; an idle wait of at most 50 ms is cut at each end)."""
    import time

    X, _, _, index, _ = world(100)
    phases = ("idle", "hold", "coalesce", "plan", "prep", "enqueue", "wait",
              "d2h", "reply", "other")
    reg = engine.obs_metrics.get_registry()

    def seconds():
        return {p: reg.counter("serve_batch_phase_seconds_total",
                               labels={"phase": p}).value for p in phases}

    fe = Frontend(ServeSession(index, resilience=ResiliencePolicy()),
                  SLOPolicy(max_batch_rows=64, max_wait_s=0.002,
                            max_queue_rows=8192)).start(warm_sizes=[64])
    try:
        f = np.array([[0, -1], [20, -1], [0, 2]], dtype=np.int32)
        fe.submit("t39", X[:3] + 1.0, f).result(timeout=60)
        time.sleep(0.12)  # the pump in its idle waits at both ends
        before = seconds()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 1.5:
            fe.submit("t39", X[:3] + 1.0, f).result(timeout=30)
            time.sleep(0.01)
        time.sleep(0.12)
        wall = time.perf_counter() - t0
        after = seconds()
    finally:
        fe.stop()
    assert after["plan"] - before["plan"] > 0.0
    total = sum(after[p] - before[p] for p in phases)
    assert abs(total - wall) <= 2 * 0.05, (total, wall)


def test_the_cells_reference_agrees_with_the_oracle():
    dim = 100
    X, member, (indptr, indices), _, by_case = world(dim)
    width = int(np.diff(indptr).max())
    matrix = np.full((ROWS, width), VOCAB, dtype=np.int32)
    rank = np.arange(len(indices)) - np.repeat(indptr[:-1], np.diff(indptr))
    matrix[np.repeat(np.arange(ROWS), np.diff(indptr)), rank] = indices
    f = np.array([by_case[c] for c in CASES] * 2, dtype=np.int32)
    Q = X[:len(f)] + 2.0
    d, i = reference_filter.exact_knn_filtered(X, matrix, Q, f, K)
    ref_d, ref_i = oracle(X, member, Q, f)
    np.testing.assert_allclose(d, ref_d, rtol=1e-6)
    assert ((i == ref_i) | np.isclose(d, ref_d[:, -1:], rtol=1e-6)).all()


# ---- what a tagged index refuses, each with its stated error -------------


def test_a_tagged_index_is_frozen():
    X, _, csr, index, _ = world(100)
    session = ServeSession(index)
    with pytest.raises(ValueError, match="built with tags is frozen"):
        session.upsert(np.arange(4), X[:4])
    with pytest.raises(ValueError, match="built with tags is frozen"):
        session.delete(np.arange(4))
    with pytest.raises(ValueError, match="built with tags is frozen"):
        session.compact()
    with pytest.raises(ValueError, match="bucket_headroom"):
        build_index(X, index.cfg.replace(bucket_headroom=0.1), tags=csr)
    with pytest.raises(ValueError, match="precision_policy='exact'"):
        ServeSession(index, precision_policy="mixed")


@pytest.mark.parametrize("backend", ["ivf", "ring", "ring-overlap"])
def test_only_the_serial_layout_takes_tags(backend):
    X, _, csr, index, _ = world(100)
    if backend == "ivf":
        from mpi_knn_tpu.ivf import build_ivf_index

        with pytest.raises(ValueError, match="takes no tags"):
            build_ivf_index(X, index.cfg.replace(partitions=8), tags=csr)
        with pytest.raises(ValueError, match="takes no tags"):
            build_ivf_index(index, index.cfg.replace(partitions=8))
        return
    with pytest.raises(ValueError, match="dense serial layout only"):
        build_index(X, index.cfg.replace(backend=backend), tags=csr)


def test_filters_against_an_index_without_tags_are_refused():
    X, *_ = world(100)
    plain = build_index(X[:2048], KNNConfig(
        k=K, backend="serial", query_tile=64, corpus_tile=TILE,
        query_bucket=64))
    with pytest.raises(ValueError, match="built without tags"):
        query_knn(X[:4], plain, filters=np.zeros((4, 1), dtype=np.int32))
    with pytest.raises(ValueError, match="built without tags"):
        ServeSession(plain).submit(
            X[:4], filters=np.zeros((4, 1), dtype=np.int32))


@pytest.mark.parametrize("bad", ["short", "unsorted", "negative"])
def test_malformed_bags_are_refused(bad):
    X, _, (indptr, indices), index, _ = world(100)
    if bad == "short":
        csr = (indptr[:-1], indices)
    elif bad == "unsorted":
        csr = (indptr[::-1].copy(), indices)
    else:
        csr = (indptr, -indices - 1)
    with pytest.raises(ValueError):
        build_index(X, index.cfg, tags=csr)


# ---- an index without tags is the index it always was --------------------


def test_an_index_without_tags_lowers_the_program_it_always_lowered():
    """Same operands, no predicate in the text, the same text whatever
    ``max_query_tags`` says, the fingerprint without the field. (The text
    against the parent commit's: ``scripts/lowered_hashes.py``, CHANGES.md.)"""
    from mpi_knn_tpu.serve import aotcache

    rng = np.random.default_rng(0)
    X = rng.integers(0, 255, (4096, 128)).astype(np.float32)
    texts = []
    for width in (2, 5):
        cfg = KNNConfig(k=K, backend="serial", query_tile=1024,
                        corpus_tile=TILE, query_bucket=1024,
                        max_query_tags=width)
        index = build_index(X, cfg)
        assert index.tags is None
        text = engine.lower_bucket(index, cfg, 1024)[0].as_text()
        assert len(engine.expected_args(index, cfg, 1024)) == 8
        assert "filter" not in text
        texts.append(hashlib.sha256(text.encode()).hexdigest())
        facts = aotcache.fingerprint_facts(index, cfg, 1024)
        assert "max_query_tags" not in facts["cfg"]
        assert "tags" not in facts["index"]
    assert texts[0] == texts[1]
    tagged = world(128)[3]
    facts = aotcache.fingerprint_facts(tagged, tagged.cfg, 1024)
    assert facts["cfg"]["max_query_tags"] == 2 and "tags" in facts["index"]
    assert len(engine.expected_args(tagged, tagged.cfg, 1024)) == 10
