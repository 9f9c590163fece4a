"""The third metric of the tile primitive (ISSUE 45): ``metric="ip"``, exact
maximum-inner-product search. The engine keeps ONE ordering — the k
smallest, ascending — so the "distance" under ``ip`` is the negated inner
product ``-<q, c>``. A score is not a distance: nothing is centred, neither
side has a norm, nothing is clamped at zero and there is no zero test.
Checked on seeded rows against the benchmark's plain reference
(``benchmark/reference_ip.py``: the direct form, no matmul) and against
numpy in float64.
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_ip
from mpi_knn_tpu import KNNConfig, all_knn, api, build_index, query_knn
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.ops import distance
from mpi_knn_tpu.ops.topk import init_topk_tiles
from mpi_knn_tpu.serve import ServeSession

M, NQ, K = 600, 40, 10
# the program's dot (float32 at ``highest``: six bf16 passes on a TPU, the
# CPU's own float32 dot here) and the reference's written-out sum each
# round a d-term float32 sum, in another order: each is off by a few 2^-24
# of sum |q_i c_i| <= |q| |c|. The tolerance is relative to |q| |c| of the
# pair, not to the score: a score may be near zero, where no relative error
# means anything. 2e-6 leaves an order of magnitude over the rounding at
# d = 256 and is far under what any wrong arithmetic does (per cents).
SCORE_TOL = 2e-6


def rows(seed: int, n: int, dim: int, sign: str = "mixed"):
    """Fractional float32 rows with an offset (a mean far from zero) and a
    scale of their own: ``sign`` "positive" / "negative" makes every inner
    product of two such sets positive / (against a positive set)
    negative."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim)) * 0.3 + 0.2
    if sign != "mixed":
        x = np.abs(x) + 0.05
    x *= np.exp(rng.uniform(np.log(0.4), np.log(2.0), n))[:, None]
    return (-x if sign == "negative" else x).astype(np.float32)


def oracle(X, Q, k, exclude_ids=None):
    """The k largest inner products in float64, negated and ascending, ties
    by the lower id; ``exclude_ids`` (nq,): a row's own id, left out."""
    s = Q.astype(np.float64) @ X.astype(np.float64).T
    if exclude_ids is not None:
        s[np.arange(len(Q)), exclude_ids] = -np.inf
    ids = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return -np.take_along_axis(s, ids, axis=1), ids.astype(np.int32)


def assert_answers(got_d, got_i, X, Q, k, exclude_ids=None):
    """Ids equal to the float64 oracle's wherever its gaps are wider than
    the rounding; every score within ``SCORE_TOL`` of |q| |c|; ascending."""
    want_d, want_i = oracle(X, Q, k, exclude_ids)
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)
    assert got_d.shape == want_d.shape and got_i.dtype == np.int32
    assert np.all(np.diff(got_d, axis=1) >= 0) and np.isfinite(got_d).all()
    scale = (np.linalg.norm(Q.astype(np.float64), axis=1)[:, None]
             * np.linalg.norm(X.astype(np.float64), axis=1)[want_i])
    assert (np.abs(got_d - want_d) / scale).max() < SCORE_TOL
    # a swap is allowed only between two scores closer than the tolerance
    differ = got_i != want_i
    if differ.any():
        gap = np.abs(got_d - want_d)[differ] / scale[differ]
        assert gap.max() < SCORE_TOL
    assert differ.mean() < 0.02


def cfg_for(**kw) -> KNNConfig:
    base = dict(k=K, backend="serial", metric="ip", query_tile=16,
                corpus_tile=128, query_bucket=16)
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
# the answers, against the plain reference and the float64 oracle


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


@pytest.mark.parametrize("dim", [8, 100, 200, 256])
@pytest.mark.parametrize("k", [1, 10, 64])
def test_ip_answers_match_the_reference(k, dim):
    X, Q = rows(1, M, dim), rows(2, NQ, dim)
    got_d, got_i = answer("all_knn-device", X, Q, cfg_for(k=k))
    assert_answers(got_d, got_i, X, Q, k)
    # and the benchmark's plain reference says the same
    ref_d, ref_i = reference_ip.exact_knn_ip(X, Q, k, block_rows=M)
    assert (got_i == ref_i).mean() > 0.98
    np.testing.assert_allclose(got_d, ref_d, rtol=0, atol=SCORE_TOL * 40)


@pytest.mark.parametrize(
    "path", ["all_knn-host", "all_knn-device", "query_knn", "session"])
def test_every_entry_point_answers_alike(path):
    X, Q = rows(3, M, 200), rows(4, NQ, 200)
    got_d, got_i = answer(path, X, Q, cfg_for())
    assert_answers(got_d, got_i, X, Q, K)
    assert (got_d < 0).all()  # the negated score of aligned rows


@pytest.mark.parametrize("m", [512, 600, 77])  # whole tiles, a padded last
@pytest.mark.parametrize("nq", [32, 37])  # tiles that do and do not divide
def test_padded_tiles_on_either_side(m, nq):
    X, Q = rows(5, m, 100), rows(6, nq, 100)
    for path in ("all_knn-device", "session"):
        got_d, got_i = answer(path, X, Q, cfg_for())
        assert_answers(got_d, got_i, X, Q, K)
        assert got_i.min() >= 0 and got_i.max() < m  # no padding row


@pytest.mark.parametrize("exclude_self", [True, False])
@pytest.mark.parametrize("sign", ["negative", "positive", "mixed"])
def test_scores_of_either_sign_and_self_exclusion_by_id(sign, exclude_self):
    """All-pairs mode: a row meets itself, and under a score the row's own
    product is not even its largest. ``exclude_self`` leaves it out BY ID;
    off, it is a candidate like any other. "negative": every score is
    negative, so every "distance" is positive; "positive": the reverse."""
    X = rows(7, 300, 100, "positive" if sign != "mixed" else "mixed")
    Q = -X if sign == "negative" else X
    ids = np.arange(len(X), dtype=np.int32)
    res = all_knn(jnp.asarray(X), queries=jnp.asarray(Q), query_ids=ids,
                  config=cfg_for(exclude_self=exclude_self))
    got_d, got_i = np.asarray(res.dists), np.asarray(res.ids)
    assert_answers(got_d, got_i, X, Q, K, ids if exclude_self else None)
    if exclude_self:
        assert not (got_i == ids[:, None]).any()
    if sign == "negative":
        assert (got_d > 0).all()
    if sign == "positive":
        assert (got_d < 0).all()
    if sign == "mixed":
        assert (got_d < 0).any()


def test_all_pairs_mode_without_queries():
    X = rows(8, 300, 64)
    res = all_knn(X, config=cfg_for())  # leave-one-out, self by id
    ids = np.arange(300, dtype=np.int32)
    assert_answers(res.dists, res.ids, X, X, K, ids)


def test_float64_debug_mode_is_exact_to_the_oracle():
    X, Q = rows(9, 400, 200), rows(10, 24, 200)
    res = all_knn(X.astype(np.float64), queries=Q.astype(np.float64),
                  config=cfg_for(dtype="float64"))
    want_d, want_i = oracle(X, Q, K)
    np.testing.assert_array_equal(np.asarray(res.ids), want_i)
    np.testing.assert_allclose(np.asarray(res.dists), want_d, rtol=1e-13)


# ---------------------------------------------------------------------------
# a score is not a distance: no centring, no norms, no clamp, no zero test


def test_nothing_is_centred_though_the_mean_is_large():
    """An index over a corpus with a large mean answers as the reference
    does; centring the queries by that mean — what the engine does for L2,
    and has leaned on for fractional rows since PR 29 — ranks differently,
    because <q - m, c> = <q, c> - <m, c> and <m, c> grows with |c|."""
    rng = np.random.default_rng(11)
    X = rows(11, M, 200) + 3.0 * rng.standard_normal(200).astype(np.float32)
    Q = rows(12, NQ, 200)
    mean = X.astype(np.float64).mean(axis=0)
    assert np.linalg.norm(mean) > 30
    index = build_index(X, cfg_for())
    assert index.mu is None  # no offset kept, none applied to a batch
    got = query_knn(Q, index)
    assert_answers(got.dists, got.ids, X, Q, K)
    prepared = api.prepare_corpus(jnp.asarray(X), cfg_for(), query_rows=NQ)
    assert prepared.mu is None
    _, centred = oracle(X, (Q - mean).astype(np.float32), K)
    assert (np.asarray(got.ids) != centred).mean() > 0.5
    # the same corpus under L2 IS centred: the rule is the metric's
    assert build_index(X, cfg_for(metric="l2")).mu is not None


def test_no_norm_plane_is_built_kept_or_read():
    X = rows(13, M, 200)
    index = build_index(X, cfg_for())
    assert index.tile_sqs is None
    assert build_index(X, cfg_for(metric="l2")).tile_sqs is not None
    assert build_index(X, cfg_for(metric="cosine")).tile_sqs is not None
    prepared = api.prepare_corpus(jnp.asarray(X), cfg_for(), query_rows=NQ)
    assert prepared.tile_sqs is None
    tiles = jnp.zeros((3, 128, 200), jnp.float32)
    assert serial.stack_norms(tiles, "ip") is None
    assert serial.resident_norms(tiles, "ip") is None
    # the batch program's operands: the stack and its ids, no third plane
    layout = index.layout
    flat = jax.tree.leaves(layout.resident(index))
    assert [a.shape for a in flat] == [(5, 128, 200), (5, 128)]
    l2 = build_index(X, cfg_for(metric="l2"))
    assert len(jax.tree.leaves(l2.layout.resident(l2))) >= 3
    # and the executable's fingerprint holds no norms' shape
    from mpi_knn_tpu.serve.aotcache import index_facts

    assert "tile_sqs" not in index_facts(index)
    assert "tile_sqs" in index_facts(l2)


def test_no_clamp_at_zero_and_no_zero_test():
    """A true neighbour's dissimilarity is NEGATIVE, so a clamp would erase
    exactly the rows that matter; a score of exactly zero is orthogonality,
    not identity, and is returned like any other."""
    e = np.eye(8, dtype=np.float32)
    X = np.concatenate([e[:4] * 2.0, -e[:4]])  # scores in {2, 0, -1}
    Q = e[:2]
    assert KNNConfig(metric="ip").exclude_zero is False
    assert KNNConfig(metric="ip", exclude_zero=True).exclude_zero is False
    assert KNNConfig(metric="l2").exclude_zero is True  # the reference's
    for cfg in (cfg_for(k=8), cfg_for(k=8, exclude_zero=True)):
        got = all_knn(X, queries=Q, config=cfg)
        d, i = np.asarray(got.dists), np.asarray(got.ids)
        np.testing.assert_array_equal(
            d, [[-2, 0, 0, 0, 0, 0, 0, 1], [-2, 0, 0, 0, 0, 0, 0, 1]])
        assert i[0, 0] == 0 and i[0, -1] == 4 and i[1, 0] == 1
        assert sorted(i[0, 1:-1]) == [1, 2, 3, 5, 6, 7]  # the zeros, all
    raw = distance.pairwise_dist(jnp.asarray(Q), jnp.asarray(X), "ip")
    assert float(raw.min()) == -2.0 and float(raw.max()) == 1.0


def test_pairwise_neg_ip_is_the_negated_dot_alone():
    X, Q = rows(14, 64, 100), rows(15, 8, 100)
    got = np.asarray(distance.pairwise_neg_ip(jnp.asarray(Q), jnp.asarray(X)))
    want = -(Q.astype(np.float64) @ X.astype(np.float64).T)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    jaxpr = str(jax.make_jaxpr(distance.pairwise_neg_ip)(Q, X))
    for absent in ("max", "sqrt", "div", "reduce_sum", "sub"):
        assert f" {absent}" not in jaxpr.replace("reduce_max", "")


@pytest.mark.parametrize("call", [
    lambda: distance.pairwise_dist(jnp.ones((2, 4)), jnp.ones((3, 4)), "dot"),
    lambda: serial.dist_steps(2, 3, "dot"),
    lambda: serial.stack_norms(jnp.ones((1, 8, 4)), "dot"),
    lambda: KNNConfig(metric="dot"),
], ids=["pairwise_dist", "dist_steps", "stack_norms", "KNNConfig"])
def test_an_unknown_metric_falls_through_to_no_branch(call):
    with pytest.raises(ValueError, match="metric"):
        call()


# ---------------------------------------------------------------------------
# the carried lane-bin selection under negative values


W, D_SEL = 1024, 32  # tiles wide enough for the lane-bin rule to engage


def carried(X, ids, Q, k, q_rows=16):
    """``serve_chunk`` over a hand-built stack of 1024-column tiles: the
    engaged program (the lists ride the scan; one finish, certificate,
    re-scan of flagged rows, merge)."""
    cfg = KNNConfig(k=k, backend="serial", metric="ip", query_tile=q_rows,
                    corpus_tile=W, exclude_self=False)
    assert serial.carried_depth(cfg, q_rows, W) is not None
    tiles = jnp.asarray(X.reshape(-1, W, X.shape[1]))
    tile_ids = jnp.asarray(ids.reshape(-1, W))
    qt = jnp.asarray(Q.reshape(-1, q_rows, Q.shape[1]))
    qid = jnp.full(qt.shape[:2], -1, jnp.int32)
    cd, ci = init_topk_tiles(qt.shape[0], q_rows, k)
    out = jax.jit(serial.serve_chunk, static_argnames=("cfg",))(
        qt, qid, cd, ci, tiles, tile_ids, None, None, cfg=cfg)
    d, i, counts = out
    return (np.asarray(d).reshape(-1, k), np.asarray(i).reshape(-1, k),
            counts)


@pytest.mark.parametrize("sign", ["negative", "positive", "mixed"])
def test_carried_selection_on_scores_of_either_sign(sign):
    """+inf as the masked value, the lists' +inf start, the certificate
    against tau and the row bound compare values and nothing else: all-
    negative "distances" (every score positive) and all-positive ones come
    out as the full-width selection's."""
    X = rows(16, 3 * W, D_SEL, "positive" if sign != "mixed" else "mixed")
    Q = rows(17, 16, D_SEL, "positive" if sign != "mixed" else "mixed")
    if sign == "negative":
        Q = -Q
    d, i, counts = carried(X, np.arange(3 * W, dtype=np.int32), Q, K)
    assert_answers(d, i, X, Q, K)
    assert np.asarray(counts.select_tiles).sum() == 1
    # no one-pass branch to count on the device: the steps' path is static
    assert counts.dist_steps is None
    assert serial.tile_counts((counts,), 1, 3, "ip").dist_steps.tolist() == [
        0, 0, 0, 0, 3]
    assert (d > 0).all() if sign == "negative" else (
        (d < 0).all() if sign == "positive" else (d < 0).any())


def test_rows_with_fewer_than_k_live_slots_are_rescanned_exactly():
    """Seven live rows in two tiles of padding: fewer than k finite
    candidates flag every row, the re-scan answers them, and the unfilled
    slots hold (+inf, -1)."""
    X = rows(18, 2 * W, D_SEL)
    ids = np.full(2 * W, -1, np.int32)
    live = np.array([3, 200, 1023, 1024, 1500, 1501, 2047])
    ids[live] = live
    Q = rows(19, 16, D_SEL)
    d, i, counts = carried(X, ids, Q, K)
    assert np.asarray(counts.select_tiles).tolist() == [0, 1]  # rescanned
    want_d, want_i = oracle(X[live], Q, 7)
    np.testing.assert_array_equal(i[:, :7], live[want_i])
    np.testing.assert_allclose(d[:, :7], want_d, rtol=0, atol=1e-5)
    assert np.isinf(d[:, 7:]).all() and (d[:, 7:] > 0).all()
    assert (i[:, 7:] == -1).all()


def test_neighbours_crowding_one_lane_fail_the_certificate_and_are_rescanned():
    """Seven rows of far the largest score in ONE lane (column 5 of their
    128-column groups): more than the lists' depth keeps of a lane, so the
    certificate flags the row and the re-scan must find all seven."""
    X = rows(20, 3 * W, D_SEL) * 0.1
    Q = rows(21, 16, D_SEL, "positive")
    depth = serial.carried_depth(
        KNNConfig(k=K, metric="ip", backend="serial"), 16, W)
    crowd = np.array([5 + 128 * g for g in range(depth + 2)])
    X[crowd] = Q[0] * np.linspace(3.0, 4.0, len(crowd))[:, None]
    d, i, counts = carried(X, np.arange(3 * W, dtype=np.int32), Q, K)
    assert np.asarray(counts.select_tiles).tolist() == [0, 1]
    assert set(crowd) <= set(i[0])
    assert_answers(d, i, X, Q, K)


# ---------------------------------------------------------------------------
# what is refused, at the configuration or the build, each with its reason


@pytest.mark.parametrize("kw,says", [
    (dict(partitions=8), "clustered"),
    (dict(partitions=8, ivf_shards=2), "clustered"),
    (dict(backend="ring"), "backend='serial'"),
    (dict(backend="ring-overlap"), "backend='serial'"),
    (dict(precision_policy="mixed"), "precision_policy='exact'"),
    (dict(dtype="bfloat16"), "dtype='float32'"),
    (dict(dtype="int8", partitions=8), "dtype='float32'"),
    (dict(dtype="int4", partitions=8), "dtype='float32'"),
    (dict(ring_transfer_dtype="int8", precision_policy="mixed"), "exact"),
    (dict(bucket_headroom=0.25), "frozen"),
], ids=lambda v: "-".join(f"{a}={b}" for a, b in v.items())
   if isinstance(v, dict) else None)
def test_refused_at_the_configuration_with_what_to_do(kw, says):
    with pytest.raises(ValueError, match=says) as err:
        KNNConfig(metric="ip", **kw)
    assert "ip" in str(err.value)


def test_refused_at_the_build_with_what_to_do():
    X = rows(22, 256, 16)
    # "auto" over the test mesh's eight devices is the corpus ring
    assert len(jax.devices()) > 1
    with pytest.raises(ValueError, match="backend='serial'"):
        build_index(X, KNNConfig(metric="ip", k=4))
    with pytest.raises(ValueError, match="backend='serial'"):
        all_knn(X, queries=X[:8], metric="ip", k=4)
    indptr = np.arange(257, dtype=np.int64)
    tags = (indptr, np.zeros(256, np.int32))
    with pytest.raises(ValueError, match="without\\s+tags"):
        build_index(X, cfg_for(k=4), tags=tags)
    # one device, named or found: the dense serial index
    assert build_index(X, KNNConfig(
        metric="ip", k=4, num_devices=1)).backend == "serial"


def test_an_ip_index_is_frozen():
    from mpi_knn_tpu.serve import mutate

    X = rows(23, 256, 16)
    session = ServeSession(build_index(X, cfg_for(k=4)))
    assert not mutate.supports_mutation(session.index)
    one = np.array([1], np.int32)
    for write in (
            lambda: session.upsert(one, X[:1]),
            lambda: session.delete(one),
            lambda: mutate.upsert_rows(session.index, one, X[:1]),
            lambda: mutate.delete_rows(session.index, one)):
        with pytest.raises(ValueError, match="frozen"):
            write()
    # an L2 index beside it still takes writes
    assert mutate.supports_mutation(build_index(X, cfg_for(metric="l2")))


# ---------------------------------------------------------------------------
# the L2 and cosine programs are the programs they were


def lowered_1024(metric: str):
    """The cells' 1024-row batch program over two 8192-row tiles, lowered
    (not compiled) with the operands a resident index hands it."""
    cfg = KNNConfig(k=10, backend="serial", metric=metric, query_tile=1024,
                    corpus_tile=8192, query_bucket=64, exclude_zero=False)
    d = 200  # the cell's width: off the lane grid, nothing is screened
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    norms = None if metric == "ip" else sds((2, 8192), f32)
    fact = sds((), jnp.bool_) if metric == "l2" else None
    low = jax.jit(serial.serve_chunk, static_argnames=("cfg",)).lower(
        sds((1, 1024, d), f32), sds((1, 1024), i32),
        sds((1, 1024, 10), f32), sds((1, 1024, 10), i32),
        sds((2, 8192, d), f32), sds((2, 8192), i32), norms, fact, cfg=cfg)
    return low, len(jax.tree.leaves(low.args_info))


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_distance_programs_hold_nothing_of_the_inner_product(metric):
    low, operands = lowered_1024(metric)
    text = low.as_text(debug_info=True)
    assert distance.IP_SCOPE not in text
    # queries, ids, two scratch, stack, ids, norms (+ the one-pass fact)
    assert operands == (8 if metric == "l2" else 7)
    scope = {"l2": serial.MULTIPASS_SCOPE, "cosine": distance.COSINE_SCOPE}
    assert scope[metric] in text


def test_the_inner_product_program_names_its_scope_and_has_no_norm_operand():
    low, operands = lowered_1024("ip")
    text = low.as_text(debug_info=True)
    assert distance.IP_SCOPE in text and operands == 6
    for other in (distance.COSINE_SCOPE, distance.QUNIT_SCOPE,
                  serial.ONEPASS_SCOPE, serial.FUSED_SCOPE, "knn.norms",
                  "knn.center"):
        assert other not in text
    # the step's arithmetic: one dot a tile step, nothing that normalises
    plain = low.as_text()
    assert plain.count("stablehlo.dot_general") == 2  # the scan + re-scan
    import re

    assert "stablehlo.sqrt" not in plain and "stablehlo.rsqrt" not in plain
    # (the re-scan's loop divides its int32 counter; no float is divided)
    assert not re.search(r"stablehlo\.divide .*xf32>", plain)
    # no clamp: no float maximum (the finish kernel takes an int32 one)
    assert not re.search(r"stablehlo\.maximum %.*xf32>", plain)
    # never the one-pass rule, whatever the corpus fact says
    cfg = cfg_for(query_tile=1024, corpus_tile=8192)
    assert not distance.onepass_applies(cfg)
    assert serial.fused_rule(cfg, 1024, 8192, 200) is None
    assert serial.carried_depth(cfg, 1024, 8192) == 5  # the cells' depth


# ---------------------------------------------------------------------------
# the instruments


def test_ip_tile_steps_count_on_a_path_of_their_own():
    cfg = cfg_for()
    X, Q = rows(24, M, 64), rows(25, NQ, 64)
    tiles = -(-M // 128)
    one_shot = all_knn(X, queries=Q, config=cfg)
    q_tiles = -(-NQ // 16)
    np.testing.assert_array_equal(
        np.asarray(one_shot.dist_steps), [0, 0, 0, 0, q_tiles * tiles])

    session = ServeSession(build_index(X, cfg))
    before = steps_counted()
    session.submit(Q[:16])  # one 16-row bucket: one query tile
    session.drain()
    after = steps_counted()
    assert after["ip"] - before["ip"] == tiles
    for other in ("onepass", "multipass", "cosine", "fused"):
        assert after[other] == before[other]
    reg = obs_metrics.MetricsRegistry()
    reg.count_dist_steps(np.array([[3, 1], [2, 0]]))
    reg.count_dist_steps(np.array([0, 0, 0, 0, 9]))
    got = {p: reg.counter(obs_metrics.DIST_STEPS, labels={"path": p}).value
           for p in obs_metrics.DIST_PATHS}
    assert got == {"onepass": 5, "multipass": 1, "cosine": 0, "fused": 0,
                   "ip": 9, "u8": 0, "fused_screen": 0}


@pytest.mark.parametrize("metric", ["l2", "cosine", "ip"])
def test_a_gauge_names_the_metric_the_resident_index_answers_by(metric):
    build_index(rows(26, 256, 16), cfg_for(metric=metric, exclude_zero=False))
    reg = obs_metrics.get_registry()
    for name in ("l2", "cosine", "ip"):
        gauge = reg.gauge("serve_index_metric", labels={"metric": name})
        assert gauge.value == float(name == metric)
    text = reg.to_prometheus()
    assert f'serve_index_metric{{metric="{metric}"}} 1' in text


# ---------------------------------------------------------------------------
# the entry points: /query, `mpi-knn serve --metric ip`, `mpi-knn knn`


def _post(url, path, data, ctype="application/json"):
    req = urllib.request.Request(
        url + path, data=data, headers={"Content-Type": ctype},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_query_route_answers_negated_products_and_refuses_writes():
    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.resilience import ResiliencePolicy

    X, Q = rows(27, 512, 200), rows(28, 8, 200, "positive")
    X[:64] = np.abs(X[:64])  # some rows every query scores high
    index = build_index(X, cfg_for(query_bucket=16))
    fe = Frontend(
        ServeSession(index, resilience=ResiliencePolicy()),
        SLOPolicy(max_batch_rows=16, max_wait_s=0.002, max_queue_rows=512),
    ).start(warm_sizes=[16], background=False)
    srv = FrontendHTTPServer(fe, port=0).start()
    try:
        status, doc = _post(srv.url, "/query", json.dumps(
            {"queries": Q.tolist()}).encode())
        assert status == 200 and doc["rows"] == 8 and doc["metric"] == "ip"
        assert_answers(np.asarray(doc["dists"], np.float32),
                       np.asarray(doc["ids"], np.int32), X, Q, K)
        assert np.asarray(doc["dists"]).max() < 0  # -<q, c>, ascending
        raw = np.arange(1, dtype="<i4").tobytes() + X[:1].tobytes()
        status, doc = _post(srv.url, "/upsert", raw,
                            "application/octet-stream")
        assert status == 400 and "frozen" in doc["error"]
        status, doc = _post(srv.url, "/delete", json.dumps(
            {"ids": [0]}).encode())
        assert status == 400 and "frozen" in doc["error"]
        with urllib.request.urlopen(srv.url + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
    finally:
        srv.stop()
        fe.stop()
    assert 'knn_dist_tile_steps_total{path="ip"}' in metrics
    assert 'serve_index_metric{metric="ip"} 1' in metrics


def test_cli_parsers_offer_the_metric_and_refuse_what_it_cannot_reach(
        tmp_path, capsys):
    from mpi_knn_tpu import cli
    from mpi_knn_tpu.frontend import cli as frontend_cli
    from mpi_knn_tpu.frontend.cli import serve_main

    for parser in (cli.build_parser(), frontend_cli.build_serve_parser()):
        (action,) = [a for a in parser._actions if a.dest == "metric"]
        assert "ip" in action.choices
    data = "synthetic:512x32c4"
    for refused in (["--partitions", "4"], ["--precision-policy", "mixed"],
                    ["--backend", "ring"], ["--bucket-headroom", "0.25"]):
        assert serve_main(["--data", data, "--metric", "ip", "-q",
                           *refused]) == 2
        assert "ip" in capsys.readouterr().err
    out = tmp_path / "knn.npz"
    assert cli.main(["--data", data, "--metric", "ip", "--k", "5",
                     "--backend", "serial", "--platform", "cpu",
                     "--save-neighbors", str(out)]) == 0
    got = np.load(out)
    X, _, _ = cli.load_corpus(data)
    ids = np.arange(len(X), dtype=np.int32)
    assert_answers(got["dists"], got["ids"], X, X, 5, ids)


def test_the_lint_matrix_holds_the_cells_ip_can_reach_and_no_other():
    from mpi_knn_tpu.analysis import lowering

    ip = [t for t in lowering.default_targets() if t.metric == "ip"]
    assert {t.label for t in ip} == {
        "serial/ip/float32", "serial/ip/float32/serve"}
    for t in ip:
        lowering.lower_target(t)  # lowers: the configuration is accepted
