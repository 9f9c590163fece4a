"""The clustered index built from a DEVICE array (``ivf/index.py
_store_on_device``): mean, training sample, assignment and fill without a
host round trip of the corpus.

- against the host build on the same seed and sample: array for array, on
  whole-number rows whose count is a power of two (the float64 mean is then
  a float32 number, so both paths centre to the same bits);
- the sample rule: train on a seeded draw, assign every row — every row in
  exactly one list, none lost, padding ids -1, the stored row its own;
- ``nprobe == partitions`` is the exact scan (the parity tests' rule,
  against the serial backend and against the benchmark's plain reference);
- what a batch probed, counted on the device beside the answer, against a
  hand count on a 4-partition index, and the registry's arithmetic;
- the new scopes in the lowered program, the build's spans, the gauges;
- a clustered session with no deadline set never walks its ladder.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, all_knn, query_knn
from mpi_knn_tpu.ivf import build_ivf_index, search_ivf
from mpi_knn_tpu.ivf.index import _fill_step, _fill_store
from mpi_knn_tpu.ivf.kmeans import (
    _assign_blocks,
    assign_rows,
    column_sums,
    sample_rows,
)
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.distance import sq_norms

STORE = ("centroids", "centroid_sqs", "buckets", "bucket_ids", "bucket_sqs")


def whole_rows(seed: int, m: int = 1024, d: int = 16, centres: int = 8):
    """Descriptor-like whole numbers in [0, 255] around a few centres."""
    rng = np.random.default_rng(seed)
    cen = rng.random((centres, d)) * 140.0
    x = cen[rng.integers(0, centres, m)] + rng.standard_normal((m, d)) * 30
    return np.clip(np.rint(x), 0, 255).astype(np.float32)


def cfg_for(**over) -> KNNConfig:
    base = dict(k=5, partitions=8, nprobe=8, kmeans_iters=4,
                exclude_self=False, query_bucket=64)
    return KNNConfig(**{**base, **over})


# ---- device build against host build -------------------------------------

@pytest.mark.parametrize("sample", [None, 256, 512])
@pytest.mark.parametrize("init", ["kmeans++", "random"])
def test_device_build_is_the_host_build(sample, init):
    X = whole_rows(3)
    cfg = cfg_for(kmeans_sample=sample, kmeans_init=init)
    host = build_ivf_index(X, cfg)
    dev = build_ivf_index(jnp.asarray(X), cfg)
    assert dev.bucket_cap == host.bucket_cap
    np.testing.assert_array_equal(dev.mu, host.mu)
    for name in STORE:
        np.testing.assert_array_equal(
            np.asarray(getattr(dev, name)), np.asarray(getattr(host, name)),
            err_msg=name)
    q = X[:64] + 0.5
    for a, b in zip(search_ivf(dev, q), search_ivf(host, q)):
        np.testing.assert_array_equal(a, b)


# ---- the whole-number mean and the store's one-pass fact (ISSUE 46) --------

@pytest.mark.parametrize("m,d,fact", [
    (1000, 128, True),  # a mean that is no whole number before the rounding
    (1024, 128, True),
    (1000, 16, None),  # off the lane grid: the walk never takes this store
])
def test_a_whole_number_corpus_is_centred_by_a_whole_number_mean(m, d, fact):
    """Host and device builds round the SAME mean and read the same fact:
    the store holds whole numbers, every one a bf16 number."""
    X = whole_rows(41, m=m, d=d)
    cfg = cfg_for(kmeans_sample=256)
    host = build_ivf_index(X, cfg)
    dev = build_ivf_index(jnp.asarray(X), cfg)
    plain = X.astype(np.float64).mean(axis=0)
    np.testing.assert_array_equal(host.mu, np.rint(plain))
    np.testing.assert_array_equal(dev.mu, host.mu)
    assert host.mu.dtype == dev.mu.dtype == np.float64
    if m == 1000:
        assert not np.array_equal(plain, np.rint(plain))
    for idx in (host, dev):
        store = np.asarray(idx.buckets)
        np.testing.assert_array_equal(store, np.rint(store))
        assert np.abs(store).max() <= 256
        assert (idx.onepass is None) == (fact is None)
        # what the rounding took off rides with the fact, and alone with it
        assert (idx.mean_frac is None) == (fact is None)
        if fact:
            assert isinstance(idx.onepass, jax.Array) and bool(idx.onepass)
            frac = plain.astype(np.float32).astype(np.float64) - idx.mu
            assert frac.any() and np.abs(frac).max() <= 0.5
            assert idx.mean_frac.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(idx.mean_frac), frac)
    for name in STORE:
        np.testing.assert_array_equal(
            np.asarray(getattr(dev, name)), np.asarray(getattr(host, name)),
            err_msg=name)


@pytest.mark.parametrize("on_device", [False, True])
@pytest.mark.parametrize("what,center,fact", [
    ("whole", True, True),
    ("whole", False, True),  # 0-255 as they are: bf16 numbers too
    ("to_1000", True, False),  # whole, but 9 bits and more: not bf16
    ("half_planted", True, False),  # one 0.5: the mean is the plain mean
    ("half_planted", False, True),  # 0.5 IS a bf16 number
    ("third_planted", False, False),  # 0.3 is not: read from the bits
    ("fractional", True, False),
])
def test_the_fact_is_read_from_the_stores_bits(what, center, fact,
                                               on_device):
    X = whole_rows(43, m=1024, d=128)
    if what == "to_1000":
        X = X * np.float32(4.0) - np.float32(3.0)
    elif what == "half_planted":
        X[517, 3] = 0.5
    elif what == "third_planted":
        X[517, 3] = 0.3
    elif what == "fractional":
        X = X + np.float32(0.125)  # bf16 numbers before centring, not after
    idx = build_ivf_index(jnp.asarray(X) if on_device else X,
                          cfg_for(center=center, kmeans_sample=256))
    assert (idx.onepass is not None) == fact
    whole = bool((X == np.rint(X)).all())
    # nothing to take off where nothing was rounded, or no walk to rank by
    assert (idx.mean_frac is not None) == (fact and whole and center)
    if center:
        assert np.array_equal(idx.mu, np.rint(idx.mu)) == whole
        if not whole and not on_device:  # the mean it always was
            np.testing.assert_array_equal(
                idx.mu, X.astype(np.float64).mean(axis=0))
    # the fact is the store's: every element's low 16 bits
    bits = np.asarray(idx.buckets).view(np.uint32)
    assert bool((bits & 0xFFFF == 0).all()) == fact


def test_a_saved_index_keeps_what_the_rounding_took_off(tmp_path):
    """The fact is read anew from the loaded store's bits; the fraction of
    the mean cannot be, so the archive carries it (one from before it did
    loads with none: its mean was not rounded)."""
    from mpi_knn_tpu.ivf import load_ivf_index, save_ivf_index

    X = whole_rows(44, m=1000, d=128)
    idx = build_ivf_index(X, cfg_for(kmeans_sample=256))
    back = load_ivf_index(save_ivf_index(idx, str(tmp_path / "whole")))
    assert bool(back.onepass)
    np.testing.assert_array_equal(
        np.asarray(back.mean_frac), np.asarray(idx.mean_frac))
    q = whole_rows(46, m=64, d=128)
    for a, b in zip(search_ivf(back, q), search_ivf(idx, q)):
        np.testing.assert_array_equal(a, b)
    frac = build_ivf_index(X + np.float32(0.125), cfg_for(kmeans_sample=256))
    back = load_ivf_index(save_ivf_index(frac, str(tmp_path / "frac")))
    assert back.onepass is None and back.mean_frac is None


def test_a_device_mean_of_a_fractional_corpus_is_the_float32_mean():
    """What the device path did before the rule, number for number: the
    float32 nearest the float64 mean of the blocks' sums."""
    X = whole_rows(45, m=1000, d=16) + np.float32(0.25)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(kmeans_sample=256))
    sums = np.asarray(column_sums(jnp.asarray(X)), np.float64)
    want = (sums.sum(axis=0) / 1000).astype(np.float32).astype(np.float64)
    np.testing.assert_array_equal(idx.mu, want)
    both, whole = column_sums(jnp.asarray(X), whole=True)
    np.testing.assert_array_equal(np.asarray(both), sums)
    assert not np.asarray(whole).any()


@pytest.mark.parametrize("m,block,bad", [
    (1024, 256, None), (1000, 333, None), (1000, 333, 999), (1024, 256, 300),
    (50, 50, 7)])
def test_column_sums_say_which_blocks_are_whole(m, block, bad):
    X = whole_rows(9, m=m, d=8)
    if bad is not None:
        X[bad, 2] += np.float32(0.5)
    sums, whole = column_sums(jnp.asarray(X), block=block, whole=True)
    np.testing.assert_array_equal(
        np.asarray(sums), np.asarray(column_sums(jnp.asarray(X),
                                                 block=block)))
    want = np.ones(-(-m // block), bool)
    if bad is not None:
        want[bad // block] = False
    np.testing.assert_array_equal(np.asarray(whole), want)


def test_an_upsert_of_a_fractional_row_turns_the_fact_off_in_place():
    """The fact is a device scalar the batch program takes: a row that is
    no bf16 number after centring flips it for good, nothing recompiles,
    the gauge reads 0 and the next batch — six passes — holds the row."""
    from mpi_knn_tpu.obs.metrics import watch_compiles
    from mpi_knn_tpu.serve.index import onepass_holds
    from mpi_knn_tpu.serve import ServeSession
    from mpi_knn_tpu.serve.mutate import upsert_rows, warm_mutation

    X = whole_rows(47, m=2048, d=128)
    q = whole_rows(48, m=64, d=128)
    cfg = cfg_for(k=4, nprobe=8, kmeans_sample=512, bucket_headroom=0.25,
                  mutation_bucket=8)
    idx = build_ivf_index(jnp.asarray(X), cfg)
    reg = obs_metrics.MetricsRegistry()
    with _swap_registry(reg):
        sess = ServeSession(idx)
        warm_mutation(idx, cfg, sizes=[8])
        (out,) = list(sess.stream([q]))
        counts = np.asarray(out.ivf_probe)
        assert counts[6] == counts[5] > 0 and onepass_holds(idx)
        assert reg.gauge("ivf_index_onepass").value == 1.0
        upsert_rows(idx, [5000], q[:1] + np.float32(1.0))
        assert bool(idx.onepass) and onepass_holds(idx)  # whole: it holds
        with watch_compiles() as compiles:
            upsert_rows(idx, [5001], q[3:4] + np.float32(0.3))
            (out,) = list(sess.stream([q]))
        assert compiles == []
        assert idx.onepass is not None and not bool(idx.onepass)
        assert not onepass_holds(idx)
        assert reg.gauge("ivf_index_onepass").value == 0.0
        counts = np.asarray(out.ivf_probe)
        assert counts[6] == 0 < counts[5]
        upsert_rows(idx, [5002], q[5:6] + np.float32(2.0))
        assert not bool(idx.onepass)  # a whole row does not bring it back
    # every list probed: the reference over the rows the index now holds
    rows = np.concatenate([X, q[:1] + 1.0, q[3:4] + np.float32(0.3)])
    ids = np.concatenate([np.arange(2048), [5000, 5001]])
    d2 = ((q[:, None].astype(np.float64) - rows[None].astype(np.float64))
          ** 2).sum(-1)
    d2[d2 == 0] = np.inf  # exclude_zero
    order = np.argsort(d2, axis=1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(out.ids), ids[order])
    np.testing.assert_allclose(
        np.asarray(out.dists), np.take_along_axis(d2, order, axis=1),
        rtol=1e-5, atol=0.25)  # the matmul form at norms of 4e5 (an ulp
    # of their sum is 0.0625), on operands the finish made fractional
    assert out.ids[3, 0] == 5001 and out.ids[0, 0] == 5000


@pytest.mark.parametrize("m,parts,sample,headroom", [
    (1000, 7, 300, 0.0),  # no block of the passes divides the rows
    (4096, 16, 1024, 0.0),
    (777, 4, None, 0.25),  # every row trains; headroom pads further
    (2048, 32, 64, 0.0),  # two points a centroid
])
def test_every_row_in_exactly_one_list(m, parts, sample, headroom):
    X = whole_rows(5, m=m, d=8) + np.float32(0.25)  # fractional after all
    idx = build_ivf_index(jnp.asarray(X), cfg_for(
        partitions=parts, nprobe=parts, kmeans_sample=sample,
        bucket_headroom=headroom))
    ids = np.asarray(idx.bucket_ids)
    live = ids >= 0
    assert sorted(ids[live].tolist()) == list(range(m))  # once each
    assert (ids[~live] == -1).all()
    counts = live.sum(axis=1)
    assert idx.bucket_cap % 8 == 0
    assert idx.bucket_cap >= int(np.ceil(counts.max() * (1 + headroom)))
    # live slots lead each bucket in ascending id; padding rows are zero
    for p in range(parts):
        assert live[p, :counts[p]].all() and not live[p, counts[p]:].any()
        assert (np.diff(ids[p, :counts[p]]) > 0).all()
    store = np.asarray(idx.buckets)
    assert not store[~live].any()
    want = X[ids[live]] - idx.mu.astype(np.float32)
    np.testing.assert_array_equal(store[live], want)
    # and each row lies with its nearest centroid
    cen = np.asarray(idx.centroids, np.float64)
    d2 = ((want[:, None, :].astype(np.float64) - cen[None]) ** 2).sum(-1)
    mine = np.nonzero(live)[0]
    gap = d2[np.arange(len(mine)), mine] - d2.min(axis=1)
    assert (gap <= 1e-3 * d2.min(axis=1) + 1e-3).all()


@pytest.mark.parametrize("block", [64, 333, 1000, 4096])
def test_assign_rows_is_assign_blocks(block):
    rng = np.random.default_rng(0)
    X = rng.standard_normal((1000, 16)).astype(np.float32)
    mu = X.mean(axis=0)
    cen = jnp.asarray(X[:9] - mu)
    got, counts = assign_rows(jnp.asarray(X), jnp.asarray(mu), cen,
                              block=min(block, 1000))
    Xc = jnp.asarray(X) - jnp.asarray(mu)
    want, _ = _assign_blocks(Xc, sq_norms(Xc), cen, 256)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(want), minlength=9))


@pytest.mark.parametrize("m,block", [(1024, 256), (1000, 333), (50, 50)])
def test_column_sums_add_up_exactly_on_whole_numbers(m, block):
    X = whole_rows(9, m=m, d=8)
    sums = np.asarray(column_sums(jnp.asarray(X), block=block), np.float64)
    assert sums.shape == (-(-m // block), 8)
    np.testing.assert_array_equal(sums.sum(axis=0),
                                  X.astype(np.float64).sum(axis=0))


@pytest.mark.parametrize("m,n", [(100, 10), (100, 99), (5000, 256)])
def test_sample_rows_are_distinct_ascending_and_seeded(m, n):
    a, b = sample_rows(m, n, 3), sample_rows(m, n, 3)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32 and a.shape == (n,)
    assert (np.diff(a) > 0).all() and a[0] >= 0 and a[-1] < m
    assert not np.array_equal(a, sample_rows(m, n, 4))


@pytest.mark.parametrize("n", [None, 100, 101])
def test_sample_of_every_row_is_none(n):
    assert sample_rows(100, n, 0) is None


@pytest.mark.parametrize("parts,elems,want", [
    (4096, 3000 * 128, 16), (4096, 1 << 30, 1), (12, 8, 12), (7, 1 << 22, 1),
])
def test_fill_step_divides_the_partitions(parts, elems, want):
    assert _fill_step(parts, elems) == want and parts % want == 0


def test_fill_store_steps_agree():
    X = whole_rows(11, m=512, d=8)
    rng = np.random.default_rng(1)
    assign = rng.integers(0, 8, 512).astype(np.int32)
    counts = np.bincount(assign, minlength=8).astype(np.int32)
    order = np.argsort(assign, kind="stable").astype(np.int32)
    cap = int(counts.max()) + 3
    args = (jnp.asarray(X), jnp.zeros(8, jnp.float32), jnp.asarray(order),
            jnp.asarray(counts))
    one = _fill_store(*args, cap=cap, step=1)
    for step in (2, 8):
        other = _fill_store(*args, cap=cap, step=step)
        for a, b in zip(one, other):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the exact scan inside it ---------------------------------------------

@pytest.mark.parametrize("sample", [None, 256])
def test_all_partitions_probed_is_the_serial_scan(sample):
    rng = np.random.default_rng(2)
    cen = rng.standard_normal((12, 32)).astype(np.float32) * 4
    X = (cen[rng.integers(0, 12, 1024)]
         + rng.standard_normal((1024, 32)).astype(np.float32))
    idx = build_ivf_index(jnp.asarray(X), cfg_for(
        k=10, kmeans_sample=sample, exclude_self=True))
    rows = np.arange(0, 1024, 4)
    gd, gi = search_ivf(idx, X[rows], query_ids=rows.astype(np.int32))
    want = all_knn(X, queries=X[rows], query_ids=rows,
                   config=KNNConfig(k=10, backend="serial"))
    # value parity (the two paths centre by means that differ in their
    # last bits: float32 here, float64 rounded to whole numbers there)
    np.testing.assert_allclose(gd, np.asarray(want.dists), rtol=1e-4,
                               atol=1e-4)
    same = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                    for a, b in zip(gi, np.asarray(want.ids))])
    assert same >= 0.999


def test_all_partitions_probed_meets_the_plain_reference():
    from benchmark import reference
    from benchmark.harness import load_by_path

    X = whole_rows(21, m=2048, d=32)
    q = whole_rows(22, m=64, d=32)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(
        k=10, partitions=16, nprobe=16, kmeans_sample=512))
    got_d, got_i = search_ivf(idx, q)
    ref_d, ref_i = reference.exact_knn(X, q, 40, exclude_zero=True)
    compare = load_by_path("drivers", "serve_ivf").compare_ivf
    verdict = compare(got_i, got_d, ref_i, ref_d, {
        "recall_min": 0.999, "tie_rtol": 1e-5,
        "returned_dist_rel_err_max": 8e-6, "corpus_rows": 2048})
    assert verdict["ok"], verdict["numbers"]
    # and a probe of one list in sixteen is seen for what it is
    few_d, few_i = search_ivf(idx, q, nprobe=1)
    verdict = compare(few_i, few_d, ref_i, ref_d, {
        "recall_min": 0.999, "tie_rtol": 1e-5,
        "returned_dist_rel_err_max": 8e-6, "corpus_rows": 2048})
    assert not verdict["numbers"]["recall_at_k"][2]
    assert verdict["numbers"]["returned_dist_rel_err_max"][2]


# ---- what a batch probed --------------------------------------------------

@pytest.mark.parametrize("nprobe", [1, 2, 4])
def test_probe_counts_against_a_hand_count(nprobe):
    X = whole_rows(7, m=640, d=16, centres=4)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(
        partitions=4, nprobe=nprobe, kmeans_sample=None))
    q = whole_rows(8, m=50, d=16, centres=4)
    res = query_knn(q, idx)
    probes, cap, live, distinct, distinct_live, walked, onepass = np.asarray(
        res.ivf_probe).tolist()
    assert walked == onepass == 0  # d = 16: row-major, no work items
    # by hand: the padded batch is one 64-row bucket; a padding row is a
    # row of zeros in the centred frame, and probes like any other
    qc = np.zeros((64, 16), np.float64)
    qc[:50] = q.astype(np.float64) - idx.mu
    cen = np.asarray(idx.centroids, np.float64)
    near = np.argsort(((qc[:, None] - cen[None]) ** 2).sum(-1), axis=1,
                      kind="stable")[:, :nprobe]
    counts = (np.asarray(idx.bucket_ids) >= 0).sum(axis=1)
    assert probes == 64 * nprobe and cap == idx.bucket_cap
    assert live == int(counts[near].sum())
    assert distinct == len(np.unique(near))
    assert distinct_live == int(counts[np.unique(near)].sum())


def test_probe_counters_arithmetic():
    reg = obs_metrics.MetricsRegistry()
    reg.count_ivf_probe(
        np.array([128, 288, 33152, 4, 1024, 24, 24], np.int32))
    reg.count_ivf_probe(np.array([64, 288, 100, 2, 500, 0, 0], np.int32))
    reg.count_ivf_probe(np.array([64, 288, 100, 2, 500, 5, 0], np.int32))
    text = reg.to_prometheus()
    for line in ("ivf_probe_slots_total 73728.0",
                 "ivf_probe_live_rows_total 33352.0",
                 'ivf_probe_partitions_total{kind="probes"} 256.0',
                 'ivf_probe_partitions_total{kind="distinct"} 8.0',
                 "ivf_probe_distinct_live_rows_total 2024.0",
                 "ivf_probe_groups_total 29.0",
                 "ivf_probe_groups_onepass_total 24.0",
                 'ivf_probe_batches_total{path="bucket_major"} 2.0',
                 'ivf_probe_batches_total{path="row_major"} 1.0'):
        assert line in text.splitlines(), (line, text)


def test_served_batches_count_what_they_probed():
    from mpi_knn_tpu.serve import ServeSession

    X = whole_rows(13, m=512, d=16, centres=4)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(partitions=4, nprobe=2))
    reg = obs_metrics.MetricsRegistry()
    with _swap_registry(reg):
        sess = ServeSession(idx)
        outs = list(sess.stream([X[:64], X[64:128]]))
    assert [o.rows for o in outs] == [64, 64]
    for o in outs:
        assert np.asarray(o.ivf_probe).shape == (7,)
    text = reg.to_prometheus().splitlines()
    assert f"ivf_probe_slots_total {float(128 * 2 * idx.bucket_cap)}" in text
    assert 'ivf_probe_partitions_total{kind="probes"} 256.0' in text
    assert f"ivf_bucket_cap {float(idx.bucket_cap)}" in text
    assert "serve_index_nprobe 2.0" in text
    fill = [ln for ln in text if ln.startswith("ivf_bucket_fill_pct ")]
    assert fill and abs(float(fill[0].split()[1])
                        - 100.0 * 512 / (4 * idx.bucket_cap)) < 1e-9


class _swap_registry:
    """The process registry replaced for a block (sessions read it at
    their start)."""

    def __init__(self, reg):
        self.reg = reg

    def __enter__(self):
        self.old = obs_metrics._default_registry
        obs_metrics._default_registry = self.reg

    def __exit__(self, *exc):
        obs_metrics._default_registry = self.old


# ---- scopes, spans, options -----------------------------------------------

@pytest.mark.parametrize("scope", ["knn.ivf/score", "knn.ivf/gather",
                                   "knn.rerank"])
def test_scopes_are_in_the_lowered_program(scope):
    from mpi_knn_tpu.serve.engine import lower_bucket

    X = whole_rows(17, m=256, d=16, centres=4)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(partitions=4, nprobe=2))
    lowered, _, _ = lower_bucket(idx, idx.cfg, 64)
    assert scope in lowered.as_text(debug_info=True)


def test_build_spans_say_what_each_phase_did(tmp_path):
    X = whole_rows(19, m=1024, d=16)
    rec = obs_spans.FlightRecorder(str(tmp_path / "flight.jsonl"))
    obs_spans.set_recorder(rec)
    try:
        idx = build_ivf_index(jnp.asarray(X), cfg_for(kmeans_sample=256))
    finally:
        obs_spans.set_recorder(None)
    spans, _ = obs_spans.reconstruct_spans(
        obs_spans.read_flight(str(tmp_path / "flight.jsonl")))
    by_name = {s["name"]: s for s in spans if s["cat"] == "index"}
    assert set(by_name) >= {"index-build", "ivf-train", "ivf-assign",
                            "ivf-fill"}
    assert all(s["dur_s"] is not None for s in by_name.values())
    build = by_name["index-build"]
    for name in ("ivf-train", "ivf-assign", "ivf-fill"):
        assert by_name[name]["parent"] == build["span"]
    assert by_name["ivf-train"]["attrs"] == {
        "rows": 256, "partitions": 8, "iters": 4}
    assert by_name["ivf-assign"]["attrs"] == {"rows": 1024}
    fill = by_name["ivf-fill"]["attrs"]
    assert fill["bucket_cap"] == idx.bucket_cap and fill["rows"] == 1024
    assert fill["bytes"] == 8 * idx.bucket_cap * (16 * 4 + 8)
    assert fill["fill_pct"] == round(100 * 1024 / (8 * idx.bucket_cap))


def test_sample_smaller_than_the_partitions_is_refused():
    with pytest.raises(ValueError, match="kmeans_sample"):
        KNNConfig(partitions=16, kmeans_sample=8)
    KNNConfig(partitions=16, kmeans_sample=16)


def test_sample_is_baked_into_the_index():
    X = whole_rows(23, m=256, d=16, centres=4)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(
        partitions=4, nprobe=2, kmeans_sample=128))
    with pytest.raises(ValueError, match="kmeans_sample"):
        idx.compatible_cfg(idx.cfg.replace(kmeans_sample=64))


@pytest.mark.parametrize("sample,cap", [
    (None, None), (128, None), (None, 128), (128, 96)])
def test_unset_build_knobs_leave_cache_addresses_alone(sample, cap):
    from mpi_knn_tpu.serve import aotcache

    X = whole_rows(29, m=256, d=16, centres=4)
    idx = build_ivf_index(X, cfg_for(partitions=4, nprobe=2,
                                     kmeans_sample=sample, bucket_cap=cap))
    doc = aotcache.fingerprint_facts(idx, idx.cfg, 64)["cfg"]
    assert ("kmeans_sample" in doc) == (sample is not None)
    assert ("bucket_cap" in doc) == (cap is not None)
    new = {"kmeans_sample", "bucket_cap"}
    assert set(doc) - new == {
        f.name for f in dataclasses.fields(KNNConfig)
    } - new - {"max_query_tags"}


# ---- the ladder stays where the configuration put it ----------------------

@pytest.mark.parametrize("deadline_s,walked", [(None, False), (0.0, True)])
def test_a_clustered_session_degrades_only_under_a_deadline(
        deadline_s, walked):
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession

    X = whole_rows(31, m=512, d=16, centres=4)
    idx = build_ivf_index(jnp.asarray(X), cfg_for(partitions=4, nprobe=4))
    sess = ServeSession(idx, resilience=ResiliencePolicy(
        batch_deadline_s=deadline_s, degrade_after=1))
    assert [label for label, _ in sess.ladder][:2] == ["full", "nprobe/2"]
    outs = list(sess.stream([X[i:i + 64] for i in range(0, 512, 64)]))
    assert len(outs) == 8
    if walked:
        assert sess.rung != "full" and sess.degradations
        assert any(o.degraded for o in outs)
    else:
        assert sess.rung == "full" and not sess.degradations
        assert all(o.degraded is None for o in outs)
        assert not any(o.deadline_breached for o in outs)


# ---- size balancing of the training rounds --------------------------------

def isotropic_classes(seed: int, classes=16, per=600, d=64):
    """Many isotropic classes in many dimensions: the law under which a
    class keeps the centres it was seeded with, however few."""
    rng = np.random.default_rng(seed)
    cen = rng.random((classes, d)) * 140.0
    which = rng.integers(0, classes, classes * per)
    x = cen[which] + rng.standard_normal((classes * per, d)) * 30.0
    x = np.clip(np.rint(x), 0, 255).astype(np.float32)
    return x - x.mean(axis=0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_balance_splits_the_largest_partitions(seed):
    from mpi_knn_tpu.ivf import kmeans

    X = isotropic_classes(seed)
    mean = X.shape[0] / 128
    plain = kmeans(X, 128, iters=10, seed=0)
    even = kmeans(X, 128, iters=10, seed=0, balance=2.0)
    assert int(np.asarray(even.counts).sum()) == X.shape[0]
    assert int(even.counts.max()) < int(plain.counts.max())
    assert int(plain.counts.max()) > 2.2 * mean  # the law has the fault
    assert int(even.counts.max()) <= 2.15 * mean
    assert float(even.inertia) <= 1.01 * float(plain.inertia)


def test_a_multiple_nobody_reaches_leaves_plain_lloyd():
    from mpi_knn_tpu.ivf import kmeans

    X = isotropic_classes(3, classes=8, per=200, d=16)
    plain = kmeans(X, 32, iters=6, seed=2)
    never = kmeans(X, 32, iters=6, seed=2, balance=1000.0)
    np.testing.assert_array_equal(np.asarray(plain.centroids),
                                  np.asarray(never.centroids))
    again = kmeans(X, 32, iters=6, seed=2, balance=1.5)
    twice = kmeans(X, 32, iters=6, seed=2, balance=1.5)
    np.testing.assert_array_equal(np.asarray(again.centroids),
                                  np.asarray(twice.centroids))
    assert not np.array_equal(np.asarray(again.centroids),
                              np.asarray(plain.centroids))


@pytest.mark.parametrize("cap", [8, 64, 127])
def test_a_stated_cap_that_cannot_hold_the_rows_is_refused(cap):
    X = whole_rows(6, m=1024, d=16)  # 8 lists: 128 slots each at least
    with pytest.raises(ValueError, match="cannot hold"):
        build_ivf_index(jnp.asarray(X), cfg_for(bucket_cap=cap))


def test_a_stated_cap_balances_the_build_on_both_paths():
    X = isotropic_classes(4) + np.float32(100.0)
    X = np.clip(np.rint(X), 0, 255).astype(np.float32)[:8192]
    caps = {}
    for cap in (None, 152):  # lists over 5/6 x 152 = 1.98 x the mean split
        cfg = cfg_for(partitions=128, nprobe=8, kmeans_iters=10,
                      kmeans_sample=4096, bucket_cap=cap)
        host = build_ivf_index(X, cfg)
        dev = build_ivf_index(jnp.asarray(X), cfg)
        assert host.bucket_cap == dev.bucket_cap
        np.testing.assert_array_equal(np.asarray(host.bucket_ids),
                                      np.asarray(dev.bucket_ids))
        caps[cap] = dev.bucket_cap
        largest = int((np.asarray(dev.bucket_ids) >= 0).sum(axis=1).max())
    assert caps[152] == 152 < caps[None]
    assert largest <= 152


# ---- a provisioned bucket height ------------------------------------------

@pytest.mark.parametrize("asked,seeds", [(400, (1, 2, 3)), (397, (4,))])
def test_a_stated_bucket_cap_fixes_the_shapes_whatever_the_data(
        asked, seeds):
    shapes = set()
    for seed in seeds:
        X = whole_rows(seed, m=1024, d=16)
        idx = build_ivf_index(jnp.asarray(X), cfg_for(bucket_cap=asked))
        live = np.asarray(idx.bucket_ids) >= 0
        assert int(live.sum()) == 1024 and live.sum(axis=1).max() < asked
        shapes.add(idx.buckets.shape)
    assert shapes == {(8, 400, 16)}  # padded to a multiple of 8


def test_a_partition_that_outgrows_the_stated_cap_raises_it():
    X = whole_rows(5, m=1024, d=16)
    # the mean itself: two training rounds split one list each, no more
    tight = build_ivf_index(jnp.asarray(X), cfg_for(bucket_cap=128))
    live = np.asarray(tight.bucket_ids) >= 0
    assert tight.bucket_cap > 128 and tight.bucket_cap % 8 == 0
    assert tight.bucket_cap - 8 < live.sum(axis=1).max() <= tight.bucket_cap
    assert sorted(np.asarray(tight.bucket_ids)[live]) == list(range(1024))
    with pytest.raises(ValueError, match="bucket_cap"):
        KNNConfig(partitions=8, bucket_cap=0)
