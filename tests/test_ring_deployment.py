"""The corpus ring as the deployment ``mnist8m-784-l2-ring4`` runs it, at a
CPU mesh's size: corpus tiles wide enough (1024) that ``lane_bin_depth``
engages inside the ring's ``shard_map``, a corpus that already lies on the
ring's sharding, the sharded plain reference, and the ring's span and
counters. (On the CPU the engaged selection under the checked ``shard_map``
takes the full-width path, ``ops/topk.py``; ``tests/test_pallas.py``
compiles the kernels inside the ring's program for the v5e.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference, reference_sharded
from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.backends import ring
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.topk import lane_bin_depth
from mpi_knn_tpu.parallel.mesh import make_ring_mesh
from mpi_knn_tpu.parallel.partition import make_global_ids

M, DIM, K, DEVICES = 4096, 24, 10, 4
Q_TILE, C_TILE = 64, 1024


@pytest.fixture(scope="module")
def corpus():
    """Whole-number rows in [0, 255] around a few centres (the benchmark's
    data at a small size): float32 sums of squares are exact, so every
    exact method returns the same distances."""
    rng = np.random.default_rng(7)
    cen = rng.random((6, DIM)) * 255.0
    x = cen[rng.integers(0, 6, M)] + rng.standard_normal((M, DIM)) * 25.0
    return np.clip(np.rint(x), 0.0, 255.0).astype(np.float32)


def config(backend: str, **kw) -> KNNConfig:
    return KNNConfig(
        k=K, backend=backend, num_devices=DEVICES, query_tile=Q_TILE,
        corpus_tile=C_TILE, matmul_precision="highest", **kw)


def same_neighbours(got, want_d, want_i, rtol=2e-5):
    """Distances equal to the matmul form's rounding (the centred rows are
    not whole numbers); ids equal wherever the reference's distance is not
    within that of a neighbouring slot's, where either order is right."""
    d, i = np.asarray(got.dists), np.asarray(got.ids)
    np.testing.assert_allclose(d, want_d, rtol=rtol)
    near = np.zeros(want_d.shape, bool)
    close = np.abs(np.diff(want_d, axis=1)) <= 4 * rtol * want_d[:, 1:]
    near[:, 1:] |= close
    near[:, :-1] |= close
    near[:, -1] = True  # the k-th may tie with the first one left out
    assert (i == want_i)[~near].all()
    assert (i == want_i).mean() > 0.98


@pytest.mark.parametrize("sliced", [False, True], ids=["all-pairs", "slice"])
@pytest.mark.parametrize("backend", ["ring-overlap", "ring"])
def test_ring_with_engaging_tiles_equals_reference_and_serial(
        corpus, backend, sliced):
    assert lane_bin_depth(Q_TILE, C_TILE, K) is not None
    lo, n = 1536, 512
    kw = {}
    if sliced:
        kw = dict(queries=corpus[lo:lo + n],
                  query_ids=np.arange(lo, lo + n, dtype=np.int32))
    got = all_knn(corpus, config=config(backend), **kw)
    serial = all_knn(corpus, config=config("serial"), **kw)
    ids = np.arange(lo, lo + n) if sliced else np.arange(M)
    ref_d, ref_i = reference.exact_knn(
        jnp.asarray(corpus), corpus[ids], K, self_ids=ids.astype(np.int32))
    same_neighbours(got, ref_d, ref_i)
    same_neighbours(got, np.asarray(serial.dists), np.asarray(serial.ids))


def buffers(arr) -> list:
    return [s.data.unsafe_buffer_pointer() for s in arr.addressable_shards]


@pytest.mark.parametrize("center", [True, False])
def test_corpus_on_the_rings_sharding_is_neither_gathered_nor_placed_again(
        corpus, center, monkeypatch):
    cfg = config("ring-overlap", center=center)
    mesh = make_ring_mesh(DEVICES, axis_name=cfg.mesh_axis)
    by_rows = NamedSharding(mesh, P(cfg.mesh_axis))
    x = jax.device_put(corpus, by_rows)
    lo, n = 2048, 256
    queries = jax.device_put(corpus[lo:lo + n], by_rows)
    seen = {}
    inner = ring._ring_knn_sharded

    def spy(queries_p, qids_p, corpus_p, corpus_ids, *a, **kw):
        seen.update(corpus=corpus_p, ids=corpus_ids, queries=queries_p)
        return inner(queries_p, qids_p, corpus_p, corpus_ids, *a, **kw)

    want = all_knn(corpus, queries=corpus[lo:lo + n], config=cfg,
                   query_ids=np.arange(lo, lo + n, dtype=np.int32))
    monkeypatch.setattr(ring, "_ring_knn_sharded", spy)
    got = all_knn(x, queries=queries, config=cfg,
                  query_ids=np.arange(lo, lo + n, dtype=np.int32))
    same_neighbours(got, np.asarray(want.dists), np.asarray(want.ids))
    for name in ("corpus", "ids", "queries"):
        arr = seen[name]
        assert arr.sharding.is_equivalent_to(by_rows, arr.ndim), name
        assert {s.data.shape[0] for s in arr.addressable_shards} == {
            arr.shape[0] // DEVICES}, name  # a quarter a device: no gather
    if not center:  # the caller's own buffers reach the program: no copy
        assert buffers(seen["corpus"]) == buffers(x)
        assert buffers(seen["queries"]) == buffers(queries)
    # the id row is made where its shards live, padding rows invalid
    np.testing.assert_array_equal(np.asarray(seen["ids"]), np.arange(M))
    padded = ring._global_ids_on(by_rows, M - 3, M)
    assert padded.sharding.is_equivalent_to(by_rows, 1)
    np.testing.assert_array_equal(
        np.asarray(padded), make_global_ids(M - 3, M))


def test_sharded_reference_equals_the_reference_on_the_gathered_array(corpus):
    # planted ties: copies of one row in different shards, and a pair of
    # rows at one distance from a probe, the lower id in the later shard
    x = corpus.copy()
    x[3000] = x[100]
    x[3500] = x[100]
    x[200] = x[101]
    x[200, 0] += 3.0
    x[3900] = x[101]
    x[3900, 1] += 3.0
    mesh = make_ring_mesh(DEVICES)
    xs = jax.device_put(x, NamedSharding(mesh, P("ring")))
    probes = np.array([100, 101, 5, 3000, 4095, 2047, 2048], np.int32)
    assert [lo for lo, _ in reference_sharded.row_shards(xs)] == [
        0, 1024, 2048, 3072]
    rows = reference_sharded.take_rows(xs, probes)
    np.testing.assert_array_equal(rows, x[probes])
    for exclude_zero in (True, False):
        want_d, want_i = reference.exact_knn(
            jnp.asarray(x), x[probes], K, self_ids=probes,
            exclude_zero=exclude_zero)
        got_d, got_i = reference_sharded.exact_knn(
            xs, rows, K, self_ids=probes, exclude_zero=exclude_zero)
        np.testing.assert_array_equal(got_d, want_d)
        np.testing.assert_array_equal(got_i, want_i)
    # ties across shards by the lower id
    d, i = reference_sharded.merge_smallest(
        np.array([[2.0, 1.0, 1.0, 3.0]]), np.array([[9, 7, 4, 1]]), 3)
    assert d.tolist() == [[1.0, 1.0, 2.0]] and i.tolist() == [[4, 7, 9]]


@pytest.mark.parametrize("schedule, rounds", [("uni", 4), ("bidir", 3)])
def test_ring_span_and_counters_move_by_the_layouts_numbers(
        corpus, schedule, rounds, tmp_path):
    cfg = config("ring-overlap", ring_schedule=schedule)
    reg = obs_metrics.get_registry()
    names = ("ring_calls_total", "ring_rounds_total", "ring_wire_bytes_total")
    before = [reg.counter(n).value for n in names]
    rec = obs_spans.FlightRecorder(str(tmp_path / "flight.jsonl"))
    obs_spans.set_recorder(rec)
    try:
        all_knn(corpus, queries=corpus[:256], config=cfg)
    finally:
        obs_spans.set_recorder(None)
        rec.close()
    moved = [reg.counter(n).value - b for n, b in zip(names, before)]
    wire = ring.ring_wire_bytes_per_batch(cfg, M, DIM, DEVICES)
    assert moved == [1, rounds, wire] and wire > 0
    spans, _ = obs_spans.reconstruct_spans(
        obs_spans.read_flight(str(tmp_path / "flight.jsonl")))
    by_name = {(s["cat"], s["name"]): s for s in spans}
    call, api = by_name[("ring", "call")], by_name[("api", "all_knn")]
    assert call["parent"] == api["span"]
    assert call["attrs"] == {"devices": DEVICES, "rounds": rounds,
                             "rows_per_block": M // DEVICES,
                             "wire_bytes": wire}
