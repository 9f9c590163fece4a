"""The corpus ring as the deployment ``mnist8m-784-l2-ring4`` runs it, at a
CPU mesh's size: corpus tiles wide enough (1024) that ``lane_bin_depth``
engages inside the ring's ``shard_map``, a corpus that already lies on the
ring's sharding, the sharded plain reference, and the ring's span and
counters. (On the CPU the engaged selection under the checked ``shard_map``
takes the full-width path, ``ops/topk.py``; ``tests/test_pallas.py``
compiles the kernels inside the ring's program for the v5e.)"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference, reference_sharded
from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.backends import ring, serial
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


# ---------------------------------------------------------------------------
# the ring's rounds with the kernel that walks the arriving stack (ISSUE 43).
# On the chip the XLA ring's checked ``shard_map`` holds it (``tests/
# test_pallas.py -k ring_program`` compiles that for four v5e); jax's Pallas
# interpreter cannot run under the check, so here the rotation runs under an
# UNCHECKED ``shard_map`` on the CPU mesh of four, where the rule sees
# operands that vary over nothing and engages as it does unsharded

RING_Q = RING_C = 1024  # the heights from which the rule's program engages
RING_TILES, RING_DIM = 2, 16  # corpus tiles a device


def _rotation(kernel: bool, Q, q_ids, X, ids, carry=()):
    """A whole rotation of ``ring._ring_knn_local`` over four CPU devices
    under a true corpus fact, its one-pass branch the kernel (interpreted)
    or, the rule answering None, the scan of tile steps it replaces:
    ``(dists, ids, TileCounts)`` as numpy."""
    cfg = KNNConfig(k=K, backend="ring-overlap", num_devices=DEVICES,
                    query_tile=RING_Q, corpus_tile=RING_C,
                    matmul_precision="high")
    mesh = make_ring_mesh(DEVICES, axis_name=cfg.mesh_axis)
    by_rows = P(cfg.mesh_axis)

    def local(q, qi, c, ci, one, *carry):
        return ring._ring_knn_local(
            q, qi, c, ci, cfg=cfg, overlap=True, axis=cfg.mesh_axis,
            q_tile=RING_Q, c_tile=RING_C, vary_axes=(cfg.mesh_axis,),
            onepass=one, carry_in=carry or None)

    with pytest.MonkeyPatch.context() as patch:
        if not kernel:
            for module in (serial, ring):
                patch.setattr(module, "fused_rule", lambda *a, **k: None)
        out = jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(by_rows,) * 4 + (P(),) + (by_rows,) * len(carry),
            out_specs=(by_rows,) * 3, check_vma=False,
        ))(Q, q_ids, X, ids, jnp.asarray(True), *carry)
    return jax.tree.map(np.asarray, out)


def _ring_case(what: str):
    """Whole-number rows over four devices (two tiles each) and queries
    that are corpus rows under their own ids — every device's queries have
    their own rows, and their duplicates, in OTHER devices' blocks, so the
    self mask works on ids that arrive with a later round — with what the
    case names laid on top."""
    rng = np.random.default_rng(43)
    m = DEVICES * RING_TILES * RING_C
    X = rng.integers(-6, 7, (m, RING_DIM)).astype(np.float32)
    X[RING_C:2 * RING_C] += 16.0  # a tile far off: the bound skips chunks
    ids = np.arange(m, dtype=np.int32)
    q_ids = rng.permutation(m)[:DEVICES * RING_Q].astype(np.int32)
    Q = X[q_ids].copy()
    X[q_ids[:64] ^ 1] = Q[:64]  # duplicates under a neighbour's id
    carry = ()
    if what == "padded":  # the last device's last tile is mostly padding
        X[-700:], ids[-700:] = 0.0, -1
    elif what == "nan":  # corpus rows that are at no distance from any query
        X[777], X[5000, 2] = np.nan, np.nan
    elif what == "carry_in":  # a carry that another corpus's rotation left
        carry = (np.sort(rng.integers(60, 260, (len(Q), K)), axis=1).astype(
            np.float32), rng.integers(10**6, 2 * 10**6, (len(Q), K)).astype(
            np.int32))
    elif what == "fractional":  # the second device's tile is no bf16 number
        Q[RING_Q + 7, 0] += 2.0 ** -10
    return Q, q_ids, X, ids, carry


@pytest.mark.parametrize("what", [
    "self_across_shards", "padded", "nan", "carry_in", "fractional"])
def test_a_round_with_the_kernel_equals_the_scan_it_replaces(what):
    """The rotation's answer, its re-scanned merges and its chunk count
    with the kernel in the rounds' one-pass branch are the scan's, bit for
    bit (ids and tie order too); the tile steps move from the one-pass
    column to the fused one, a device's row each."""
    case = _ring_case(what)
    scan_d, scan_i, scan_n = _rotation(False, *case)
    d, i, n = _rotation(True, *case)
    np.testing.assert_array_equal(d, scan_d)
    np.testing.assert_array_equal(i, scan_i)
    np.testing.assert_array_equal(n.select_tiles, scan_n.select_tiles)
    steps = DEVICES * RING_TILES  # a device's query tile meets every tile
    multi = [steps * (what == "fractional" and dev == 1)
             for dev in range(DEVICES)]
    assert scan_n.dist_steps.tolist() == [[steps - s, s] for s in multi]
    assert n.dist_steps.tolist() == [[0, s, 0, steps - s] for s in multi]
    # rounds x tiles x chunks a device, by what became of them
    assert n.bins_chunks.shape == (DEVICES, 2)
    assert (n.bins_chunks.sum(axis=1) == steps * (RING_Q // 16)).all()
    np.testing.assert_array_equal(n.bins_chunks, scan_n.bins_chunks)
    assert (n.bins_chunks[:, 1] > 0).all()  # the far tile's chunks
    assert (i[np.isfinite(d)] >= 0).all() and (d[:, :-1] <= d[:, 1:])[
        np.isfinite(d[:, 1:])].all()
    own = i == np.asarray(case[1])[:, None]
    assert not own.any() and (d[np.isfinite(d)] > 0).all()
    if what == "nan":  # (a query tile that holds one is no bf16 number)
        assert not np.isin(i, (777, 5000)).any()
    if what == "carry_in":  # some of the other corpus's rows survive
        assert (i >= 10**6).any() and (i < 10**6).any()


@pytest.fixture
def unchecked_ring(monkeypatch):
    """``all_knn``'s ring under an unchecked ``shard_map``: what the rule
    sees on the chip under the checked one, where the CPU can run it."""
    monkeypatch.setattr(
        jax, "shard_map", functools.partial(jax.shard_map, check_vma=False))
    ring._ring_knn_sharded.clear_cache()
    yield
    ring._ring_knn_sharded.clear_cache()


@pytest.mark.parametrize("backend", ["ring-overlap", "ring"])
def test_a_ring_call_counts_its_fused_steps_a_device(
        corpus, backend, unchecked_ring):
    """``KNNResult.dist_steps`` of a ring call is one row a device:
    ``[0, multi-pass, 0, fused]`` where the rule engaged, ``[one-pass,
    multi-pass]`` where it did not (a bf16 wire keeps the program it
    had); ``bins_chunks`` adds up to rounds x tiles x chunks a device;
    the registry takes both forms."""
    x = np.concatenate([corpus[:, :16]] * 2)  # 8192 rows: two tiles a device
    kw = dict(k=K, backend=backend, num_devices=DEVICES, query_tile=RING_Q,
              corpus_tile=RING_C, matmul_precision="high")
    steps = DEVICES * RING_TILES
    got = all_knn(jnp.asarray(x), queries=x[:DEVICES * RING_Q], **kw)
    assert np.asarray(got.dist_steps).tolist() == [[0, 0, 0, steps]] * DEVICES
    chunks = np.asarray(got.bins_chunks)
    assert chunks.shape == (DEVICES, 2)
    assert (chunks.sum(axis=1) == steps * (RING_Q // 16)).all()
    assert np.asarray(got.select_tiles).sum(axis=1).tolist() == [
        DEVICES] * DEVICES  # a merge a round
    # a fractional query tile on one device: its steps are multi-pass
    frac = x[:DEVICES * RING_Q].copy()
    frac[2 * RING_Q + 1, 0] += 0.5
    mixed = all_knn(jnp.asarray(x), queries=frac, **kw)
    assert np.asarray(mixed.dist_steps).tolist() == [
        [0, steps * (dev == 2), 0, steps * (dev != 2)]
        for dev in range(DEVICES)]
    serial_res = all_knn(x, queries=frac, **{**kw, "backend": "serial"})
    np.testing.assert_array_equal(
        np.asarray(mixed.dists), np.asarray(serial_res.dists))
    # no fact travels with a narrowed wire: the two-column form, one row
    narrow = all_knn(jnp.asarray(x), queries=x[:DEVICES * RING_Q],
                     ring_transfer_dtype="bfloat16", **kw)
    assert np.asarray(narrow.dist_steps).tolist() == [0, DEVICES * steps]
    registry = obs_metrics.MetricsRegistry()
    for res in (got, mixed, narrow):
        registry.count_dist_steps(res.dist_steps)
    registry.count_bins_chunks(got.bins_chunks)
    counted = {p: registry.counter(
        obs_metrics.DIST_STEPS, labels={"path": p}).value
        for p in obs_metrics.DIST_PATHS}
    assert counted == {"onepass": 0, "multipass": steps + DEVICES * steps,
                       "cosine": 0, "fused": (2 * DEVICES - 1) * steps,
                       "ip": 0, "u8": 0, "fused_screen": 0}
    assert sum(registry.counter(
        obs_metrics.BINS_CHUNKS, labels={"path": p}).value
        for p in obs_metrics.BINS_PATHS) == chunks.sum()


def test_a_checked_ring_on_the_cpu_keeps_the_two_column_form(corpus):
    """Off the TPU the checked ``shard_map`` keeps the per-tile program
    (the interpreter cannot run under the check): one row a device,
    ``[one-pass, multi-pass]``, and no chunk count."""
    x = np.concatenate([corpus[:, :16]] * 2)
    got = all_knn(jnp.asarray(x), queries=x[:DEVICES * RING_Q], k=K,
                  backend="ring-overlap", num_devices=DEVICES,
                  query_tile=RING_Q, corpus_tile=RING_C,
                  matmul_precision="high")
    steps = DEVICES * RING_TILES
    assert np.asarray(got.dist_steps).tolist() == [[steps, 0]] * DEVICES
    assert got.bins_chunks is None
    registry = obs_metrics.MetricsRegistry()
    registry.count_dist_steps(got.dist_steps)
    assert registry.counter(obs_metrics.DIST_STEPS,
                            labels={"path": "onepass"}).value == (
        DEVICES * steps)
