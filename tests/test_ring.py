"""Ring backends vs serial backend on 8 virtual CPU devices — the
distributed-without-a-cluster strategy from SURVEY.md §4. Property: ring
output == serial output for any (m, k, P) — the property the reference's
buggy rotation violated (SURVEY.md Q1)."""

import jax
import numpy as np
import pytest

from mpi_knn_tpu import all_knn
from mpi_knn_tpu.parallel.mesh import make_ring_mesh
from tests.oracle import int_sq_l2, oracle_all_knn, recall_against_oracle


def _data(rng, m=96, d=12):
    return (rng.standard_normal((m, d)) * 3).astype(np.float32)


def _as_sets(ids):
    return [set(r.tolist()) for r in np.asarray(ids)]


@pytest.mark.parametrize("backend", ["ring", "ring-overlap"])
def test_ring_equals_serial_all_pairs(rng, backend):
    X = _data(rng, m=96)
    serial = all_knn(X, k=7, backend="serial", query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=7, backend=backend)
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


@pytest.mark.parametrize("schedule", ["stream", "twolevel"])
def test_ring_merge_schedule_parity(rng, schedule):
    """The per-round block merge honors cfg.merge_schedule inside the ring
    (shared merge_tiles_into_carry) — both schedules must equal serial, with
    the block split across multiple on-device tiles so level 1 really runs
    per tile."""
    X = _data(rng, m=128)
    serial = all_knn(X, k=6, backend="serial", query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=6, backend="ring", query_tile=8, corpus_tile=8,
                   merge_schedule=schedule)
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_ring_bf16_transfer_exact_on_integer_data(rng):
    """ring_transfer_dtype='bfloat16' halves the bytes per ppermute; on
    integer-valued data (raw pixels <= 255 are bf16-exact) the results must
    equal serial EXACTLY. center off so values stay integral."""
    X = np.rint(rng.random((96, 24)) * 255.0).astype(np.float32)
    serial = all_knn(X, k=5, backend="serial", center=False, zero_eps=0.5,
                     query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=5, backend="ring", center=False, zero_eps=0.5,
                   ring_transfer_dtype="bfloat16")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-6
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_ring_bf16_transfer_recall_on_float_data(rng):
    """On non-integer data the one-time bf16 cast of the rotating block may
    flip near-ties; id-set recall vs serial is the contract (>= 0.99 on
    well-separated blobs)."""
    from mpi_knn_tpu.utils.report import recall_at_k

    X = _data(rng, m=128)
    serial = all_knn(X, k=6, backend="serial", query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=6, backend="ring-overlap",
                   ring_transfer_dtype="bfloat16")
    rec = recall_at_k(np.asarray(ring.ids), np.asarray(serial.ids))
    assert rec >= 0.99, rec


@pytest.mark.parametrize("backend", ["ring", "ring-overlap"])
def test_ring_non_divisible_m(rng, backend):
    """m=101 is not divisible by P=8 — the reference silently corrupted here
    (SURVEY.md Q6); we pad and mask."""
    X = _data(rng, m=101)
    serial = all_knn(X, k=5, backend="serial", query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=5, backend=backend)
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_ring_query_mode(rng):
    X = _data(rng, m=80)
    Q = _data(rng, m=37)
    serial = all_knn(X, queries=Q, k=6, backend="serial", query_tile=16, corpus_tile=16)
    ring = all_knn(X, queries=Q, k=6, backend="ring-overlap")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_ring_cosine(rng):
    X = _data(rng, m=64)
    serial = all_knn(X, k=4, backend="serial", metric="cosine", query_tile=16, corpus_tile=16)
    ring = all_knn(X, k=4, backend="ring", metric="cosine")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )


def test_ring_explicit_small_mesh(rng):
    """Ring over a 4-device sub-mesh via explicit mesh argument."""
    X = _data(rng, m=64)
    mesh = make_ring_mesh(4)
    serial = all_knn(X, k=5, backend="serial", query_tile=16, corpus_tile=16)
    ring = all_knn(X, k=5, backend="ring-overlap", mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )


def test_ring_k_spans_blocks(rng):
    """k larger than any single shard (12 per device at m=96/P=8) forces the
    cross-round merge to actually carry state between rotations."""
    X = _data(rng, m=96)
    serial = all_knn(X, k=20, backend="serial", query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=20, backend="ring-overlap")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_auto_backend_resolves_on_multi_device():
    """The package docstring's own example must work on a multi-device host
    (auto -> ring-overlap)."""
    X = np.random.default_rng(3).standard_normal((40, 8)).astype(np.float32)
    res = all_knn(X, k=3)
    assert res.ids.shape == (40, 3)


def test_output_sharding_follows_ring(rng):
    """The result must stay sharded over the ring axis (no hidden all-gather
    inside the backend) — device memory for the output scales as q/P."""
    from jax.sharding import PartitionSpec

    X = _data(rng, m=96)
    ring = all_knn(X, k=4, backend="ring-overlap")
    assert len(jax.devices()) == 8
    assert ring.dists.shape == (96, 4)
    spec = ring.dists.sharding.spec
    assert spec[0] == "ring", f"expected query axis sharded over ring, got {spec}"


@pytest.mark.parametrize("p", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("backend", ["ring", "ring-overlap"])
def test_bidir_bit_identical_to_serial_every_p(rng, p, backend):
    """The tentpole property of the full-duplex schedule: at EVERY ring
    size — including the degenerate P=1, the all-rounds-degenerate P=2, an
    odd P, and even Ps with a real antipodal round — bidir results are
    bit-identical to serial AND to the uni ring (tiles pinned equal on both
    sides so the per-pair distance kernels match shape-for-shape)."""
    X = _data(rng, m=96)
    mesh = make_ring_mesh(p)
    serial = all_knn(X, k=7, backend="serial", query_tile=4, corpus_tile=4)
    uni = all_knn(X, k=7, backend=backend, mesh=mesh,
                  query_tile=4, corpus_tile=4)
    bidir = all_knn(X, k=7, backend=backend, mesh=mesh,
                    query_tile=4, corpus_tile=4, ring_schedule="bidir")
    np.testing.assert_array_equal(
        np.asarray(bidir.ids), np.asarray(serial.ids)
    )
    np.testing.assert_array_equal(
        np.asarray(bidir.dists), np.asarray(serial.dists)
    )
    np.testing.assert_array_equal(
        np.asarray(bidir.dists), np.asarray(uni.dists)
    )


@pytest.mark.parametrize("p", [1, 2, 3, 4, 8])
def test_bidir_mixed_precision_bit_identical_every_p(rng, p):
    """bidir × precision_policy='mixed': the compress-and-rerank pipeline
    lives inside the shared per-tile reduction, so the schedule change must
    not perturb it — bit-identity to the mixed serial backend at every P
    (c_tile=16 > 4k=12 so the two-pass pipeline actually runs)."""
    X = _data(rng, m=128, d=16)
    cfg_kw = dict(k=3, query_tile=8, corpus_tile=16,
                  precision_policy="mixed")
    serial = all_knn(X, backend="serial", **cfg_kw)
    bidir = all_knn(X, backend="ring-overlap", mesh=make_ring_mesh(p),
                    ring_schedule="bidir", **cfg_kw)
    np.testing.assert_array_equal(
        np.asarray(bidir.ids), np.asarray(serial.ids)
    )
    np.testing.assert_array_equal(
        np.asarray(bidir.dists), np.asarray(serial.dists)
    )


def test_bidir_bf16_transfer_exact_on_integer_data(rng):
    """ring_transfer_dtype composes with bidir: BOTH travelers circulate at
    the transfer dtype (cast once, upcast per merge), so integer-valued
    data stays exactly equal to serial."""
    X = np.rint(rng.random((96, 24)) * 255.0).astype(np.float32)
    serial = all_knn(X, k=5, backend="serial", center=False, zero_eps=0.5,
                     query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=5, backend="ring", center=False, zero_eps=0.5,
                   ring_transfer_dtype="bfloat16", ring_schedule="bidir")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-6
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_bidir_non_divisible_m(rng):
    """Padding + masking under the two-traveler rotation (m=101, P=8)."""
    X = _data(rng, m=101)
    serial = all_knn(X, k=5, backend="serial", query_tile=32, corpus_tile=32)
    ring = all_knn(X, k=5, backend="ring-overlap", ring_schedule="bidir")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_bidir_query_mode(rng):
    X = _data(rng, m=80)
    Q = _data(rng, m=37)
    serial = all_knn(X, queries=Q, k=6, backend="serial",
                     query_tile=16, corpus_tile=16)
    ring = all_knn(X, queries=Q, k=6, backend="ring-overlap",
                   ring_schedule="bidir")
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


def test_ring_respects_tiling(rng):
    """Tiny tiles force the per-device nested tiling path; results unchanged."""
    X = _data(rng, m=96)
    serial = all_knn(X, k=5, backend="serial", query_tile=16, corpus_tile=16)
    ring = all_knn(X, k=5, backend="ring-overlap", query_tile=4, corpus_tile=4)
    np.testing.assert_allclose(
        np.asarray(ring.dists), np.asarray(serial.dists), rtol=1e-5, atol=1e-5
    )
    assert _as_sets(ring.ids) == _as_sets(serial.ids)


@pytest.mark.parametrize("schedule", ["uni", "bidir"])
@pytest.mark.parametrize("backend", ["ring", "ring-overlap"])
def test_ring_takes_one_pass_on_whole_number_rows_and_agrees_with_serial(
        rng, backend, schedule):
    """PR 29: the corpus side of the one-pass rule is reduced across the
    ring in the centring pass, each chip's query tiles decide their own
    side, and the answer is the serial backend's and an int64
    computation's, exactly. 1024-row query tiles: ``ONEPASS_MIN_ROWS``."""
    import jax.numpy as jnp

    X = rng.integers(0, 256, (4096, 16)).astype(np.float32)
    kw = dict(k=7, query_tile=1024, corpus_tile=128, matmul_precision="high")
    serial = all_knn(X, backend="serial", **kw)
    for corpus in (X, jnp.asarray(X)):
        ring = all_knn(corpus, backend=backend, num_devices=4,
                       ring_schedule=schedule, **kw)
        # one row of counts a device
        assert np.asarray(ring.dist_steps).tolist() == [[32, 0]] * 4
        np.testing.assert_array_equal(
            np.asarray(ring.dists), np.asarray(serial.dists))
    # (whole-number distances tie, and ids may differ among equal ones)
    d = int_sq_l2(X, X)
    np.fill_diagonal(d, np.iinfo(np.int64).max)
    d[d == 0] = np.iinfo(np.int64).max  # duplicates are excluded by value
    np.testing.assert_array_equal(
        np.asarray(serial.dists), np.sort(d, axis=1)[:, :7].astype(np.float32))
    # a wire that narrows the block keeps the program it always ran
    narrow = all_knn(jnp.asarray(X), backend=backend, num_devices=4,
                     ring_schedule=schedule, ring_transfer_dtype="bfloat16",
                     **kw)
    assert np.asarray(narrow.dist_steps).tolist() == [0, 4 * 32]


@pytest.mark.parametrize("schedule", ["uni", "bidir"])
def test_dp_by_ring_mesh_takes_one_pass_on_whole_number_rows(
        rng, schedule):
    """The corpus fact is replicated over BOTH mesh axes, each device's
    query tiles decide their own side (2 x 4 devices, 1024-row tiles)."""
    import jax.numpy as jnp

    from mpi_knn_tpu.parallel.mesh import make_mesh2d

    X = rng.integers(0, 256, (8192, 16)).astype(np.float32)
    kw = dict(k=7, query_tile=1024, corpus_tile=128, matmul_precision="high")
    serial = all_knn(X, backend="serial", **kw)
    ring = all_knn(jnp.asarray(X), backend="ring-overlap",
                   mesh=make_mesh2d(2, 4), ring_schedule=schedule, **kw)
    assert np.asarray(ring.dist_steps).tolist() == [[64, 0]] * 8
    np.testing.assert_array_equal(
        np.asarray(ring.dists), np.asarray(serial.dists))


# every (policy, wire) pair the config admits: the int8 wire needs the
# rerank of ``mixed`` (config.py refuses it under ``exact``)
_POLICY_WIRE = [
    ("exact", None),
    ("exact", "bfloat16"),
    ("mixed", None),
    ("mixed", "int8"),
]


@pytest.mark.parametrize("p", [1, 2, 4, 8])
@pytest.mark.parametrize("schedule", ["uni", "bidir"])
@pytest.mark.parametrize("policy,wire", _POLICY_WIRE)
def test_xla_ring_matrix_agrees_with_serial(policy, wire, schedule, p):
    """Every schedule x policy x wire the config admits, at every ring
    size, against the serial backend under the same policy. Over a float
    wire on whole-number rows (centred they are bf16 numbers: the bf16
    wire and the compress pass of ``mixed`` round nothing) ids and
    distances are serial's bit for bit; the int8 wire rounds, and is held
    to ``tests/test_quant.py``'s gate against the float64 oracle."""
    rng = np.random.default_rng(11)
    kw = dict(backend="ring-overlap", num_devices=p, ring_schedule=schedule,
              precision_policy=policy, ring_transfer_dtype=wire)
    if wire == "int8":
        X = np.rint(rng.random((512, 96)) * 255.0).astype(np.float32)
        got = all_knn(X, k=10, query_tile=64, corpus_tile=128, **kw)
        want_d, want_i = oracle_all_knn(X, k=10)
        rec = recall_against_oracle(got.ids, want_d, want_i, 10)
        assert rec >= 0.99, rec
        return
    X = rng.integers(0, 256, (96, 24)).astype(np.float32)
    # no two of a row's nearest k + 1 tie: the ids have one right order
    d2 = int_sq_l2(X, X)
    np.fill_diagonal(d2, np.iinfo(d2.dtype).max)
    assert (np.diff(np.sort(d2, axis=1)[:, :6], axis=1) > 0).all()
    tiles = dict(k=5, query_tile=8, corpus_tile=16)
    serial = all_knn(X, backend="serial", precision_policy=policy, **tiles)
    ring = all_knn(X, **tiles, **kw)
    np.testing.assert_array_equal(np.asarray(ring.ids), np.asarray(serial.ids))
    np.testing.assert_array_equal(
        np.asarray(ring.dists), np.asarray(serial.dists))
    np.testing.assert_array_equal(
        np.asarray(ring.dists), np.sort(d2, axis=1)[:, :5])
