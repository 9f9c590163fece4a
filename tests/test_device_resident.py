"""Device-resident input paths and honest timing helpers.

The bench methodology requires that a corpus already living on device is
never bounced through the host (SURVEY.md §6 tracing row: naive timing of
async dispatch would lie; naive np.asarray of device inputs would measure
transfers). These tests pin the parity and the padding/cap helpers behind
that path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.parallel.partition import pad_rows_any
from mpi_knn_tpu.utils.timing import device_sync


def _data(rng, m=96, d=16):
    return rng.standard_normal((m, d)).astype(np.float32)


@pytest.mark.parametrize("backend", ["serial", "ring-overlap"])
def test_device_resident_matches_host(rng, backend):
    """jax.Array inputs give bit-identical neighbors to numpy inputs."""
    X = _data(rng)
    cfg = KNNConfig(k=5, backend=backend, query_tile=16, corpus_tile=32)
    host = all_knn(X, config=cfg)
    dev = all_knn(jax.device_put(jnp.asarray(X)), config=cfg)
    np.testing.assert_array_equal(np.asarray(host.ids), np.asarray(dev.ids))
    np.testing.assert_allclose(
        np.asarray(host.dists), np.asarray(dev.dists), rtol=1e-6
    )


def test_device_resident_query_mode(rng):
    X, Q = _data(rng), _data(rng, m=24)
    cfg = KNNConfig(k=4, backend="serial", query_tile=8, corpus_tile=32)
    host = all_knn(X, queries=Q, config=cfg)
    dev = all_knn(
        jax.device_put(jnp.asarray(X)),
        queries=jax.device_put(jnp.asarray(Q)),
        config=cfg,
    )
    np.testing.assert_array_equal(np.asarray(host.ids), np.asarray(dev.ids))


def test_pad_rows_any_device_and_host(rng):
    x = rng.standard_normal((10, 4)).astype(np.float32)
    out_h = pad_rows_any(x, 16, fill=0.0, dtype=jnp.float32)
    out_d = pad_rows_any(jax.device_put(jnp.asarray(x)), 16)
    assert out_h.shape == out_d.shape == (16, 4)
    np.testing.assert_array_equal(np.asarray(out_h), np.asarray(out_d))
    # fill value respected for int ids (padding must be -1, not 0)
    ids = jnp.arange(10, dtype=jnp.int32)
    padded = pad_rows_any(ids, 16, fill=-1, dtype=jnp.int32)
    assert np.asarray(padded)[10:].tolist() == [-1] * 6
    with pytest.raises(ValueError):
        pad_rows_any(ids, 4)


def test_effective_tiles_caps_product():
    from mpi_knn_tpu.backends.serial import cap_corpus_tile, effective_tiles

    cfg = KNNConfig(
        k=10, query_tile=4096, corpus_tile=1 << 20, max_tile_elems=1 << 28
    )
    # "whole corpus per tile" at SIFT1M scale must be clamped: the distance
    # block materialized per step is q_tile × c_tile elements
    q_tile, c_tile = effective_tiles(cfg, m=1_000_000, nq=1_000_000)
    assert q_tile * c_tile <= cfg.max_tile_elems
    assert c_tile % 128 == 0 and c_tile >= 128
    # small problems are still clamped to the problem size, not the cap
    q_tile, c_tile = effective_tiles(cfg, m=1000, nq=1000)
    assert c_tile <= 1024 + 128
    # the cap is HARD even when the 128-alignment floor can't hold
    assert cap_corpus_tile(8, 1024, 64) * 8 <= 64
    assert cap_corpus_tile(1, 1 << 20, 1 << 10) == 1 << 10
    # alignment kept when the cap allows it
    assert cap_corpus_tile(1000, 1 << 20, 1 << 28) % 128 == 0


def test_ring_tile_cap_runs(rng):
    """Ring backend respects max_tile_elems: the cap genuinely shrinks
    c_tile (16 -> 8 here) and results still match serial."""
    X = _data(rng, m=128, d=8)
    cfg = KNNConfig(
        k=3, backend="ring", query_tile=8, corpus_tile=16, max_tile_elems=64
    )
    want = all_knn(X, config=cfg.replace(backend="serial"))
    got = all_knn(X, config=cfg)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))


def test_device_sync_pytree_and_sharded(rng):
    """device_sync accepts pytrees and sharded arrays without error."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    x = jnp.arange(16.0)
    device_sync(x, {"a": x * 2, "b": (x, None, 3)})
    mesh = make_ring_mesh(8)
    xs = jax.device_put(x, NamedSharding(mesh, P(mesh.axis_names[0])))
    device_sync(xs)


def test_sift_like_integer_valued():
    from mpi_knn_tpu.data.synthetic import make_sift_like

    X = make_sift_like(m=100, d=8)
    np.testing.assert_array_equal(X, np.rint(X))
