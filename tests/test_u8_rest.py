"""``dtype="uint8"``: the dense serial stack at one byte an element (ISSUE
48) — a lossless at-rest form for whole-number rows in [0, 255]. A byte
index answers what the float32 index of the same rows answers, to the bit;
a block-fed build equals the one-array build, planes included; what cannot
be held losslessly, or combined, is refused with its reason."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.serve import build_index, build_index_blocks, query_knn


def _cfg(k=10, q_tile=1024, **kw):
    return KNNConfig(k=k, backend="serial", corpus_tile=1024,
                     query_tile=q_tile, query_bucket=64, exclude_self=False,
                     **kw)


def _rows(seed, m, d, lo=0, hi=256):
    """Whole-number rows around a few centres (pixel- or descriptor-like),
    so that neighbours are near and ties and zeros occur."""
    rng = np.random.default_rng(seed)
    cen = rng.integers(lo + 40, hi - 40, (8, d))
    x = cen[rng.integers(0, 8, m)] + rng.integers(-30, 31, (m, d))
    return np.clip(x, lo, hi - 1).astype(np.float32)


def _reference(x, q, k, exclude_zero):
    """The direct form in float64: sum((q - c)^2), the k smallest."""
    d2 = ((q[:, None, :].astype(np.float64) - x[None, :, :]) ** 2).sum(-1)
    if exclude_zero:
        d2[d2 <= 0] = np.inf
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d2, order, axis=1), order


@pytest.fixture(scope="module")
def pair128():
    x = _rows(0, 3000, 128)
    x[5] = x[4]  # a duplicate: a zero distance between corpus rows
    cfg = _cfg()
    return x, build_index(x, cfg), build_index(
        x.astype(np.uint8), cfg.replace(dtype="uint8"))


@pytest.mark.parametrize("d,q_tile,nq,k,exclude_zero", [
    # 1024 query rows: the one-pass branch — the kernel that walks the
    # stack on the lane grid, the scan's one-pass tile steps off it
    (128, 1024, 1024, 10, True), (128, 1024, 1024, 30, False),
    (192, 1024, 1024, 30, False), (100, 1024, 1024, 10, False),
    (100, 1024, 1024, 30, True),
    # (d = 784 at 1024 rows: the float32 side's rows-minor kernel takes
    # minutes interpreted; its centred 9-bit values are met at 64 rows)
    # 256 and 64: the scan's tile steps over widened tiles, no branch
    (128, 256, 256, 10, True), (128, 256, 256, 30, False),
    (128, 64, 40, 10, True), (128, 64, 40, 30, False),
    (192, 64, 64, 10, True), (192, 64, 64, 30, False),
    (784, 64, 64, 10, True), (784, 64, 64, 30, False),
    (100, 64, 7, 10, True), (100, 64, 7, 30, False),
])
def test_byte_index_equals_float32_index_and_the_reference(
        d, q_tile, nq, k, exclude_zero):
    m = 1100
    x = _rows(d, m, d)
    q = _rows(d + 1, nq, d)
    q[0] = x[17]  # a query that IS a corpus row
    cfg = _cfg(k=k, q_tile=q_tile, exclude_zero=exclude_zero)
    wide = build_index(x, cfg)
    rest = build_index(x.astype(np.uint8), cfg.replace(dtype="uint8"))
    assert rest.tiles.dtype == jnp.uint8 and wide.tiles.dtype == jnp.float32
    assert rest.tiles.nbytes * 4 == wide.tiles.nbytes
    a, b = query_knn(q, wide), query_knn(q, rest)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)
    ref_d, ref_i = _reference(x, q, k, exclude_zero)
    np.testing.assert_array_equal(b.dists, ref_d)
    # ids: equal but where the reference's stable order meets a tie
    same = b.ids == ref_i
    tied = ref_d == np.roll(ref_d, 1, axis=1)
    tied |= ref_d == np.roll(ref_d, -1, axis=1)
    assert (same | tied).all()
    # which path: the byte stack's one-pass steps have their own column
    steps = np.asarray(b.dist_steps).ravel()
    if q_tile >= 1024:
        assert steps.size == 6 and steps[5] > 0 and steps[:5].sum() == 0
    else:
        assert steps.tolist() == [0, rest.tiles.shape[0]]


def test_the_planes_of_a_byte_index_are_the_float32_index_s(pair128):
    x, wide, rest = pair128
    np.testing.assert_array_equal(np.asarray(rest.tile_sqs),
                                  np.asarray(wide.tile_sqs))
    np.testing.assert_array_equal(np.asarray(rest.tile_ids),
                                  np.asarray(wide.tile_ids))
    np.testing.assert_array_equal(rest.mu, wide.mu)
    np.testing.assert_array_equal(np.asarray(rest.rest_offset), wide.mu)
    np.testing.assert_array_equal(
        np.asarray(rest.tiles).reshape(-1, 128)[:3000], x)
    assert rest.onepass is not None and wide.onepass is not None
    assert wide.rest_offset is None


@pytest.mark.parametrize("sizes", [
    [3000], [1024, 1024, 952], [700, 1500, 800], [1, 2047, 952],
    [2999, 1], [100] * 30,
])
@pytest.mark.parametrize("dtype", ["uint8", "float32"])
def test_block_fed_build_equals_the_one_array_build(pair128, sizes, dtype):
    """Blocks of uneven sizes, off the tile grid, a last partial tile: the
    same stack, ids and norms, bit for bit."""
    x, wide, rest = pair128
    want = rest if dtype == "uint8" else wide
    cuts = np.cumsum([0] + sizes)
    blocks = [x[lo:hi] for lo, hi in zip(cuts[:-1], cuts[1:])]
    if dtype == "uint8":  # bytes and floats may alternate: both are checked
        blocks = [b.astype(np.uint8) if i % 2 else b
                  for i, b in enumerate(blocks)]
    got = build_index_blocks(x.shape, iter(blocks), _cfg(dtype=dtype))
    for name in ("tiles", "tile_ids", "tile_sqs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=name)
    np.testing.assert_array_equal(got.mu, want.mu)
    assert (got.onepass is None) == (want.onepass is None)
    assert got.layout is want.layout


def test_blocks_from_a_callable_and_device_blocks(pair128):
    x, _, rest = pair128
    parts = [jnp.asarray(x[:2048].astype(np.uint8)), x[2048:]]
    got = build_index_blocks(
        x.shape, lambda i: parts[i] if i < 2 else None, _cfg(dtype="uint8"))
    np.testing.assert_array_equal(np.asarray(got.tiles),
                                  np.asarray(rest.tiles))


def test_fractional_block_build_is_the_one_array_build_to_the_mean_s_bits():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2000, 64)).astype(np.float32)
    q = rng.normal(size=(64, 64)).astype(np.float32)
    cfg = _cfg(q_tile=64)
    a = query_knn(q, build_index(x, cfg))
    b = query_knn(q, build_index_blocks(
        x.shape, [x[:900], x[900:]], cfg))
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_allclose(a.dists, b.dists, rtol=1e-5)


@pytest.mark.parametrize("blocks,why", [
    ([(1000, 128)], "held 1000 rows of 3000"),
    ([(2000, 128), (2000, 128)], "does not lie in a corpus"),
    ([(3000, 64)], "does not lie in a corpus"),
])
def test_blocks_that_do_not_make_the_corpus_are_refused(blocks, why):
    with pytest.raises(ValueError, match=why):
        build_index_blocks(
            (3000, 128), [np.zeros(s, np.uint8) for s in blocks],
            _cfg(dtype="uint8"))


def test_fractional_queries_take_the_multipass_branch(pair128):
    """Fractional query rows against a byte index are answered at the
    configured precision over the widened rows: the float32 index's
    answer at ``highest``, not refused."""
    x, wide, rest = pair128
    q = _rows(9, 1024, 128) + np.float32(0.25)
    a, b = query_knn(q, wide), query_knn(q, rest)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)
    assert np.asarray(b.dist_steps).ravel().tolist() == [
        0, rest.tiles.shape[0], 0, 0, 0, 0]
    assert (b.dists != np.rint(b.dists)).any()


@pytest.mark.parametrize("value,row", [(3.5, 1234), (-1.0, 7), (256.0, 2999),
                                       (np.nan, 2048)])
@pytest.mark.parametrize("how", ["one-array", "blocks", "device", "all_knn"])
def test_rows_a_byte_cannot_hold_are_refused_with_the_row_named(
        value, row, how):
    x = _rows(1, 3000, 128)
    x[row, 5] = value
    x[min(row + 100, 2999), 9] = 300.0  # a later offender is not the one named
    cfg = _cfg(dtype="uint8")
    with pytest.raises(ValueError, match=rf"row {row} of the corpus"):
        if how == "one-array":
            build_index(x, cfg)
        elif how == "device":
            build_index(jnp.asarray(x), cfg)
        elif how == "blocks":
            build_index_blocks(x.shape, [x[:1000], x[1000:]], cfg)
        else:
            all_knn(x, queries=x[:8], config=cfg)


@pytest.mark.parametrize("change,why", [
    (dict(metric="cosine"), "requires metric='l2'"),
    (dict(metric="ip"), "metric='ip' requires dtype='float32'"),
    (dict(precision_policy="mixed"), "requires precision_policy='exact'"),
    (dict(backend="ring"), "does not run on backend='ring'"),
    (dict(backend="ring-overlap"), "does not run on backend='ring-overlap'"),
    (dict(partitions=16), "DENSE index's lossless at-rest form"),
    (dict(bucket_headroom=0.25), "is frozen"),
])
def test_what_uint8_is_refused_with(change, why):
    with pytest.raises(ValueError, match=why):
        KNNConfig(dtype="uint8", **{"backend": "serial", **change})


@pytest.mark.parametrize("dtype", ["int8", "int4"])
def test_the_clustered_store_s_codes_keep_their_message(dtype):
    with pytest.raises(ValueError, match="clustered .IVF. store's") as e:
        KNNConfig(dtype=dtype)
    assert "lossless" in str(e.value) and "dtype='uint8'" in str(e.value)
    KNNConfig(dtype=dtype, partitions=16)  # what it is for


def test_uint8_over_several_devices_tags_and_writes_are_refused(pair128):
    x, _, rest = pair128
    with pytest.raises(ValueError, match="rests on one device"):
        build_index(x, KNNConfig(dtype="uint8", backend="auto"))
    tags = (np.arange(3001), np.zeros(3000, np.int32))
    with pytest.raises(ValueError, match="tags holds float32 rows"):
        build_index(x, _cfg(dtype="uint8"), tags=tags)
    from mpi_knn_tpu.serve import ServeSession
    from mpi_knn_tpu.serve.mutate import U8_FROZEN, supports_mutation

    assert not supports_mutation(rest)
    session = ServeSession(rest)
    for write in (lambda: session.upsert([1], x[:1]),
                  lambda: session.delete([1])):
        with pytest.raises(ValueError) as e:
            write()
        assert str(e.value) == U8_FROZEN
    with pytest.raises(ValueError, match="precision_policy"):
        rest.compatible_cfg(rest.cfg.replace(precision_policy="mixed"))


def test_all_knn_takes_a_byte_corpus(pair128):
    x, _, _ = pair128
    q = _rows(4, 1024, 128)
    cfg = _cfg()
    a = all_knn(x, queries=q, config=cfg)
    b = all_knn(x.astype(np.uint8), queries=q,
                config=cfg.replace(dtype="uint8"))
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)
    # all pairs, from a device array of bytes
    pairs = cfg.replace(exclude_self=True)
    a = all_knn(x[:1024], config=pairs)
    b = all_knn(jnp.asarray(x[:1024].astype(np.uint8)),
                config=pairs.replace(dtype="uint8"))
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.dists, b.dists)


@pytest.mark.parametrize("q,c,d,want", [
    (1024, 8192, 128, 1024), (4096, 8192, 128, 1024), (256, 8192, 128, 256),
    (1024, 8192, 256, 1024), (64, 8192, 128, None), (1024, 8192, 784, None),
    (1024, 8192, 192, None), (1024, 8192, 100, None),
])
def test_the_shape_rule_admits_a_byte_stack_on_the_lane_grid(q, c, d, want):
    from mpi_knn_tpu.ops.fused_scan import fused_scan_vmem_bytes
    from mpi_knn_tpu.ops.topk import fused_scan_engages, lane_bin_depth

    depth = lane_bin_depth(q, c, 10)
    assert fused_scan_engages(q, c, d, depth, itemsize=1) == want
    assert fused_scan_engages(q, c, d, depth, itemsize=2) is None
    if want:
        wide = fused_scan_vmem_bytes(want, c, d, depth)
        rest = fused_scan_vmem_bytes(want, c, d, depth, itemsize=1)
        # two buffers of a byte tile for two of a float32 one, the widened
        # piece and the offset's row on top
        piece = 1024 * d
        assert wide - rest == 2 * c * d * 3 - piece * 4 - 2 * 8 * d * 4


def test_the_byte_kernel_s_scratch_is_what_the_arithmetic_says():
    """The kernel as traced for a byte stack: its operands (a uint8 tile
    block, the offset's row last), its scratch shapes and the VMEM it asks
    for, against ``fused_scan_vmem_bytes``."""
    from mpi_knn_tpu.backends.serial import bound_refreshes
    from mpi_knn_tpu.ops import fused_scan as fs

    q, c, d, depth, tiles = 1024, 8192, 128, 5, 3

    def call(stack, offset):
        return fs.fused_scan(
            jnp.zeros((q, d), jnp.float32), jnp.zeros((q,), jnp.int32),
            jnp.zeros((q,), jnp.float32), stack,
            jnp.zeros((tiles, c), jnp.int32),
            jnp.zeros((tiles, c), jnp.float32), bound_refreshes(tiles),
            k=10, depth=depth, exclude_self=False, exclude_zero=True,
            zero_eps=0.0, block=q, **offset)

    def kernel_of(stack, **offset):
        jaxpr = jax.make_jaxpr(lambda s: call(s, offset))(stack)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return eqn

    rest = kernel_of(jnp.zeros((tiles, c, d), jnp.uint8),
                     offset=jnp.zeros((d,), jnp.float32))
    wide = kernel_of(jnp.zeros((tiles, c, d), jnp.float32))
    shapes = [(tuple(v.aval.shape), str(v.aval.dtype)) for v in rest.invars]
    assert ((tiles, c, d), "uint8") in shapes and shapes[-1] == (
        (1, d), "float32")
    assert len(rest.invars) == len(wide.invars) + 1
    limit = {name: e.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
             for name, e in (("rest", rest), ("wide", wide))}
    assert limit["rest"] == fs.fused_scan_vmem_bytes(
        q, c, d, depth, 1) + fs._VMEM_HEADROOM
    assert limit["wide"] == fs.fused_scan_vmem_bytes(
        q, c, d, depth) + fs._VMEM_HEADROOM
    with pytest.raises(ValueError, match="comes with its offset"):
        kernel_of(jnp.zeros((tiles, c, d), jnp.uint8))


def test_the_aot_key_carries_the_at_rest_type(pair128):
    from mpi_knn_tpu.serve import aotcache

    _, wide, rest = pair128
    a = aotcache.fingerprint_facts(wide, wide.cfg, 1024)
    b = aotcache.fingerprint_facts(rest, rest.cfg, 1024)
    assert a["index"]["tiles"][1] == "float32"
    assert b["index"]["tiles"][1] == "uint8"
    assert b["index"].get("rest_offset") is True
    assert "rest_offset" not in a["index"]
    assert aotcache.fingerprint(wide, wide.cfg, 1024) != aotcache.fingerprint(
        rest, rest.cfg, 1024)


def test_gauges_and_counters_of_a_byte_index(pair128):
    from mpi_knn_tpu.obs import metrics as obs_metrics
    from mpi_knn_tpu.serve import ServeSession

    x, _, _ = pair128
    reg = obs_metrics.get_registry()
    build_index(x, _cfg())
    assert reg.gauge("serve_index_rest_bytes_per_row").value == 4 * 128 + 8
    rest = build_index(x.astype(np.uint8), _cfg(dtype="uint8"))
    assert reg.gauge("serve_index_rest_bytes_per_row").value == 128 + 8
    assert reg.gauge("serve_index_onepass").value == 1.0
    u8 = reg.counter("knn_dist_tile_steps_total", labels={"path": "u8"})
    before = u8.value
    session = ServeSession(rest)
    session.submit(_rows(5, 1024, 128))
    session.drain()
    assert u8.value - before == rest.tiles.shape[0]


def test_block_ingest_spans_sit_inside_the_build_span(tmp_path, pair128):
    from mpi_knn_tpu.obs.spans import (
        FlightRecorder,
        read_flight,
        set_recorder,
    )

    x, _, _ = pair128
    path = str(tmp_path / "flight.jsonl")
    set_recorder(FlightRecorder(path, fresh=True))
    try:
        build_index_blocks(x.shape, [x[:2000], x[2000:].astype(np.uint8)],
                           _cfg(dtype="uint8"))
    finally:
        set_recorder(None)
    begun = [e for e in read_flight(path) if e.get("ev") == "B"]
    build = [e for e in begun if e["name"] == "index-build"]
    blocks = [e for e in begun if e["name"] == "block-ingest"]
    assert len(build) == 1 and len(blocks) == 2
    assert [e["attrs"]["rows"] for e in blocks] == [2000, 1000]
    assert [e["attrs"]["bytes"] for e in blocks] == [
        2000 * 128 * 4, 1000 * 128]
    assert all(e["parent"] == build[0]["span"] for e in blocks)
    assert build[0]["attrs"]["bytes"] == 3000 * 128  # at rest


def test_bvecs_file_reaches_the_build_as_bytes(tmp_path, pair128):
    from mpi_knn_tpu.data.vecs import bvecs_blocks

    x, _, rest = pair128
    path = tmp_path / "base.bvecs"
    body = np.empty((3000, 4 + 128), np.uint8)
    body[:, :4] = np.frombuffer(np.int32(128).tobytes(), np.uint8)
    body[:, 4:] = x.astype(np.uint8)
    body.tofile(path)
    shape, blocks = bvecs_blocks(path, block_rows=1300)
    assert shape == (3000, 128)
    first = blocks(0)
    assert first.dtype == np.uint8 and first.shape == (1300, 128)
    assert blocks(2).shape == (400, 128) and blocks(3) is None
    got = build_index_blocks(shape, blocks, _cfg(dtype="uint8"))
    np.testing.assert_array_equal(np.asarray(got.tiles),
                                  np.asarray(rest.tiles))
    assert bvecs_blocks(path, limit=100)[0] == (100, 128)
    with open(path, "ab") as f:
        f.write(b"\x01\x02")
    with pytest.raises(ValueError, match="truncated row 3000"):
        bvecs_blocks(path)
    with pytest.raises(ValueError, match="not a .bvecs file"):
        bvecs_blocks(tmp_path / "x.fvecs")
