import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu.ops.distance import pairwise_cosine, pairwise_dist, pairwise_sq_l2
from tests.oracle import int_sq_l2


def _np_sq_l2(x, y):
    return ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)


def test_sq_l2_matches_dense_oracle(rng):
    x = rng.standard_normal((37, 19)).astype(np.float32)
    y = rng.standard_normal((53, 19)).astype(np.float32)
    got = np.asarray(pairwise_sq_l2(jnp.asarray(x), jnp.asarray(y)))
    want = _np_sq_l2(x.astype(np.float64), y.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_sq_l2_f64_debug_mode_is_tight(rng):
    x = rng.standard_normal((16, 33))
    got = np.asarray(pairwise_sq_l2(jnp.asarray(x, dtype=jnp.float64), jnp.asarray(x, dtype=jnp.float64)))
    want = _np_sq_l2(x, x)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)


def test_sq_l2_self_distance_near_zero_and_clamped(rng):
    x = rng.standard_normal((24, 64)).astype(np.float32) * 10
    d = np.asarray(pairwise_sq_l2(jnp.asarray(x), jnp.asarray(x)))
    assert (d >= 0).all()
    # matmul-form cancellation keeps the diagonal near zero at f32
    assert np.abs(np.diag(d)).max() < 1e-2 * np.abs(d).max()


def test_sq_l2_bf16_inputs_accumulate_f32(rng):
    x = rng.standard_normal((32, 128)).astype(np.float32)
    got = np.asarray(
        pairwise_sq_l2(jnp.asarray(x, dtype=jnp.bfloat16), jnp.asarray(x, dtype=jnp.bfloat16))
    )
    assert got.dtype == np.float32
    want = _np_sq_l2(x.astype(np.float64), x.astype(np.float64))
    # bf16 inputs: loose tolerance, but structure must hold
    np.testing.assert_allclose(got, want, rtol=0.1, atol=1.0)


def test_precomputed_norms_are_equivalent(rng):
    x = rng.standard_normal((8, 12)).astype(np.float32)
    y = rng.standard_normal((9, 12)).astype(np.float32)
    xs = (x.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    ys = (y.astype(np.float64) ** 2).sum(-1).astype(np.float32)
    a = pairwise_sq_l2(jnp.asarray(x), jnp.asarray(y))
    b = pairwise_sq_l2(jnp.asarray(x), jnp.asarray(y), x_sq=jnp.asarray(xs), y_sq=jnp.asarray(ys))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_cosine_distance(rng):
    x = rng.standard_normal((21, 17)).astype(np.float32)
    y = rng.standard_normal((13, 17)).astype(np.float32)
    got = np.asarray(pairwise_cosine(jnp.asarray(x), jnp.asarray(y)))
    xn = x / np.linalg.norm(x, axis=-1, keepdims=True)
    yn = y / np.linalg.norm(y, axis=-1, keepdims=True)
    want = np.maximum(1.0 - xn @ yn.T, 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # self-similarity -> distance ~ 0
    self_d = np.asarray(pairwise_cosine(jnp.asarray(x), jnp.asarray(x)))
    assert np.abs(np.diag(self_d)).max() < 1e-5


def test_metric_dispatch(rng):
    x = jnp.asarray(rng.standard_normal((4, 5)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(pairwise_dist(x, x, "l2")), np.asarray(pairwise_sq_l2(x, x))
    )
    np.testing.assert_array_equal(
        np.asarray(pairwise_dist(x, x, "cosine")), np.asarray(pairwise_cosine(x, x))
    )
    with pytest.raises(ValueError):
        pairwise_dist(x, x, "manhattan")


# ---------------------------------------------------------------------------
# the one-pass rule (PR 29): whole-number rows centred by a whole-number
# offset are bf16 numbers, and for them one bf16 x bf16 MXU pass returns what
# the configured multi-pass dot returns


def _int_sq_l2_topk(q, c, k):
    return np.sort(int_sq_l2(q, c), axis=1)[:, :k]


# (query rows, tile rows) a one-pass program needs: ONEPASS_MIN_ROWS
_ROWS = dict(query_tile=1024, corpus_tile=256)


def _whole(rng, rows, dim, lo=0, hi=256):
    return rng.integers(lo, hi, (rows, dim)).astype(np.float32)


def _steps(*results):
    """[one-pass, multi-pass] tile steps of one-shot calls, as they carry
    them (one row a device); a cosine call's [0, 0, cosine]."""
    return sum(
        np.asarray(r.dist_steps).reshape(
            -1, np.shape(r.dist_steps)[-1]).sum(axis=0) for r in results
    ).tolist()


@pytest.mark.parametrize("precision", ["high", "highest"])
@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("dim", [128, 784])
def test_whole_number_rows_take_one_pass_and_are_exact(
        rng, dim, on_device, precision):
    from mpi_knn_tpu import all_knn

    c, q = _whole(rng, 1024, dim), _whole(rng, 1024, dim)
    put = jnp.asarray if on_device else (lambda a: a)
    res = all_knn(put(c), queries=put(q), k=10, backend="serial",
                  matmul_precision=precision, **_ROWS)
    assert _steps(res) == [4, 0]  # 1 query tile x 4 tiles
    # EQUAL, not close: every product and partial sum is a whole number
    np.testing.assert_array_equal(
        np.asarray(res.dists), _int_sq_l2_topk(q, c, 10).astype(np.float32))


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
def test_fractional_rows_bypass_bit_identical_to_the_parents_formula(
        rng, on_device):
    """Gaussian rows: the offset is the plain mean as the parent computed
    it (eager, float32 accumulation on the device, float64 on the host) and
    the tile program's answer is that of a program with no branch in it."""
    from mpi_knn_tpu import all_knn

    c = rng.standard_normal((1024, 96)).astype(np.float32) * 40 + 100
    q = rng.standard_normal((1024, 96)).astype(np.float32) * 40 + 100
    kw = dict(k=10, backend="serial", matmul_precision="high", **_ROWS)
    if on_device:
        cj, qj = jnp.asarray(c), jnp.asarray(q)
        got = all_knn(cj, queries=qj, **kw)
        mu = jnp.mean(cj, axis=0, dtype=jnp.float32)
        want = all_knn(cj - mu, queries=qj - mu, center=False, **kw)
    else:
        got = all_knn(c, queries=q, **kw)
        mu = c.astype(np.float64).mean(axis=0)
        want = all_knn(c - mu, queries=q - mu, center=False, **kw)
    assert _steps(got, want) == [0, 8]
    # a corpus that does not qualify has no branch in its program: the
    # count is known before the program runs
    assert isinstance(got.dist_steps, np.ndarray)
    np.testing.assert_array_equal(np.asarray(got.dists), np.asarray(want.dists))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))


@pytest.mark.parametrize("backend", ["serial", "ring"])
def test_all_knn_under_an_outer_jit_decides_inside_the_program(rng, backend):
    """Traced, the corpus fact is a tracer: it is not read and never leaves
    the trace; the program carries both branches and reports its own
    count. A plain call afterwards reads its fact and agrees."""
    import jax

    from mpi_knn_tpu import all_knn

    x = jnp.asarray(_whole(rng, 2048, 16))
    kw = dict(k=5, backend=backend, matmul_precision="high", **_ROWS,
              **(dict(num_devices=2) if backend == "ring" else {}))
    fn = jax.jit(lambda c: all_knn(c, **kw))
    text = fn.lower(x).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    traced, plain = fn(x), all_knn(x, **kw)
    assert _steps(traced) == _steps(plain) == [2 * 8, 0]
    np.testing.assert_array_equal(
        np.asarray(traced.dists), np.asarray(plain.dists))
    np.testing.assert_array_equal(
        np.asarray(traced.dists)[:, 0],
        np.sort(np.where(
            np.eye(2048, dtype=bool), np.iinfo(np.int64).max,
            int_sq_l2(x, x),
        ), axis=1)[:, 0].astype(np.float32))


@pytest.mark.parametrize("rows,fact", [
    ("uint8", True),  # whole numbers in [0, 255]
    ("negative", True),  # whole numbers in [-255, 0]
    ("equal_column", True),  # a column of one value centres to zeros
    ("wide_exact", True),  # {0, 1024}: centred to +-512, past 256 yet bf16
    ("wide_inexact", False),  # [0, 1023]: centred past 256, 9+ bits needed
    ("halves", False),  # x.5 rows are not whole: the mean stays fractional
], ids=lambda v: str(v))
def test_the_bf16_test_decides_not_a_range_guess(rng, rows, fact):
    from mpi_knn_tpu.ops.distance import bf16_exact, center_for_l2

    c = {
        "uint8": lambda: _whole(rng, 256, 32),
        "negative": lambda: _whole(rng, 256, 32, -255, 1),
        "equal_column": lambda: np.concatenate(
            [_whole(rng, 256, 31), np.full((256, 1), 7, np.float32)], axis=1),
        "wide_exact": lambda: rng.integers(0, 2, (256, 32)).astype(
            np.float32) * 1024,
        "wide_inexact": lambda: _whole(rng, 256, 32, 0, 1024),
        "halves": lambda: _whole(rng, 256, 32) + 0.5,
    }[rows]()
    for corpus in (c, jnp.asarray(c)):
        centred, _, got, mu = center_for_l2(corpus, corpus, all_pairs=True)
        assert bool(got) is fact
        assert bf16_exact(np.asarray(centred)) is fact
        if rows != "halves":  # a whole-number corpus: a whole-number offset
            np.testing.assert_array_equal(np.asarray(mu), np.rint(np.asarray(mu)))
        # the offset is a translation, whichever it is
        np.testing.assert_allclose(
            np.asarray(centred) + np.asarray(mu), c, rtol=0, atol=1e-3)


@pytest.mark.parametrize("values,dtype,want", [
    ([0.0, -0.0, 1.0, -255.0, 256.0, 512.0, 0.5, 2.0 ** -100], "float32", True),
    ([187.25], "float32", False),  # ten significant bits
    ([257.0], "float32", False),
    ([0.1], "float32", False),
    ([float("inf"), 1.0], "float32", True),
    ([float("nan"), 1.0], "float32", False),
    ([1.0, 1.0009765625], "float16", False),  # fits float16, not bf16
    ([3.0, 240.0], "float16", True),
    (list(range(256)), "uint8", True),
], ids=lambda v: str(v)[:24])
def test_bf16_exact_says_the_same_on_the_host_and_on_the_device(
        values, dtype, want):
    """The device test reads bit patterns, the host test rounds with
    ml_dtypes: one verdict."""
    from mpi_knn_tpu.ops.distance import bf16_exact

    a = np.asarray(values, dtype=dtype)
    assert bf16_exact(a) is want
    assert bool(bf16_exact(jnp.asarray(a))) is want


def test_the_device_test_reads_bits_and_rounds_nothing():
    """Inside a fusion the TPU compiler may keep a float32 -> bfloat16 ->
    float32 round trip in float32, and a test that rounds and compares
    then reads True for any data (on the v5e fractional rows took the
    one-pass dot: PERF.md §6, PR 29). No CPU run can show that, so the
    programs that hold the test are held to its form: the bit pattern,
    and no narrowing to bfloat16 anywhere in them."""
    import jax

    from mpi_knn_tpu.ops.distance import _center_on_device, bf16_exact

    x = jax.ShapeDtypeStruct((64, 8), jnp.float32)
    for fn in (_center_on_device, jax.jit(bf16_exact)):
        text = fn.lower(x).as_text()
        assert "bitcast_convert" in text and "xbf16>" not in text, text


def test_one_fractional_query_row_takes_the_old_path_for_its_tile(rng):
    """Two query tiles, one fractional element in the second: that tile's
    steps run the configured dot, the first tile's the one pass, and every
    row's answer is what a program without the branch gives."""
    from mpi_knn_tpu import KNNConfig
    from mpi_knn_tpu.backends.serial import knn_chunk_update
    from mpi_knn_tpu.ops.topk import init_topk_tiles

    c = _whole(rng, 1024, 64, -128, 128)
    q = _whole(rng, 2048, 64, -128, 128)
    q[1500, 3] += 0.001
    cfg = KNNConfig(k=10, backend="serial", matmul_precision="high",
                    center=True, **_ROWS)
    args = (
        jnp.asarray(q.reshape(2, 1024, 64)),
        jnp.full((2, 1024), -1, jnp.int32),
        jnp.asarray(c.reshape(4, 256, 64)),
        jnp.arange(1024, dtype=jnp.int32).reshape(4, 256),
    )
    carry = lambda: init_topk_tiles(2, 1024, 10, dtype=jnp.float32)  # noqa: E731
    d0, i0 = knn_chunk_update(*args, *carry(), cfg)
    d1, i1, counts = knn_chunk_update(*args, *carry(), cfg, jnp.asarray(True))
    # 256-column tiles are under the carried selection's rule: no such count
    assert counts.dist_steps.tolist() == [4, 4] and counts.select_tiles is None
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    # and a corpus that does not qualify sends every tile down the old path
    d2, i2, counts = knn_chunk_update(*args, *carry(), cfg, jnp.asarray(False))
    assert counts.dist_steps.tolist() == [0, 8]
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d0))


@pytest.mark.parametrize("overrides", [
    dict(metric="cosine"),
    dict(dtype="float64"),
    dict(precision_policy="mixed", matmul_precision=None),
    dict(center=False),
    dict(matmul_precision="default"),
    dict(query_tile=512),  # under ONEPASS_MIN_ROWS: not worth the branch
], ids=lambda o: ",".join(f"{k}={v}" for k, v in o.items()))
def test_configurations_the_rule_does_not_take_keep_their_program(
        rng, overrides):
    from mpi_knn_tpu import KNNConfig, all_knn
    from mpi_knn_tpu.backends.serial import onepass_rule

    cfg = KNNConfig(**{**dict(k=10, backend="serial",
                              matmul_precision="high", **_ROWS), **overrides})
    assert not onepass_rule(cfg, cfg.query_tile)
    c, q = _whole(rng, 512, 32), _whole(rng, 1024, 32)
    res = all_knn(jnp.asarray(c), queries=jnp.asarray(q), config=cfg)
    one, *other = _steps(res)  # a cosine call counts on a path of its own
    assert one == 0 and other[-1] > 0 and sum(other) == other[-1]
    if cfg.metric == "l2" and cfg.matmul_precision != "default":
        np.testing.assert_allclose(
            np.asarray(res.dists), _int_sq_l2_topk(q, c, 10), rtol=1e-5)
