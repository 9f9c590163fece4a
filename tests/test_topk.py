import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu.ops.topk import (
    cascade_smallest_k,
    init_topk,
    lane_bin_depth,
    lane_bin_flagged_share,
    mask_tile,
    merge_topk,
    smallest_k,
)
from mpi_knn_tpu.types import INVALID_ID


def _np_smallest_k(d, ids, k):
    order = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, order, -1), np.take_along_axis(ids, order, -1)


def test_smallest_k_matches_argsort(rng):
    d = rng.standard_normal((11, 40)).astype(np.float32)
    ids = np.broadcast_to(np.arange(40, dtype=np.int32), (11, 40))
    got_d, got_i = smallest_k(jnp.asarray(d), jnp.asarray(ids[0]), 7)
    want_d, want_i = _np_smallest_k(d, ids, 7)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_smallest_k_pads_when_k_exceeds_candidates(rng):
    d = rng.standard_normal((3, 5)).astype(np.float32)
    got_d, got_i = smallest_k(jnp.asarray(d), jnp.arange(5, dtype=jnp.int32), 9)
    assert got_d.shape == (3, 9)
    assert np.isinf(np.asarray(got_d)[:, 5:]).all()
    assert (np.asarray(got_i)[:, 5:] == INVALID_ID).all()


def test_inf_slots_get_invalid_ids():
    d = jnp.asarray([[0.5, jnp.inf, 0.1]])
    ids = jnp.asarray([7, 8, 9], dtype=jnp.int32)
    got_d, got_i = smallest_k(d, ids, 3)
    np.testing.assert_array_equal(np.asarray(got_i), [[9, 7, INVALID_ID]])


def test_merge_associativity(rng):
    """merge(merge(a,b),c) == smallest_k(a ‖ b ‖ c) — the property that makes
    ring-order irrelevant (SURVEY.md §4 'Unit')."""
    k = 6
    q = 9
    parts = []
    for s in range(3):
        d = rng.standard_normal((q, 15)).astype(np.float32)
        ids = (np.arange(15, dtype=np.int32) + 100 * s)
        parts.append((d, np.broadcast_to(ids, (q, 15))))

    cd, ci = init_topk(q, k)
    for d, ids in parts:
        nd, ni = smallest_k(jnp.asarray(d), jnp.asarray(ids), k)
        cd, ci = merge_topk(cd, ci, nd, ni)

    all_d = np.concatenate([p[0] for p in parts], axis=-1)
    all_i = np.concatenate([p[1] for p in parts], axis=-1)
    want_d, want_i = _np_smallest_k(all_d, all_i, k)
    np.testing.assert_allclose(np.asarray(cd), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ci), want_i)


def test_merge_commutativity(rng):
    k = 4
    da = rng.standard_normal((5, k)).astype(np.float32)
    db = rng.standard_normal((5, k)).astype(np.float32)
    ia = np.arange(k, dtype=np.int32) + np.zeros((5, 1), np.int32)
    ib = ia + 50
    ab = merge_topk(jnp.asarray(da), jnp.asarray(ia), jnp.asarray(db), jnp.asarray(ib))
    ba = merge_topk(jnp.asarray(db), jnp.asarray(ib), jnp.asarray(da), jnp.asarray(ia))
    np.testing.assert_array_equal(np.asarray(ab[0]), np.asarray(ba[0]))


def test_mask_tile_padding_and_self_exclusion():
    d = jnp.asarray([[1.0, 0.0, 2.0, 3.0]])
    cand = jnp.asarray([0, 1, 2, INVALID_ID], dtype=jnp.int32)
    qids = jnp.asarray([2], dtype=jnp.int32)
    out = np.asarray(
        mask_tile(d, cand, query_ids=qids, exclude_self=True, exclude_zero=True)
    )
    # candidate 1: zero distance -> excluded; candidate 2 == self; candidate 3 pad
    np.testing.assert_array_equal(np.isinf(out), [[False, True, True, True]])


def test_mask_tile_zero_eps():
    d = jnp.asarray([[1e-13, 1e-3]])
    cand = jnp.asarray([0, 1], dtype=jnp.int32)
    out = np.asarray(mask_tile(d, cand, exclude_self=False, exclude_zero=True, zero_eps=1e-12))
    assert np.isinf(out[0, 0]) and not np.isinf(out[0, 1])


@pytest.mark.parametrize("c,block", [(40, 8), (129, 16), (256, 128), (30, 64)])
def test_block_method_is_exact(rng, c, block):
    """topk_method='block' must be bit-identical to exact for every shape:
    wider-than-block rows (two-level path), non-divisible widths (inf
    padding), and narrower-than-block rows (falls through to plain exact)."""
    d = rng.standard_normal((9, c)).astype(np.float32)
    ids = np.broadcast_to(np.arange(c, dtype=np.int32), (9, c))
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), 7, method="block", block=block
    )
    want_d, want_i = _np_smallest_k(d, ids, 7)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_block_method_k_exceeding_block_falls_back(rng):
    d = rng.standard_normal((4, 60)).astype(np.float32)
    ids = np.broadcast_to(np.arange(60, dtype=np.int32), (4, 60))
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), 12, method="block", block=8
    )
    want_d, want_i = _np_smallest_k(d, ids, 12)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_block_method_keeps_inf_invalid(rng):
    d = jnp.full((3, 200), jnp.inf)
    got_d, got_i = smallest_k(
        d, jnp.arange(200, dtype=jnp.int32), 5, method="block", block=64
    )
    assert np.isinf(np.asarray(got_d)).all()
    assert (np.asarray(got_i) == INVALID_ID).all()


@pytest.mark.parametrize(
    "c,k,max_width",
    [(100, 5, 16), (513, 5, 64), (50, 5, 512), (100, 20, 8), (41, 3, 7)],
)
def test_cascade_smallest_k_matches_exact(rng, c, k, max_width):
    """Including max_width < k (fold width must self-correct to >= 2k) and
    non-divisible chunking."""
    d = rng.standard_normal((6, c)).astype(np.float32)
    ids = np.broadcast_to(np.arange(c, dtype=np.int32), (6, c))
    got_d, got_i = cascade_smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), k, max_width=max_width
    )
    want_d, want_i = _np_smallest_k(d, ids, k)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_bf16_method_recall(rng):
    """'bf16' preselects with half-width keys then finishes exact — no
    exactness guarantee, but on well-separated random data it must recover
    essentially everything (measured recall is the method's contract)."""
    hits = total = 0
    for trial in range(5):
        d = rng.standard_normal((32, 600)).astype(np.float32) * 100.0
        ids = np.broadcast_to(np.arange(600, dtype=np.int32), (32, 600))
        got_d, got_i = smallest_k(
            jnp.asarray(d), jnp.asarray(ids[0]), 8, method="bf16"
        )
        want_d, want_i = _np_smallest_k(d, ids, 8)
        # distances of recovered ids must be the TRUE f32 values, not
        # bf16-rounded ones: check each returned (id, dist) against the
        # original matrix
        gd, gi = np.asarray(got_d), np.asarray(got_i)
        assert gd.dtype == np.float32
        np.testing.assert_array_equal(
            gd, np.take_along_axis(d, gi, axis=1)
        )
        for r in range(32):
            hits += len(set(gi[r]) & set(want_i[r]))
            total += 8
    assert hits / total >= 0.999, hits / total


def test_bf16_method_small_c_falls_back_exact(rng):
    d = rng.standard_normal((4, 20)).astype(np.float32)
    ids = np.broadcast_to(np.arange(20, dtype=np.int32), (4, 20))
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), 6, method="bf16"
    )
    want_d, want_i = _np_smallest_k(d, ids, 6)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_approx_method_runs_on_cpu(rng):
    d = rng.standard_normal((4, 64)).astype(np.float32)
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.arange(64, dtype=jnp.int32), 5, method="approx"
    )
    # on CPU approx_min_k falls back to exact
    want_d, _ = _np_smallest_k(
        d, np.broadcast_to(np.arange(64, dtype=np.int32), d.shape), 5
    )
    np.testing.assert_allclose(np.sort(np.asarray(got_d)), want_d, rtol=1e-6)


def test_approx_rerank_method_recall(rng):
    """'approx-rerank' (TPU-KNN recipe: overfetched approx preselect +
    exact f32 rerank) makes no exactness claim, but on CPU approx_min_k is
    an exact fallback, so the output must match exact top-k — and every
    returned pair must be self-consistent against the input."""
    d = rng.standard_normal((16, 640)).astype(np.float32)
    ids = np.broadcast_to(np.arange(640, dtype=np.int32), (16, 640))
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), 8, method="approx-rerank",
        recall_target=0.9,
    )
    want_d, want_i = _np_smallest_k(d, ids, 8)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    # each returned pair is a real (id, dist) from the input row
    for r in range(16):
        for dist, i in zip(np.asarray(got_d)[r], np.asarray(got_i)[r]):
            assert d[r, i] == dist


def test_approx_rerank_small_c_falls_back_exact(rng):
    """c <= 4k: no preselect possible, plain exact path."""
    d = rng.standard_normal((4, 20)).astype(np.float32)
    ids = np.broadcast_to(np.arange(20, dtype=np.int32), (4, 20))
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), 6, method="approx-rerank"
    )
    want_d, want_i = _np_smallest_k(d, ids, 6)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_approx_rerank_nondivisible_width_padded(rng):
    """The 128-lane alignment pad (+inf/-1) must never surface in results
    (the r3 transport-wedge guard applies to the preselect too)."""
    d = rng.standard_normal((5, 333)).astype(np.float32)
    ids = np.broadcast_to(np.arange(333, dtype=np.int32), (5, 333))
    got_d, got_i = smallest_k(
        jnp.asarray(d), jnp.asarray(ids[0]), 7, method="approx-rerank"
    )
    want_d, want_i = _np_smallest_k(d, ids, 7)
    np.testing.assert_allclose(np.asarray(got_d), want_d, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    assert (np.asarray(got_i) >= 0).all()


# --- the lane-bin selection: the engaged form of method="exact" -------------


def _full_width(d, ids, k):
    """What every engaged call must equal: lax.top_k over the whole row."""
    neg, pos = jax.lax.top_k(-jnp.asarray(d), k)
    vals = np.asarray(-neg)
    out = np.asarray(ids)[np.asarray(pos)]
    return vals, np.where(np.isinf(vals), INVALID_ID, out)


def _tile_ids(c, contiguous):
    """A tile's id vector: a contiguous run, or a shuffled sparse one whose
    last 37 columns are padding (-1, which mask_tile holds at +inf)."""
    if contiguous:
        return np.arange(c, dtype=np.int32) + 70_000
    ids = np.random.default_rng(c).permutation(3 * c)[:c].astype(np.int32)
    ids[-37:] = INVALID_ID
    return ids


@pytest.mark.parametrize("contiguous", [True, False], ids=["contig", "padded"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
@pytest.mark.parametrize("k", [1, 10, 16])
@pytest.mark.parametrize("q,c", [(8, 1024), (64, 2048), (256, 1024), (8, 8192)])
def test_lane_bin_selection_equals_full_width_top_k(q, c, k, dtype, contiguous):
    """The engaged path against lax.top_k: distances bit-equal, ids equal
    wherever a row's k+1 smallest are distinct (all rows, on continuous
    data)."""
    assert lane_bin_depth(q, c, k) is not None
    rng = np.random.default_rng([q, c, k])
    d = rng.standard_normal((q, c)).astype(dtype)
    ids = _tile_ids(c, contiguous)
    d[:, ids < 0] = np.inf
    got_d, got_i = jax.jit(smallest_k, static_argnums=2)(
        jnp.asarray(d), jnp.asarray(ids), k)
    want_d, want_i = _full_width(d, ids, k)
    assert got_d.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got_d), want_d)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_lane_bin_rows_short_of_k_finite_values(rng):
    """Rows with fewer than k finite values end in (+inf, INVALID_ID), an
    all-NaN row returns what lax.top_k returns for it, and a row of equal
    values returns k of them with distinct ids."""
    q, c, k = 16, 1024, 10
    d = rng.standard_normal((q, c)).astype(np.float32)
    d[0, 3:] = np.inf  # 3 finite values
    d[1, :] = np.inf  # none
    d[2, :] = np.nan
    d[3, :] = 2.5
    ids = np.arange(c, dtype=np.int32)
    got_d, got_i = smallest_k(jnp.asarray(d), jnp.asarray(ids), k)
    got_d, got_i = np.asarray(got_d), np.asarray(got_i)
    want_d, want_i = _full_width(d, ids, k)
    np.testing.assert_array_equal(got_d, want_d)
    np.testing.assert_array_equal(got_i[:2], want_i[:2])
    np.testing.assert_array_equal(got_i[4:], want_i[4:])
    assert np.isinf(got_d[0, 3:]).all() and (got_i[0, 3:] == INVALID_ID).all()
    assert (got_i[1] == INVALID_ID).all() and np.isnan(got_d[2]).all()
    assert (got_d[3] == 2.5).all() and len(set(got_i[3])) == k


@pytest.mark.parametrize("extra", [0, 1], ids=["R-in-a-lane", "R+1-in-a-lane"])
def test_lane_bin_planted_collision_is_flagged_and_exact(rng, extra):
    """R (and R+1) of a row's k smallest in ONE lane: more than the bins
    keep, so the certificate flags the row and the tile step's answer comes
    from the full-width fallback, still exact. One group fewer is kept
    whole and flags nothing."""
    q, c, k = 64, 2048, 10
    depth = lane_bin_depth(q, c, k)
    d = rng.standard_normal((q, c)).astype(np.float32)
    ids = np.arange(c, dtype=np.int32)
    held = d.copy()
    held[5, 7:7 + 128 * (depth - 1):128] = -50.0 - np.arange(depth - 1)
    assert lane_bin_flagged_share(held, k) == (0.0, 0.0)
    lane = 7 + 128 * np.arange(depth + 1 + extra)  # columns 7, 135, 263, ...
    d[5, lane] = -50.0 - np.arange(len(lane))
    share_rows, tile = lane_bin_flagged_share(d, k)
    assert (share_rows, tile) == (1 / q, 1.0)
    got_d, got_i = smallest_k(jnp.asarray(d), jnp.asarray(ids), k)
    want_d, want_i = _full_width(d, ids, k)
    np.testing.assert_array_equal(np.asarray(got_d), want_d)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


@functools.partial(jax.jit, static_argnames=("k", "depth"))
def _carried(stack_d, stack_ids, k, depth):
    """The lists carried over a (T, q, c) stack of tiles, then one finish:
    what an engaged ``merge_tiles_into_carry`` selects with."""
    from mpi_knn_tpu.ops.lane_bin import (
        lane_bin_insert,
        lane_bin_lists,
        lane_bin_result,
    )

    q = stack_d.shape[1]
    lists, _ = jax.lax.scan(
        lambda lists, tile: (lane_bin_insert(lists, *tile, depth), None),
        lane_bin_lists(q, depth, stack_d.dtype), (stack_d, stack_ids))
    return lane_bin_result(lists, q, k)


@pytest.mark.parametrize("q,tiles,c,k,contiguous", [
    (8, 5, 1024, 1, True), (24, 3, 2048, 10, False), (64, 4, 1024, 16, False)])
def test_carried_lists_equal_full_width_top_k_of_the_stack(
        q, tiles, c, k, contiguous):
    """Lists carried over the tiles of a stack (rows not a whole strip, -1
    ids, k 1 / 10 / 16) hold the stack's k smallest: bit-equal to
    ``lax.top_k`` over the concatenated tiles, ids too (continuous data: no
    equal distances), nothing flagged. (Against the per-tile program:
    ``tests/test_serial.py``.)"""
    depth = lane_bin_depth(q, c, k)
    rng = np.random.default_rng([q, c, k])
    d = rng.standard_normal((tiles, q, c)).astype(np.float32)
    ids = np.stack([_tile_ids(c, contiguous) + 3 * c * t * (
        _tile_ids(c, contiguous) >= 0) for t in range(tiles)]).astype(np.int32)
    d[np.broadcast_to((ids < 0)[:, None, :], d.shape)] = np.inf
    got_d, got_i, flagged = _carried(jnp.asarray(d), jnp.asarray(ids), k, depth)
    assert not np.asarray(flagged).any()
    wide = np.moveaxis(d, 0, 1).reshape(q, tiles * c)
    want_d, want_i = _full_width(wide, ids.reshape(-1), k)
    np.testing.assert_array_equal(np.asarray(got_d), want_d)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


@pytest.mark.parametrize("planted,flags", [(3, False), (4, True), (5, True)],
                         ids=["R-1-in-a-lane", "R-in-a-lane", "R+1-in-a-lane"])
def test_carried_lists_flag_a_collision_no_tile_sees(rng, planted, flags):
    """R (and R + 1) of a row's k - 1 smallest in ONE lane, one or two a
    tile: no tile's own certificate flags anything, the carried lists'
    does, for that row alone; one fewer is kept whole. Rows short of k
    finite values, NaN rows and a row of equal values flag as a tile's do."""
    q, tiles, c, k = 16, 4, 1024, 10
    depth = lane_bin_depth(q, c, k)
    assert depth == 4
    d = rng.standard_normal((tiles, q, c)).astype(np.float32)
    for j in range(planted):
        d[j % tiles, 5, 7 + 128 * (j // tiles)] = -50.0 - j
    d[:, 1, :] = np.inf  # no finite value
    d[:, 2, :] = np.nan
    d[:, 3, :] = 2.5
    d[:, 4, :] = np.inf
    d[2, 4, 100:103] = [3.0, 1.0, 2.0]  # three finite values
    assert all(lane_bin_flagged_share(d[t][[0, 3, 5, 6] * 4], k) == (0.0, 0.0)
               for t in range(tiles))
    ids = np.arange(tiles * c, dtype=np.int32).reshape(tiles, c)
    got_d, got_i, flagged = map(np.asarray, _carried(
        jnp.asarray(d), jnp.asarray(ids), k, depth))
    want = np.zeros(q, bool)
    want[[1, 2, 4]] = True  # the k-th candidate is not finite
    want[5] = flags
    np.testing.assert_array_equal(flagged, want)
    wide = np.moveaxis(d, 0, 1).reshape(q, tiles * c)
    want_d, want_i = _full_width(wide, ids.reshape(-1), k)
    sound = ~want
    np.testing.assert_array_equal(got_d[sound], want_d[sound])
    keep = sound.copy()
    keep[3] = False  # equal values: any k distinct ids
    np.testing.assert_array_equal(got_i[keep], want_i[keep])
    assert (got_d[3] == 2.5).all() and len(set(got_i[3])) == k
    assert got_d[4, :3].tolist() == [1.0, 2.0, 3.0]


@functools.partial(jax.jit, static_argnames=("k", "depth", "refresh"))
def _carried_under_a_bound(stack_d, stack_ids, start, k, depth, refresh=True):
    """:func:`_carried` the way an engaged ``merge_tiles_into_carry`` runs
    it since ISSUE 35: a row bound rides the scan beside the lists, taken
    anew from them at ``backends/serial.py bound_refreshes``' steps, and
    *bins* inserts the chunks that hold a value at or under it. Returns
    (vals, ids, flagged, lists, chunks inserted)."""
    from mpi_knn_tpu.backends.serial import bound_refreshes
    from mpi_knn_tpu.ops.lane_bin import (
        lane_bin_bound,
        lane_bin_insert,
        lane_bin_lists,
        lane_bin_no_bound,
        lane_bin_result,
    )

    tiles, q, _ = stack_d.shape

    def step(state, tile):
        *lists, bound, inserted = state
        *tile, due = tile
        bound = jax.lax.cond(
            due, lambda: jnp.minimum(bound, lane_bin_bound(lists, k)),
            lambda: bound)
        *lists, n = lane_bin_insert(lists, *tile, depth, bound)
        return (*lists, bound, inserted + n), None

    due = bound_refreshes(tiles) if refresh else np.zeros(tiles, bool)
    (*lists, _, inserted), _ = jax.lax.scan(
        step,
        (*lane_bin_lists(q, depth, stack_d.dtype),
         jnp.minimum(lane_bin_no_bound(q, stack_d.dtype), start[:, None]),
         jnp.int32(0)),
        (stack_d, stack_ids, due))
    return *lane_bin_result(lists, q, k), lists, inserted


def _bound_case(data, q, tiles, c, k, rng):
    """A (tiles, q, c) stack of distance tiles of one kind."""
    if data == "fractional":
        # a row's neighbours in the first tile, a few rows' best in a late
        # one: those chunks insert, the others hold nothing under the bound
        d = 1.0 + rng.random((tiles, q, c), dtype=np.float32)
        lanes = rng.permuted(np.tile(np.arange(c), (q, 1)), axis=1)[:, :k + 2]
        d[0][np.arange(q)[:, None], lanes] = rng.random(
            (q, k + 2), dtype=np.float32)
        d[tiles - 1, ::64, 5] = -1.0
    elif data == "ties":
        # whole numbers, most rows' k-th smallest shared by many columns
        d = rng.integers(0, 6, (tiles, q, c)).astype(np.float32)
    elif data in ("descending", "ascending"):
        # every row's values in (reverse) order over the whole stack
        d = np.sort(rng.random((q, tiles * c), dtype=np.float32), axis=1)
        if data == "descending":
            d = d[:, ::-1]
        d = np.ascontiguousarray(np.moveaxis(d.reshape(q, tiles, c), 1, 0))
    elif data == "inf-and-nan":
        # tombstones and padding (+inf columns), a NaN row, a row short of k
        d = rng.random((tiles, q, c), dtype=np.float32)
        d[:, :, rng.random(c) < 0.3] = np.inf
        d[1, :, 900:] = np.inf
        d[:, 3, :] = np.nan
        d[:, 6, :] = np.inf
        d[2, 6, 40:44] = [4.0, 2.0, 1.0, 3.0]
    else:  # "collision": depth + 1 of a row's smallest in one lane
        d = rng.random((tiles, q, c), dtype=np.float32)
        for j in range(6):
            d[j % tiles, 9, 77 + 128 * (j // tiles)] = -5.0 - j
    return d


@pytest.mark.parametrize("start", ["from-inf", "from-finite"])
@pytest.mark.parametrize("data", ["fractional", "ties", "descending",
                                  "ascending", "inf-and-nan", "collision"])
@pytest.mark.parametrize("q,tiles,c", [(64, 8, 2048), (1024, 6, 2048),
                                       (4096, 3, 1024)],
                         ids=["64-rows-depth-4", "1024-rows-depth-5",
                              "4096-rows-depth-5"])
def test_carried_lists_under_a_row_bound_answer_as_without_it(
        q, tiles, c, data, start):
    """ISSUE 35: what *bins* skips under the row bound changes nothing that
    ``lane_bin_result`` returns — ``vals``, ``ids`` and ``flagged`` are
    those of the scan without a bound, bit for bit, on any data; rows that
    pass the certificate hold the full-width answer (what the re-scan gives
    the flagged ones). The bound starts at +inf, as ``_merge_carried``
    starts it, or finite: each row's final k-th smallest, the tightest
    value a caller may hand in (a tie with the bound is kept). From +inf
    a descending corpus inserts every chunk, an ascending one few."""
    k = 10
    depth = lane_bin_depth(q, c, k)
    assert depth == (4 if q == 64 else 5)
    rng = np.random.default_rng([q, len(data), start == "from-inf"])
    d = _bound_case(data, q, tiles, c, k, rng)
    ids = np.arange(tiles * c, dtype=np.int32).reshape(tiles, c)
    wide = np.moveaxis(d, 0, 1).reshape(q, tiles * c)
    want_d, want_i = _full_width(wide, ids.reshape(-1), k)
    bound = np.full(q, np.inf, np.float32)
    if start == "from-finite":
        bound = np.where(np.isfinite(want_d[:, k - 1]), want_d[:, k - 1],
                         np.inf).astype(np.float32)
    plain = tuple(map(np.asarray, _carried(
        jnp.asarray(d), jnp.asarray(ids), k, depth)))
    *got, _, inserted = _carried_under_a_bound(
        jnp.asarray(d), jnp.asarray(ids), jnp.asarray(bound), k, depth)
    got = tuple(map(np.asarray, got))
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a, b)
    flagged = got[2]
    if data == "collision":
        assert flagged.tolist() == [r == 9 for r in range(q)]
    elif data == "inf-and-nan":
        assert flagged[3] and flagged[6]
    elif data != "ties":  # equal values may share a lane
        assert not flagged.any()
    np.testing.assert_array_equal(got[0][~flagged], want_d[~flagged])
    if data != "ties":  # equal distances: any of the tied ids
        np.testing.assert_array_equal(got[1][~flagged], want_i[~flagged])
    from mpi_knn_tpu.ops.lane_bin import lane_bin_chunks

    chunks = tiles * lane_bin_chunks(q, c)
    inserted = int(inserted)
    if data == "descending" and start == "from-inf":
        assert inserted == chunks
    elif data == "descending":  # told the answer, it waits for the end
        assert inserted <= chunks // tiles
    elif data == "ascending":
        # the first tile's, then (from +inf) what the first refresh lets by
        assert inserted <= (chunks // tiles) * (1 if start != "from-inf" else 2)
    elif data == "fractional":  # the first tile's and the planted ones
        assert 0 < inserted <= (chunks // tiles) * 3 // 2
    else:
        assert 0 < inserted <= chunks


def test_a_bound_of_inf_and_no_bound_fill_the_same_lists(rng):
    """A bound that skips nothing (+inf, never refreshed) and a call
    without a bound — the parent's kernel — leave the same lists, slot for
    slot, and every chunk is counted as inserted."""
    from mpi_knn_tpu.ops.lane_bin import (
        lane_bin_chunks,
        lane_bin_insert,
        lane_bin_lists,
    )

    q, tiles, c, k, depth = 48, 3, 2048, 10, 4
    d = rng.standard_normal((tiles, q, c)).astype(np.float32)
    d[1, :, 500:600] = np.inf
    ids = np.arange(tiles * c, dtype=np.int32).reshape(tiles, c)
    lists = lane_bin_lists(q, depth)
    for t in range(tiles):
        lists = lane_bin_insert(lists, jnp.asarray(d[t]), jnp.asarray(ids[t]),
                                depth)
    *_, under, inserted = _carried_under_a_bound(
        jnp.asarray(d), jnp.asarray(ids), jnp.full(q, jnp.inf, jnp.float32), k,
        depth,
        refresh=False)
    for a, b in zip(lists, under):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(inserted) == tiles * lane_bin_chunks(q, c)


@pytest.mark.parametrize(
    "q,c,k,ids_ndim,depth",
    [
        # the three cells' per-tile calls
        (4096, 8192, 10, 1, 5),
        (1024, 8192, 10, 1, 5),
        (64, 8192, 10, 1, 4),
        # k = 1: each lane's minimum is enough, nothing can be flagged
        (4096, 8192, 1, 1, 1),
        (256, 2048, 16, 1, 5),
        (1024, 8192, 32, 1, 7),
        (8, 1024, 10, 1, 4),
        # bypassed: 2-D ids (merges, the cascade, IVF)
        (4096, 8192, 10, 2, None),
        # bypassed: the stream schedule's carry || tile, and narrow tiles
        (4096, 8192 + 10, 10, 1, None),
        (4096, 896, 10, 1, None),
        (64, 512, 10, 1, None),
        # bypassed: k beyond what 8 slots a lane can certify
        (1024, 8192, 100, 1, None),
        (4096, 8192, 256, 1, None),
        # 1024 columns are 8 groups: at most 7 slots a lane
        (4096, 1024, 32, 1, 7),
        (4096, 1024, 48, 1, None),
        # a tile of a few rows: the expected share would let k past 128,
        # the finish kernel's 128-lane accumulators do not
        (1, 8192, 128, 1, 8),
        (1, 8192, 129, 1, None),
        (1, 8192, 152, 1, None),
        (2, 2048, 128, 1, 8),
        (2, 2048, 130, 1, None),
        (8, 8192, 118, 1, 8),
        (8, 8192, 119, 1, None),
    ],
)
def test_lane_bin_engage_rule(q, c, k, ids_ndim, depth):
    assert lane_bin_depth(q, c, k, ids_ndim) == depth


@pytest.mark.parametrize("q,c,k", [(1, 8192, 128), (1, 8192, 129),
                                   (2, 2048, 128), (2, 2048, 130)])
def test_few_rows_and_k_at_the_accumulator_width(q, c, k):
    """A one- or two-row tile engages up to k = 128, every lane of the
    finish kernel's accumulators in use, and bypasses from 129: the exact k
    smallest on both sides of the boundary."""
    assert (lane_bin_depth(q, c, k) is not None) == (k <= 128)
    d = np.random.default_rng([q, c, k]).standard_normal((q, c))
    d = d.astype(np.float32)
    ids = _tile_ids(c, contiguous=True)
    got_d, got_i = smallest_k(jnp.asarray(d), jnp.asarray(ids), k)
    want_d, want_i = _full_width(d, ids, k)
    np.testing.assert_array_equal(np.asarray(got_d), want_d)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_query_knn_with_two_rows_and_k_past_the_accumulators(rng):
    """The serving path at a shape the rule once engaged and the kernel
    could not hold (2-row query tile, k = 130): answers as the full-width
    path gives them."""
    from mpi_knn_tpu import KNNConfig, build_index, query_knn

    X = rng.standard_normal((2048, 16)).astype(np.float32)
    Q = rng.standard_normal((2, 16)).astype(np.float32)
    cfg = KNNConfig(k=130, corpus_tile=2048, query_tile=2, backend="serial")
    res = query_knn(Q, build_index(X, cfg), cfg)
    d2 = ((Q[:, None, :].astype(np.float64) - X[None]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1)[:, :130]
    np.testing.assert_array_equal(np.asarray(res.ids), want)


def test_lane_bin_import_starts_once_and_lands():
    """The early import of the kernels: one thread a process however often
    the chunk programs' owners ask, and the module is there once it ends."""
    import sys

    from mpi_knn_tpu.ops import topk

    topk.start_lane_bin_import()
    thread = topk._lane_bin_import
    topk.start_lane_bin_import()
    assert topk._lane_bin_import is thread
    thread.join(timeout=60)
    assert not thread.is_alive() and "mpi_knn_tpu.ops.lane_bin" in sys.modules


def test_bypassed_calls_keep_the_full_width_path(rng):
    """2-D ids and k beyond the rule give the same values as ever."""
    d = rng.standard_normal((16, 2048)).astype(np.float32)
    ids = np.arange(2048, dtype=np.int32)
    ids2 = np.broadcast_to(ids, d.shape)
    assert lane_bin_flagged_share(d, 300) is None
    for k, i in [(10, ids2), (300, ids)]:
        got_d, got_i = smallest_k(jnp.asarray(d), jnp.asarray(i), k)
        want_d, want_i = _np_smallest_k(d, ids2, k)
        np.testing.assert_array_equal(np.asarray(got_d), want_d)
        np.testing.assert_array_equal(np.asarray(got_i), want_i)
