"""The fused scan (``ops/fused_scan.py``, ISSUE 37): one kernel that walks a
tile stack — the one-pass dot, the masks, the bound's test and *bins* of
every tile step — returns what the engaged scan of tile steps returns, bit
for bit on whole-number rows; the rule that engages it, by the shapes; the
counter that says it ran. Since ISSUE 40 also at widths off the lane grid
(the tile taken rows-minor, a piece a grid step) and with the query tile
walked in row blocks. The kernel body is interpreted here; where the
shape rule would keep a width or a height out, the tests force the kernel
in (and the bound onto the scan it is compared with). Since ISSUE 51 the
kernel has a THREE-PASS form for fractional float32 rows (the screened
scan's: k' for k, slots for ids), whose lists are held against the XLA
screened scan's and against float64. Since ISSUE 55 a FILTERED form: a
tagged index's words an operand, the lists and the count held against
the masked XLA scan's and against float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.backends import serial
from mpi_knn_tpu.obs.metrics import DIST_PATHS, DIST_STEPS, MetricsRegistry
from mpi_knn_tpu.ops.distance import sq_norms
from mpi_knn_tpu.ops.topk import fused_scan_engages, lane_bin_depth

K = 10
C_TILE = 1024  # one chunk a tile: the interpreter's time goes with it


def _case(q, d, tiles, c_tile=C_TILE, seed=0):
    """A whole-number stack and query tile that meet everything a merge
    can meet: tombstoned ids inside a tile and padding ids at the stack's
    end, queries that ARE corpus rows (a self match by id, a zero
    distance by value), ties at the bound (few distinct distances), a row
    whose neighbours share a lane (it fails the certificate), and a carry
    that is not empty."""
    rng = np.random.default_rng([seed, q, d, tiles])
    X = rng.integers(-6, 7, (tiles, c_tile, d)).astype(np.float32)
    X[2::3] += 16.0  # every third tile lies far off: the bound skips it
    X = X.reshape(tiles * c_tile, d)
    Q = rng.integers(-6, 7, (q, d)).astype(np.float32)
    ids = np.arange(tiles * c_tile, dtype=np.int32)
    # queries 8.. are corpus rows under their own ids; 16.. under others'
    rows = rng.choice(min(tiles, 2) * c_tile, size=16, replace=False)
    Q[8:24] = X[rows]
    q_ids = np.arange(q, dtype=np.int32) + tiles * c_tile
    q_ids[8:16] = ids[rows[:8]]
    # row 0: eight near neighbours in lane 5 of the first tile's groups
    Q[0] = 0.0
    lane = 5 + 128 * np.arange(8)
    X[lane] = 0.0
    X[lane, np.arange(8) % d] = 1.0
    X[lane[4:], (np.arange(4) + 1) % d] = 1.0
    ids[rng.choice(tiles * c_tile, size=5, replace=False)] = -1  # tombstones
    ids[-37:] = -1  # padding
    # an incoming carry: the answer of another small stack
    carry_d = np.sort(rng.integers(20, 60, (q, K)).astype(np.float32), axis=1)
    carry_i = rng.integers(10**6, 2 * 10**6, (q, K)).astype(np.int32)
    return (jnp.asarray(Q), jnp.asarray(q_ids),
            jnp.asarray(X.reshape(tiles, c_tile, d)),
            jnp.asarray(ids.reshape(tiles, c_tile)),
            jnp.asarray(carry_d), jnp.asarray(carry_i))


def _merge(monkeypatch, block, cfg, q_x, q_ids, tiles, tile_ids, cd, ci):
    """``merge_tiles_into_carry`` under a true one-pass verdict, as the
    fused kernel over row blocks of ``block`` rows or (None) as the scan
    of tile steps under the bound."""
    monkeypatch.setattr(serial, "fused_rule", lambda *a, **k: block)
    monkeypatch.setattr(serial, "lane_bin_bound_rides", lambda *a: True)

    @jax.jit
    def run(q_x, q_ids, tiles, tile_ids, cd, ci):
        return serial.merge_tiles_into_carry(
            q_x, q_ids, sq_norms(q_x), tiles, tile_ids,
            serial.stack_norms(tiles, "l2"), cd, ci, cfg, jnp.asarray(True))

    # (a fifth output is the certified screen's: none of these programs'
    # — they carry the one-pass branch)
    return jax.tree.map(
        np.asarray, run(q_x, q_ids, tiles, tile_ids, cd, ci)[:4])


@pytest.mark.parametrize("q,d,tiles,blocks", [
    *((q, d, 17, 1) for q in (64, 256, 1024) for d in (100, 128, 784)),
    # (the interpreter's time goes with the rows: refreshes at tiles 1, 2);
    # the all-kNN cell's tile, in the four blocks the rule gives it
    *((4096, d, 3, 4) for d in (100, 128, 784)),
    (64, 128, 1, 1), (256, 100, 1, 1), (1024, 128, 1, 1),
    (64, 128, 40, 1), (256, 100, 40, 1), (1024, 128, 40, 1),
    # four row blocks (ISSUE 40): a block's edge runs through the queries
    # that are corpus rows (8 .. 24: the self and zero masks on both sides
    # of it), every block has rows tied at their bound, and the lists,
    # the bound and the count start anew a block
    (64, 784, 17, 4), (64, 100, 17, 4), (256, 104, 17, 4), (64, 128, 17, 4),
    (64, 784, 1, 4), (128, 784, 40, 2),
])
def test_fused_scan_returns_what_the_scan_of_tile_steps_returns(
        monkeypatch, q, d, tiles, blocks):
    cfg = KNNConfig(k=K, query_tile=q, corpus_tile=C_TILE,
                    exclude_self=True, exclude_zero=True)
    assert lane_bin_depth(q, C_TILE, K) is not None
    case = _case(q, d, tiles)
    scan = _merge(monkeypatch, None, cfg, *case)
    fused = _merge(monkeypatch, q // blocks, cfg, *case)
    for name, a, b in zip(("vals", "ids", "rescanned", "chunks"), scan, fused):
        np.testing.assert_array_equal(a, b, err_msg=name)
    vals, ids, rescanned, chunks = fused
    assert rescanned  # row 0's neighbours share a lane
    assert chunks.sum() == tiles * (q // 16) and chunks[0] > 0
    assert (chunks[1] > 0) == (tiles > 2)  # the far tiles' chunks
    assert (ids >= 0).all()  # no tombstone, no padding row
    # a query that is a corpus row does not get that row: not under its own
    # id, not at distance zero
    assert (vals[8:24] > 0).all()


@pytest.mark.parametrize("exclude_self,exclude_zero,c_tile,d,blocks", [
    (False, False, C_TILE, 128, 1), (False, True, 2048, 128, 1),
    (True, False, 4096, 128, 1),
    (True, True, 8192, 128, 1),  # the cells' tile: eight chunks
    # a rows-minor tile's pieces, a grid step each (ISSUE 40)
    (False, False, 2048, 784, 1), (False, True, 4096, 200, 2),
    (True, False, 8192, 784, 4), (True, True, 8192, 784, 1),
])
def test_fused_scan_masks_and_several_chunks_a_tile(
        monkeypatch, exclude_self, exclude_zero, c_tile, d, blocks):
    q, tiles = 64, 9
    cfg = KNNConfig(k=K, query_tile=q, corpus_tile=c_tile,
                    exclude_self=exclude_self, exclude_zero=exclude_zero)
    case = _case(q, d, tiles, c_tile)
    scan = _merge(monkeypatch, None, cfg, *case)
    fused = _merge(monkeypatch, q // blocks, cfg, *case)
    for name, a, b in zip(("vals", "ids", "rescanned", "chunks"), scan, fused):
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert fused[3][1] > 0  # the far tiles' chunks


@pytest.mark.parametrize("q,c,d,block,why", [
    (1024, 8192, 128, 1024, "the bulk cell's 1024-row program"),
    (256, 8192, 128, 256, "from the height at which the bound rides"),
    (1024, 8192, 256, 1024, "a wider row on the lane grid"),
    (1024, 1024, 128, 1024, "a narrow tile"),
    (64, 8192, 128, None, "no bound rides a 64-row bucket's scan"),
    (4096, 8192, 128, 1024, "a 128 MiB tile in four blocks the bound rides"),
    (2048, 8192, 128, 1024, "memory: 64 MiB of distances; two blocks"),
    (1024, 8192, 1536, None, "memory: two 48 MiB buffers of the tile"),
    (1024, 8192, 100, None, "the sublane grid: (d, T, c) at 1224 tiles"),
    (1024, 8192, 784, 1024, "a width-784 stack rests as (T, d, c)"),
    (4096, 8192, 784, 1024, "the all-kNN cell's tile"),
    (1024, 8192, 192, 1024, "the filtered cell's width, without a predicate"),
    (4096, 8192, 1536, None, "memory at every height the bound rides"),
    (1032, 8192, 128, None, "the kernel walks whole strips of 16 rows"),
    (4112, 8192, 128, None, "16 x 257 rows: no block height divides them"),
    (4128, 8192, 128, None, "q is halved, not cut in thirds of 86 strips"),
    (1536, 8192, 128, 768, "a height the kernel takes whole, twice"),
    (512, 8192, 784, 512, "a 512-row program at a width off the lane grid"),
])
def test_the_shape_rule_of_the_fused_scan(q, c, d, block, why):
    depth = lane_bin_depth(q, c, K)
    assert depth is not None
    assert fused_scan_engages(q, c, d, depth) == block, why


@pytest.mark.parametrize("q,d,grid,view,why", [
    (1024, 128, "(24,)", False, "the bulk cell's: one block, a row-major "
     "tile by its index — the program PR 37 wrote"),
    (4096, 128, "(4, 24)", False, "row blocks lead the grid"),
    (1024, 784, "(24, 8)", True, "a rows-minor tile a piece a grid step"),
    (4096, 784, "(4, 24, 8)", True, "the all-kNN cell's: both"),
])
def test_the_grid_of_the_fused_scan_follows_the_shapes(q, d, grid, view, why):
    """One kernel, its grid by the shapes: (row blocks where the rule gives
    a block under q,) tiles (, a tile's pieces where the stack rests rows
    minor — there, and only there, the stack goes in under the view
    ``swapaxes(1, 2)``). Traced over abstract operands: nothing runs."""
    import re

    tiles, c = 24, 8192
    depth = lane_bin_depth(q, c, K)
    block = fused_scan_engages(q, c, d, depth)
    assert block == 1024

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    from mpi_knn_tpu.ops.fused_scan import fused_scan

    text = str(jax.make_jaxpr(lambda *operands: fused_scan(
        *operands, serial.bound_refreshes(tiles), k=K, depth=depth,
        exclude_self=True, exclude_zero=True, zero_eps=0.0, block=block))(
        arg((q, d), jnp.float32), arg((q,), jnp.int32),
        arg((q,), jnp.float32), arg((tiles, c, d), jnp.float32),
        arg((tiles, c), jnp.int32), arg((tiles, c), jnp.float32)))
    assert re.findall(r"grid=(\([^)]*\))", text) == [grid], why
    assert ("transpose[" in text) == view, why


@pytest.mark.parametrize("change,engages,why", [
    ({}, True, "the bulk cell's configuration"),
    ({"query_tile": 512}, False, "a program without the one-pass rule"),
    ({"metric": "cosine"}, False, "cosine keeps its program"),
    ({"precision_policy": "mixed"}, False, "rerank keeps its program"),
    ({"k": 200}, False, "k beyond the lane-bin rule"),
    ({"merge_schedule": "stream"}, False, "no carried lists"),
    ({"matmul_precision": "default"}, False, "one pass already"),
])
def test_which_programs_take_the_fused_scan(change, engages, why):
    cfg = KNNConfig(**{**dict(k=K, query_tile=1024, corpus_tile=8192),
                       **change})
    block = cfg.query_tile if engages else None
    assert serial.fused_rule(cfg, cfg.query_tile, 8192, 128) == block, why
    # under a checked shard_map (the ring's rounds) the same answer on
    # the TPU; off it the interpreter cannot run there and the scan stays
    assert serial.fused_rule(cfg, cfg.query_tile, 8192, 128, True) is None
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert serial.fused_rule(
            cfg, cfg.query_tile, 8192, 128, True) == block, why


@pytest.mark.parametrize("d,q_tile", [
    (128, 1024),
    # the all-kNN cell's form (ISSUE 40): a 4096-row query tile in four
    # blocks, a width off the lane grid
    (104, 4096),
])
def test_a_call_counts_its_fused_steps_and_matches_the_ring(d, q_tile):
    """A one-shot call over whole-number rows takes the kernel in every
    tile step and says so (``dist_steps``' fourth column, the counter's
    ``path="fused"``, counted a (query tile, corpus tile) whatever the
    blocks); fractional queries take the multi-pass scan of the same
    program; the ring's rounds over the same rows (a checked
    ``shard_map`` on four CPU devices: the per-tile program stays; with
    the kernel, ``tests/test_ring_deployment.py``) answer the same."""
    rng = np.random.default_rng(3)
    X = rng.integers(0, 200, (4096, d)).astype(np.float32)
    kw = dict(k=K, query_tile=q_tile, corpus_tile=1024)
    steps = 4096 // q_tile * 4
    res = all_knn(X, backend="serial", **kw)
    assert np.asarray(res.dist_steps).tolist() == [0, 0, 0, steps]
    assert np.asarray(res.bins_chunks).sum() == steps * (q_tile // 16)
    registry = MetricsRegistry()
    registry.count_dist_steps(res.dist_steps)
    counted = {p: registry.counter(DIST_STEPS, labels={"path": p}).value
               for p in DIST_PATHS}
    assert counted == {
        "onepass": 0, "multipass": 0, "cosine": 0, "fused": steps, "ip": 0,
        "u8": 0, "fused_screen": 0}
    ring = all_knn(X, backend="ring-overlap", num_devices=4, **kw)
    np.testing.assert_array_equal(
        np.asarray(ring.dists), np.asarray(res.dists))
    # (among equal distances the full-width selection may order ids
    # otherwise)
    assert (np.asarray(ring.ids) == np.asarray(res.ids)).mean() > 0.999
    frac = all_knn(X, queries=X[:q_tile] + 0.25, backend="serial", **kw)
    assert np.asarray(frac.dist_steps).tolist() == [0, 4, 0, 0]
    if q_tile == 4096:
        # no bound rides the multi-pass scan of a tile that tall: every
        # chunk is inserted
        assert np.asarray(frac.bins_chunks).tolist() == [4 * 256, 0]


# ---------------------------------------------------------------------------
# the filtered form (ISSUE 55): a predicate's words an operand of the kernel

F_TILE = 4096  # the narrowest tile whose words are whole vectors: 128 a row
FEW = 5  # slots a sparse tag lies on: fewer than k


def _tagged(q, tiles, tile_ids, seed=0):
    """Bitsets over a (tiles, F_TILE) stack as a tagged index keeps them
    (``serve/tags.py``: (F + 1, T, c_tile / 32) uint32, the last row all
    ones) and a query tile's bitset rows (q, 2) that meet every kind of
    predicate: no constraint (row 0, whose eight near neighbours share a
    lane: flagged by the certificate), a tag on ``FEW`` slots (rows 1 and
    9, the second a corpus row: fewer than k match, empty slots in the
    answer), a tag on no slot (row 2), two tags with no slot in common
    (row 3), one and two tags of every density elsewhere. The sparse
    tag's slots and the dense tags' hold tombstoned and padding ids."""
    rng = np.random.default_rng([seed, q, tiles])
    slots = tiles * F_TILE
    share = (0.5, 0.2, 0.05, 0.01)
    member = rng.random((len(share), slots)) < np.array(share)[:, None]
    dead = np.flatnonzero(np.asarray(tile_ids).reshape(-1) < 0)
    member[0, dead] = True  # a set bit over a tombstone, over the padding
    sparse = np.zeros(slots, dtype=bool)
    sparse[np.r_[rng.choice(slots, FEW, replace=False), dead[:2]]] = True
    apart = member[1] & ~member[0]
    planes = np.stack([*member, sparse, np.zeros(slots, bool), apart,
                       np.ones(slots, bool)])
    n = len(planes) - 1  # the row of ones: no constraint
    # slot c of a tile is bit c // W of word c % W
    w = F_TILE // 32
    bits = planes.reshape(len(planes), tiles, 32, w).astype(np.uint32)
    tag_bits = (bits << np.arange(32, dtype=np.uint32)[:, None]).sum(
        axis=2, dtype=np.uint32)
    q_tags = np.stack([rng.integers(0, 4, q), np.where(
        rng.random(q) < 0.5, n, rng.integers(0, 4, q))], axis=1)
    q_tags[0] = n, n
    q_tags[1] = q_tags[9] = 4, n
    q_tags[2] = 5, n
    q_tags[3] = 0, 6
    keep = planes[q_tags[:, 0]] & planes[q_tags[:, 1]]
    return (jnp.asarray(tag_bits), jnp.asarray(q_tags.astype(np.int32)),
            keep.reshape(q, tiles, F_TILE))


def _merge_filtered(monkeypatch, block, cfg, q_x, q_ids, tiles, tile_ids,
                    tag_bits, q_tags, sift=True):
    """:func:`_merge` with a predicate's words, into an empty carry: the
    filtered kernel over row blocks of ``block`` rows or (None) the masked
    scan of tile steps; ``sift`` False: the kernel with the bit left out
    of the test beside the dot."""
    from mpi_knn_tpu.ops import fused_scan as kernel

    monkeypatch.setattr(serial, "fused_rule", lambda *a, **k: block)
    monkeypatch.setattr(serial, "lane_bin_bound_rides", lambda *a: True)
    if not sift:
        whole = kernel.fused_scan
        monkeypatch.setattr(kernel, "fused_scan",
                            lambda *a, **kw: whole(*a, **kw, sift=False))

    @jax.jit
    def run(q_x, q_ids, tiles, tile_ids, tag_bits, q_tags):
        return serial.merge_tiles_into_carry(
            q_x, q_ids, sq_norms(q_x), tiles, tile_ids,
            serial.stack_norms(tiles, "l2"),
            *serial.init_topk(q_x.shape[0], cfg.k), cfg, jnp.asarray(True),
            serial.filter_words(tag_bits, q_tags))

    return jax.tree.map(
        np.asarray, run(q_x, q_ids, tiles, tile_ids, tag_bits, q_tags)[:4])


@pytest.mark.parametrize("q,d,tiles,blocks,sift", [
    # the filtered cell's buckets at its width (rows-minor: a tile's words
    # fetched once for its pieces) and on the lane grid (row-major)
    *((q, d, 3, 1, True) for q in (256, 512, 1024) for d in (192, 128)),
    # a taller query tile in row blocks: a block takes its rows of the words
    (4096, 128, 1, 4, True), (64, 192, 3, 4, True),
    # the bit left out of the test beside the dot: more chunks marked,
    # the same lists and the same count after the exact test
    (256, 192, 3, 1, False), (64, 128, 3, 2, False),
])
def test_filtered_fused_scan_returns_what_the_masked_scan_returns(
        monkeypatch, q, d, tiles, blocks, sift):
    cfg = KNNConfig(k=K, query_tile=q, corpus_tile=F_TILE,
                    exclude_self=True, exclude_zero=True)
    q_x, q_ids, stack, tile_ids, _, _ = _case(q, d, tiles, F_TILE)
    tag_bits, q_tags, keep = _tagged(q, tiles, tile_ids)
    case = (q_x, q_ids, stack, tile_ids, tag_bits, q_tags)
    np.testing.assert_array_equal(  # the packing the kernel reads
        np.asarray(serial.filter_keep(
            serial.filter_words(tag_bits, q_tags)[-1], F_TILE)), keep[:, -1])
    scan = _merge_filtered(monkeypatch, None, cfg, *case)
    fused = _merge_filtered(monkeypatch, q // blocks, cfg, *case, sift=sift)
    for name, a, b in zip(("vals", "ids", "rescanned", "chunks"), scan, fused):
        np.testing.assert_array_equal(a, b, err_msg=name)
    vals, ids, rescanned, chunks = fused
    # float64, straight from the planes (whole-number rows: exact)
    X = np.asarray(stack, np.float64).reshape(-1, d)
    Q = np.asarray(q_x, np.float64)
    d2 = (Q * Q).sum(1)[:, None] - 2.0 * Q @ X.T + (X * X).sum(1)[None]
    all_ids = np.asarray(tile_ids).reshape(-1)
    ok = (keep.reshape(q, -1) & (all_ids >= 0)[None] & (d2 > 0)
          & (all_ids[None] != np.asarray(q_ids)[:, None]))
    want = np.sort(np.where(ok, d2, np.inf), axis=1)[:, :K]
    np.testing.assert_array_equal(vals, want.astype(np.float32))
    assert ((ids < 0) == np.isinf(vals)).all()
    hit = ids >= 0
    assert ok[np.nonzero(hit)[0], ids[hit]].all()  # an id is its slot here
    assert rescanned  # rows short of k candidates, row 0's shared lane
    assert np.isfinite(vals[0]).all()  # no constraint
    matched = ok[[1, 9]].sum(axis=1)
    assert (matched < K).all() and (matched > 0).all()
    assert (np.isfinite(vals[[1, 9]]).sum(axis=1) == matched).all()
    assert np.isinf(vals[[2, 3]]).all()  # matched by nothing
    assert chunks.sum() == tiles * (q // 16) * (F_TILE // 1024)
    assert chunks[0] > 0


@pytest.mark.parametrize("change,q,c,d,block,why", [
    ({}, 256, 8192, 192, 256, "from the height the bound rides at"),
    ({}, 512, 8192, 192, 512, "the filtered cell's scan part of a batch"),
    ({}, 1024, 8192, 192, 1024, "a whole bucket in the scan regime"),
    ({}, 512, 8192, 128, 512, "on the lane grid too"),
    ({}, 4096, 8192, 128, 1024, "a taller tile in row blocks"),
    ({}, 64, 8192, 192, None, "no one-pass branch under 256 rows"),
    ({}, 128, 8192, 192, None, "no one-pass branch under 256 rows"),
    ({"dtype": "uint8"}, 512, 8192, 128, None, "a byte stack has no words"),
    ({}, 512, 2048, 192, None, "64 words a row: no whole vector"),
    ({}, 512, 4096, 192, 512, "128 words a row: one"),
    ({}, 512, 8192, 100, None, "the sublane grid, as without a predicate"),
    ({"matmul_precision": "default"}, 512, 8192, 192, None,
     "one pass already: no branch to take"),
    ({"precision_policy": "mixed"}, 512, 8192, 192, None, "no lists"),
])
def test_which_filtered_programs_take_the_fused_scan(change, q, c, d, block,
                                                     why):
    """``fused_rule(..., filtered=True)``: by the program and its operands,
    and the unfiltered answers as they were (512 rows: no branch)."""
    cfg = KNNConfig(**{**dict(k=K, query_tile=q, corpus_tile=c), **change})
    assert serial.fused_rule(cfg, q, c, d, filtered=True) == block, why
    if q < serial.ONEPASS_MIN_ROWS:
        assert serial.fused_rule(cfg, q, c, d) is None
    # the three-pass form stays unfiltered
    assert serial.screen_rule(cfg, 1024, c, 128, filtered=True) is None
    depth = lane_bin_depth(min(q, 1024), c, K)
    assert fused_scan_engages(q, c, d, depth, passes=3, filtered=True) is None


# ---------------------------------------------------------------------------
# the three-pass form (ISSUE 51): the screened scan of fractional rows


WIDE = 32  # k' at k = 10 (``backends/serial.py screen_width``)


def _fractional_case(q, d, tiles, dead_tile=None, seed=0):
    """Fractional rows in classes (near neighbours at close, distinct
    distances), ids that are no slot numbers, tombstones scattered and the
    last tile SHORT (it ends in padding); ``dead_tile``: a whole tile
    tombstoned. Queries 0 .. 15 are corpus rows under their own ids."""
    rng = np.random.default_rng([seed, q, d, tiles])
    n = tiles * C_TILE
    cen = rng.normal(size=(16, d))
    x = (cen[rng.integers(0, 16, n)] + 0.4 * rng.normal(size=(n, d))).astype(
        np.float32)
    qx = (cen[rng.integers(0, 16, q)] + 0.4 * rng.normal(size=(q, d))).astype(
        np.float32)
    ids = rng.permutation(n).astype(np.int32) + 7
    q_ids = np.full(q, -1, np.int32)
    rows = rng.choice(n - C_TILE, size=16, replace=False)
    qx[:16], q_ids[:16] = x[rows], ids[rows]
    ids[rng.choice(n, size=9, replace=False)] = -1
    ids[-41:] = -1
    x[-41:] = 0.0
    ids = ids.reshape(tiles, C_TILE)
    if dead_tile is not None:
        ids[dead_tile] = -1
    tiles_x = jnp.asarray(x.reshape(tiles, C_TILE, d))
    return (jnp.asarray(qx), jnp.asarray(q_ids), sq_norms(jnp.asarray(qx)),
            tiles_x, jnp.asarray(ids), serial.stack_norms(tiles_x, "l2"))


def _xla_screened_lists(case, cfg, due, depth):
    """The lists of the XLA screened scan (``_merge_carried``'s
    ``bounded``), step by step from its own pieces: the three-pass distance
    tile (float32's own dot on this backend), the bound taken anew where
    ``due``, *bins* under it with the iota plane's slots."""
    from mpi_knn_tpu.ops.lane_bin import (
        lane_bin_bound, lane_bin_insert, lane_bin_lists, lane_bin_no_bound)

    q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs = case
    q = q_x.shape[0]
    lists, bound, inserted = lane_bin_lists(q, depth), lane_bin_no_bound(q), 0
    for t in range(tiles.shape[0]):
        if due[t]:
            bound = jnp.minimum(bound, lane_bin_bound(lists, WIDE))
        dist = serial.masked_dist_tile(
            q_x, q_ids, q_sq, tiles[t], tile_ids[t], tile_sqs[t], cfg,
            screen=True)
        *lists, n = lane_bin_insert(
            lists, dist, t * C_TILE + jnp.arange(C_TILE, dtype=jnp.int32),
            depth, bound)
        inserted += int(n)
    return lists, inserted


@pytest.mark.parametrize("d,tiles,dead_tile,due,block,why", [
    (128, 3, None, None, 1024, "1024 x (3 x 1024) x 128, a short last tile"),
    (128, 4, 1, None, 1024, "a tombstoned tile"),
    (128, 6, None, [0, 1, 0, 1, 1, 1], 1024, "the bound anew at four tiles"),
    (256, 3, None, [0, 1, 1], 512, "a wider row, the query tile in two "
     "blocks"),
])
def test_three_pass_lists_are_the_xla_screened_scans(
        d, tiles, dead_tile, due, block, why):
    """The kernel's three-pass form against the XLA screened scan, list
    for list: a row's k' candidates are the same SLOTS as a set, every
    value within the re-derived ``screen_eps`` of the float64 value of
    the row its slot names (the kernel's dot really is three bf16 passes
    here: the interpreter multiplies the pieces), no dead column and no
    row under the query's own id among them, the chunk count the
    bounded scan's but for chunks that straddle a bound in the last
    bits."""
    from mpi_knn_tpu.ops.fused_scan import fused_scan
    from mpi_knn_tpu.ops.lane_bin import lane_bin_result

    q = 1024
    cfg = KNNConfig(k=K, query_tile=q, corpus_tile=C_TILE, exclude_self=True,
                    exclude_zero=True, center=False)
    depth = lane_bin_depth(q, C_TILE, WIDE)
    assert depth == 7
    case = _fractional_case(q, d, tiles, dead_tile)
    q_x, q_ids, q_sq, stack, ids, sqs = case
    due = serial.bound_refreshes(tiles) if due is None else np.array(
        due, bool)
    kd, ki, n = fused_scan(
        *case, due, k=WIDE, depth=depth, exclude_self=True,
        exclude_zero=False, zero_eps=0.0, block=block, screen=True)
    want_lists, want_n = _xla_screened_lists(case, cfg, due, depth)
    got = jax.tree.map(np.asarray, lane_bin_result((kd, ki), q, WIDE))
    want = jax.tree.map(np.asarray, lane_bin_result(want_lists, q, WIDE))
    np.testing.assert_array_equal(got[2], want[2], err_msg="lanes' flags")
    # float64: every pair's real value, dead columns and own ids masked
    flat, flat_ids = np.asarray(stack).reshape(-1, d), np.asarray(ids).ravel()
    x64, c64 = np.asarray(q_x, np.float64), flat.astype(np.float64)
    real = ((x64 ** 2).sum(-1)[:, None] - 2 * x64 @ c64.T
            + (c64 ** 2).sum(-1)[None, :])
    real[:, flat_ids < 0] = np.inf
    real[np.asarray(q_ids)[:, None] == flat_ids[None, :]] = np.inf
    eps = np.asarray(serial.screen_eps(
        "l2", d, q_x, q_sq, jnp.max(sqs), fused=True), np.float64)
    rows = np.arange(q)[:, None]
    assert (got[1] >= 0).all() and np.isfinite(real[rows, got[1]]).all()
    err = np.abs(got[0] - real[rows, got[1]])
    assert (err <= eps[:, None]).all(), (err / eps[:, None]).max()
    # the three passes are there: nearer than one pass could be (2^-8 of
    # 2 |q| |c| an element), and no float32 dot's exactness
    assert 1e-3 * eps.min() < err.max() < 0.2 * eps.min()
    same = np.array([set(a) == set(b) for a, b in zip(got[1], want[1])])
    # where the sets differ the (k'+1)-th real value is within the
    # kernel's error of the k'-th: either is the screen's answer
    order = np.sort(real, axis=1)
    assert same.mean() > 0.99, same.mean()
    assert ((order[:, WIDE] - order[:, WIDE - 1])[~same]
            <= 2 * err.max()).all()
    every = tiles * (q // 16)
    assert abs(int(n) - want_n) <= 0.02 * every and 0 < int(n) <= every
    if dead_tile is not None:  # under a finite bound +inf passes no test
        assert int(n) <= every - q // 16


def test_three_pass_rule_and_vmem():
    """The three-pass form's own engage rule (``fused_scan_engages`` with
    ``passes=3``): float32 on the lane grid, with ITS buffers counted —
    the lists at depth 7, the query side and a piece's bf16 copy three
    widths wide — under the kernel's 64 MiB; the one-pass answers are
    what they were."""
    from mpi_knn_tpu.ops.fused_scan import fused_scan_vmem_bytes
    from mpi_knn_tpu.ops.topk import _FUSED_VMEM_BYTES

    depth = lane_bin_depth(1024, 8192, WIDE)
    one = fused_scan_vmem_bytes(1024, 8192, 128, depth)
    three = fused_scan_vmem_bytes(1024, 8192, 128, depth, passes=3)
    # two more widths of the query side (two buffers) and of a piece's
    # copy, the float32 piece and what the cut left, the slots' row
    assert three - one == (2 * 2 * 1024 * 128 * 2 + 2 * 1024 * 128 * 2
                           + 2 * 1024 * 128 * 4 + 8192 * 4)
    assert three < _FUSED_VMEM_BYTES
    for q, c, d, block in [(1024, 8192, 128, 1024), (4096, 8192, 128, 1024),
                           (1024, 8192, 256, 512), (1024, 8192, 1536, None),
                           (1024, 8192, 100, None), (1024, 8192, 784, None),
                           (64, 8192, 128, None)]:
        assert fused_scan_engages(
            q, c, d, lane_bin_depth(max(q, 1024), c, WIDE), 4,
            passes=3) == block, (q, c, d)
    assert fused_scan_engages(1024, 8192, 128, depth, 1, passes=3) is None
    cfg = KNNConfig(k=K, query_tile=1024, corpus_tile=8192)
    for change, facts, block in [
            ({}, {}, 1024), ({"metric": "cosine"}, {}, None),
            ({"metric": "ip"}, {}, None), ({"matmul_precision": "high"}, {},
                                           None),
            ({}, {"branch": True}, None), ({}, {"filtered": True}, None),
            ({}, {"varying": True}, None)]:
        assert serial.fused_screen_rule(
            cfg.replace(**change), 1024, 8192, 128, **facts) == block
    assert serial.screen_rule(cfg, 1024, 8192, 1536) == WIDE
    assert serial.fused_screen_rule(cfg, 1024, 8192, 1536) is None
    assert serial.fused_screen_rule(cfg, 512, 8192, 128) is None
