"""Range search over the dense serial stack: for every query row EVERY live
corpus row at a squared L2 distance strictly under the row's radius — a
list of no fixed length, empty for most rows and thousands long for a few
— as one batch program of static shapes (``serve_chunk_range``; the engine
compiles it a row bucket beside the k-NN programs, ``serve/engine.py
get_range_executable``).

Only whole-number rows come here (``config.py _refuse_under_range``,
``serve/index.py refuse_range_build``: a byte stack, or a float32 stack
that holds the one-pass fact, at most 256 wide; query rows are checked to
be bytes at the door, ``require_byte_rows``), so every distance is a whole
number a float32 holds exactly, however it is summed: the centred rows'
norms and ``x_sq - 2 xy`` stay under 2^24 (256 x 255^2 = 16 646 400), the
dot's own partial sums are EVEN whole numbers under 2^25, which float32
holds too. ``d < r`` is therefore tested as ``d <= under`` with ``under``
the largest float32 below ``r`` (:func:`range_bound`), one compare in
every path, and the paths agree to the bit.

Three steps a query tile.

**The scan** (scope ``knn.scan_range``). Where ``ops/topk.py
fused_scan_engages`` admits the whole query tile (the cells' 1024-row
bucket) it is the fused kernel in its ranged form (``ops/fused_scan.py``):
the row's bound starts at its radius and is never taken anew, what passes
is inserted into the lane lists (``RANGE_DEPTH`` a lane) and COUNTED, a row
and a tile. Elsewhere (small buckets, narrow tiles) a scan of one-pass
tile steps counts alone and keeps no lists. Either way the step hands on
``counts`` — for every row the values under its radius in every tile,
exact — and from them ``n``, a row's true number of results.

**The finish** (scope ``knn.range_finish``). The lists sorted by (distance,
id); a row is *complete* when as many of its slots are under the radius as
it has results. A row with more than ``range_cap`` results is *refused*
(the caller answers it with an error that names it and its count: nothing
is ever cut). The answers leave flat: every row's results one after
another in row order (``lims`` = the running sum of ``n`` over the rows
not refused), a complete row's from its sorted lists.

**The overflow** (scope ``knn.range_overflow``): a row whose lists lost a
result (a lane of them took more than its depth), or that had no lists.
Its tiles with a count are fetched from the stack where it rests — B at a
time, never the stack —, every distance of the row against them is made
again in the direct form ``sum((q - c)^2)``, the results of a tile are
brought to its front by one sort and written at the row's offset plus the
counts of the tiles before: tile after tile in ascending order, so that a
tile's unused tail is overwritten by the next. At last the row's results
are sorted together. Complete whatever the law of the data: a row's cost
goes with the tiles that hold a result of it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.backends.serial import masked_dist_tile
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.distance import sq_norms, widen_rows
from mpi_knn_tpu.ops.topk import fused_scan_engages
from mpi_knn_tpu.parallel.partition import pad_cols
from mpi_knn_tpu.types import INVALID_ID

SCAN_SCOPE = "knn.scan_range"
FINISH_SCOPE = "knn.range_finish"
OVERFLOW_SCOPE = "knn.range_overflow"

# the lane lists' depth under a radius: 512 slots a row. A row of some
# dozens of results fits (a lane of 128 takes a fifth by chance only from
# about a hundred on); deeper lists cost the kernel's insertion, its VMEM
# and the finish's sort in proportion
RANGE_DEPTH = 4
# tiles of one overflow row fetched and ranked together
OVERFLOW_TILES = 16
# results of a batch that leave in the first of the two flat pieces (the
# host fetches the second only where a batch's results pass it)
HEAD_RESULTS = 1 << 18
_I32_MAX = np.iinfo(np.int32).max
_LANES = 128


def range_bound(radius):
    """The largest float32 strictly under ``radius``: ``d < radius`` is
    ``d <= range_bound(radius)`` for every float32 ``d``. numpy in, numpy
    out; a radius that is no positive finite number bounds nothing."""
    r = np.asarray(radius, dtype=np.float32)
    return np.where(np.isfinite(r) & (r > 0),
                    np.nextafter(r, np.float32(-np.inf)),
                    np.float32(-1.0)).astype(np.float32)


def require_byte_rows(queries: np.ndarray) -> None:
    """A range request's query rows are bytes — whole numbers in [0, 255],
    as the corpus's are: anything else would be rounded on its way into
    the one-pass dot, and a row near the radius needs the exact value."""
    q = np.asarray(queries)
    bad = ~((q == np.rint(q)) & (q >= 0) & (q <= 255)).all(axis=1)
    if bad.any():
        raise ValueError(
            f"range search takes whole-number query rows in [0, 255]; row "
            f"{int(np.argmax(bad))} holds another value (fractional rows "
            "near the radius need an exact finish that has no range form "
            "yet)")


def range_cfg(cfg: KNNConfig) -> KNNConfig:
    """``cfg`` as the range programs read it: the zero test by VALUE is
    exact (a whole-number distance is 0 or at least 1; the k-NN programs'
    relative threshold would hide true results a few units away)."""
    return cfg if cfg.zero_eps > 0.0 else cfg.replace(zero_eps=0.5)


def range_engages(q: int, c_tile: int, dim: int, itemsize: int) -> bool:
    """Whether the scan of a (q x c_tile) range step is the fused kernel's
    ranged form: the shapes' rule (``ops/topk.py fused_scan_engages``) at
    the lists' depth, the whole query tile a block, on the lane grid."""
    return dim % _LANES == 0 and fused_scan_engages(
        q, c_tile, dim, RANGE_DEPTH, itemsize) == q


def flat_sizes(q: int, cap: int) -> tuple[int, int]:
    """``(head, total)`` results of one query tile's two flat pieces."""
    total = q * cap
    return min(HEAD_RESULTS, total), total


def _kernel_scan(q_x, q_ids, q_sq, under, tiles, tile_ids, tile_sqs, offset,
                 cfg):
    from mpi_knn_tpu.ops.fused_scan import fused_scan

    q = q_x.shape[0]
    n_tiles = tiles.shape[0]
    kd, ki, _, planes = fused_scan(
        q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs,
        np.zeros(n_tiles, np.int32), k=1, depth=RANGE_DEPTH,
        exclude_self=cfg.exclude_self, exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps, block=q, offset=offset, under=under)
    n = jnp.sum(planes, axis=(0, 2), dtype=jnp.int32)

    def counts_of(r):  # (T,) of row r: a plane's lanes are 128 tiles
        return jax.lax.dynamic_index_in_dim(
            planes, r, axis=1, keepdims=False).reshape(-1)[:n_tiles]

    return (kd, ki), n, counts_of


def _counting_scan(q_x, q_ids, q_sq, under, tiles, tile_ids, tile_sqs,
                   offset, cfg):
    def step(_, tile):
        blk, blk_ids, blk_sq = tile
        d = masked_dist_tile(q_x, q_ids, q_sq, widen_rows(blk, offset),
                             blk_ids, blk_sq, cfg, True)
        return None, jnp.sum(d <= under[:, None], axis=1, dtype=jnp.int32)

    _, by_tile = jax.lax.scan(step, None, (tiles, tile_ids, tile_sqs))
    n = jnp.sum(by_tile, axis=0, dtype=jnp.int32)

    def counts_of(r):
        return jax.lax.dynamic_index_in_dim(
            by_tile, r, axis=1, keepdims=False)

    return None, n, counts_of


def _range_tile(q_x, q_ids, under, tiles, tile_ids, tile_sqs, offset,
                cfg: KNNConfig):
    """One query tile: ``(n (q,), head_d, head_i, rest_d, rest_i, counts
    (5,))`` — ``counts`` int32 ``[tile steps in the kernel, tile steps
    counted alone, overflow rows, refused rows, tile fetches of the
    overflow]``."""
    q, dim = q_x.shape
    n_tiles, c_tile = tiles.shape[:2]
    cap = int(cfg.range_cap)
    head, total = flat_sizes(q, cap)
    f32, i32 = jnp.float32, jnp.int32
    in_kernel = range_engages(q, c_tile, dim, tiles.dtype.itemsize)
    with jax.named_scope(SCAN_SCOPE):
        q_sq = sq_norms(q_x)
        lists, n, counts_of = (_kernel_scan if in_kernel else _counting_scan)(
            q_x, q_ids, q_sq, under, tiles, tile_ids, tile_sqs, offset, cfg)
    with jax.named_scope(FINISH_SCOPE):
        if lists is None:
            width = _LANES
            sorted_d = jnp.full((q, width), jnp.inf, f32)
            sorted_i = jnp.full((q, width), INVALID_ID, i32)
            slots = jnp.zeros(q, i32)
        else:
            kd, ki = lists
            width = kd.shape[1]
            hit = kd <= under[:, None]
            slots = jnp.sum(hit, axis=1, dtype=i32)
            sorted_d, sorted_i = jax.lax.sort(
                (jnp.where(hit, kd, jnp.inf),
                 jnp.where(hit, ki, _I32_MAX)), dimension=1, num_keys=2)
        refused = n > cap
        complete = (slots == n) & ~refused
        live = (n > 0) & ~refused
        at = jnp.cumsum(jnp.where(refused, 0, n)) - jnp.where(refused, 0, n)
        at = at.astype(i32)  # a row's first slot of the flat answer
        rows = jnp.arange(q, dtype=i32)
        order = jnp.sort(jnp.where(live, rows, q))  # live rows, ascending
        n_live = jnp.sum(live, dtype=i32)

    # room past the last result for the widest window written
    room = max(c_tile, width, cap)
    tile_at = jnp.arange(n_tiles, dtype=i32)
    b = OVERFLOW_TILES

    def from_lists(r, flat_d, flat_i, fetched):
        with jax.named_scope(FINISH_SCOPE):
            flat_d = jax.lax.dynamic_update_slice(
                flat_d, sorted_d[r], (at[r],))
            flat_i = jax.lax.dynamic_update_slice(
                flat_i, sorted_i[r], (at[r],))
        return flat_d, flat_i, fetched

    def from_stack(r, flat_d, flat_i, fetched):
        with jax.named_scope(OVERFLOW_SCOPE):
            by_tile = counts_of(r)
            before = (jnp.cumsum(by_tile) - by_tile).astype(i32)
            # the row's tiles with a result, ascending, then n_tiles
            held = jnp.concatenate([
                jnp.sort(jnp.where(by_tile > 0, tile_at, n_tiles)),
                jnp.full(b, n_tiles, i32)])
            n_held = jnp.sum(by_tile > 0, dtype=i32)
            x = q_x[r]

            def fetch(it, state):
                flat_d, flat_i = state
                which = jax.lax.dynamic_slice(held, (it * b,), (b,))
                real = which < n_tiles
                which = jnp.minimum(which, n_tiles - 1)
                # a tile by its index, where it rests: a gather of the b
                # at once is laid out anew by the v5e compiler, the WHOLE
                # stack copied in pieces ahead of it
                d, ids = [], []
                for j in range(b):
                    diff = widen_rows(jax.lax.dynamic_index_in_dim(
                        tiles, which[j], keepdims=False), offset) - x
                    d.append(jnp.sum(diff * diff, axis=-1))
                    ids.append(jax.lax.dynamic_index_in_dim(
                        tile_ids, which[j], keepdims=False))
                d, ids = jnp.stack(d), jnp.stack(ids)
                ok = real[:, None] & (ids >= 0) & (d <= under[r])
                if cfg.exclude_zero:
                    ok &= d > 0
                if cfg.exclude_self:
                    ok &= ids != q_ids[r]
                d, ids = jax.lax.sort(
                    (jnp.where(ok, d, jnp.inf),
                     jnp.where(ok, ids, _I32_MAX)), dimension=1, num_keys=2)
                for j in range(b):  # ascending: a tail is overwritten
                    where = jnp.where(
                        real[j], at[r] + before[which[j]], total)
                    flat_d = jax.lax.dynamic_update_slice(
                        flat_d, d[j], (where,))
                    flat_i = jax.lax.dynamic_update_slice(
                        flat_i, ids[j], (where,))
                return flat_d, flat_i

            flat_d, flat_i = jax.lax.fori_loop(
                0, (n_held + b - 1) // b, fetch, (flat_d, flat_i))
            # the row's results together, ascending, ties by the lower id
            own = jnp.arange(cap, dtype=i32) < n[r]
            d = jax.lax.dynamic_slice(flat_d, (at[r],), (cap,))
            ids = jax.lax.dynamic_slice(flat_i, (at[r],), (cap,))
            d, ids = jax.lax.sort(
                (jnp.where(own, d, jnp.inf), jnp.where(own, ids, _I32_MAX)),
                num_keys=2)
            flat_d = jax.lax.dynamic_update_slice(flat_d, d, (at[r],))
            flat_i = jax.lax.dynamic_update_slice(flat_i, ids, (at[r],))
        return flat_d, flat_i, fetched + n_held

    def one_row(state):
        j, flat_d, flat_i, fetched = state
        r = order[j]
        return (j + 1, *jax.lax.cond(
            complete[r], from_lists, from_stack, r, flat_d, flat_i, fetched))

    _, flat_d, flat_i, fetched = jax.lax.while_loop(
        lambda state: state[0] < n_live, one_row,
        (i32(0), jnp.full(total + room, jnp.inf, f32),
         jnp.full(total + room, INVALID_ID, i32), i32(0)))
    steps = i32(n_tiles)
    counts = jnp.stack([
        steps if in_kernel else i32(0), i32(0) if in_kernel else steps,
        jnp.sum(live & ~complete, dtype=i32), jnp.sum(refused, dtype=i32),
        fetched])
    return (n, flat_d[:head], flat_i[:head], flat_d[head:total],
            flat_i[head:total], counts)


def serve_chunk_range(
    q_tiles: jax.Array,  # (QT, q_tile, d) one padded, centred query batch
    qid_tiles: jax.Array,  # (QT, q_tile)
    under_tiles: jax.Array,  # (QT, q_tile) range_bound of the rows' radii;
    # negative for a padding row, which then has no result
    tiles: jax.Array,  # (T, c_tile, d) RESIDENT corpus tiles
    tile_ids: jax.Array,
    tile_sqs: jax.Array,
    onepass: jax.Array | None = None,  # held at the build; not read
    offset: jax.Array | None = None,  # (d,) of a BYTE stack
    *,
    cfg: KNNConfig,
):
    """One range batch against the resident stack (``serve_chunk``'s
    operands with the rows' bounds where the scratch was). Returns, a
    leading axis a query tile: ``n`` (QT, q_tile) every row's true number
    of results; the flat answers in two pieces, ``head_d`` / ``head_i``
    (QT, H) and ``rest_d`` / ``rest_i`` (QT, q_tile x range_cap - H) —
    within a tile the rows' results one after another in row order, a
    refused row (``n`` > ``range_cap``) none, each row's ascending by
    distance, ties by the lower id; and ``counts`` (QT, 5)
    (:func:`_range_tile`)."""
    del onepass
    cfg = range_cfg(cfg)
    q_tiles = pad_cols(q_tiles, tiles.shape[-1])
    return jax.lax.map(
        lambda a: _range_tile(*a, tiles, tile_ids, tile_sqs, offset, cfg),
        (q_tiles, qid_tiles, under_tiles))


def assemble(n: np.ndarray, head_d, head_i, rest, rows: int, cap: int):
    """The host's half: ``(lims (rows + 1,), dists, ids, refused)`` of a
    batch's first ``rows`` rows from what the program returned — ``n``
    (QT, q_tile), the head pieces (QT, H), and ``rest()`` -> (rest_d,
    rest_i), called only where a tile's results pass the head.
    ``refused``: ``[(row, count), ...]`` of the rows over ``cap``, which
    have no results here."""
    qt, q_tile = n.shape
    flat_n = n.reshape(-1)[:rows].astype(np.int64)
    over = flat_n > cap
    take = np.where(over, 0, flat_n)
    lims = np.concatenate([[0], np.cumsum(take)])
    # (padding rows have no result, so a tile's total is its real rows')
    per_tile = np.where(n > cap, 0, n).sum(axis=1)
    tails = None
    pieces_d, pieces_i = [], []
    for t in range(qt):
        want = int(per_tile[t])
        d, i = head_d[t][:want], head_i[t][:want]
        if want > head_d.shape[1]:
            tails = tails or rest()
            more = want - head_d.shape[1]
            d = np.concatenate([d, tails[0][t][:more]])
            i = np.concatenate([i, tails[1][t][:more]])
        pieces_d.append(d)
        pieces_i.append(i)
    refused = [(int(r), int(flat_n[r])) for r in np.nonzero(over)[0]]
    return (lims, np.concatenate(pieces_d), np.concatenate(pieces_i),
            refused)
