"""The two kernels of the lane-bin selection (``ops/topk.py`` has the
mechanism, the engage rule and the fallback): *bins*, which thins a wide
distance tile to a few hundred candidates a row, and *finish*, which takes
the k smallest of those and certifies that nothing dropped could have
belonged. Selection-only Pallas kernels — no dot in either — over blocks of
the tile that XLA's matmul fusion writes; Mosaic on the TPU, the same
bodies interpreted elsewhere, so CPU tests run what the chip runs. Imports
nothing of ``ops/topk.py``, which imports this module when it first selects
from a wide tile (pallas is ~0.8 s to import)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_knn_tpu.types import INVALID_ID

_INF = jnp.inf
_LANES = 128  # a vreg's lane count: column groups are lane-aligned slices
# the rows a bins strip holds in registers: 2 vregs a value, so two
# independent compare-exchange chains interleave in the VLIW schedule
_STRIP = 16
_BLOCK_ROWS = 128  # row block of the bins kernel (128 x 8192 f32: 4 MiB)
# row block of the finish kernel: each pass is a chain of two cross-lane
# reductions that only the block's other rows can fill (128-row blocks ran
# two thirds slower at 1024 rows, 512-row four times; 32-row pieces six
# times: PERF.md §6, PR 27)
_FINISH_ROWS = 256
_BLOCK_COLS = 8192  # widest column block of the bins kernel
_I32_MAX = jnp.iinfo(jnp.int32).max

# The kernel bodies are written in lax primitives: a Python operator or a
# jnp function on a tracer (``v < kept``, ``s * 16``, ``jnp.where``) is a
# nested jit to trace and lower, and the column groups and the finish's
# passes are walked by loops instead of unrolled. Tracing and lowering run
# in Python at every process start — a persistent compilation cache does
# not skip them — and the unrolled jnp form cost 5 s of set-up a program on
# the chip's host, the rolled form with operators still 0.9 s (PERF.md §6,
# PR 27).


def _interpret() -> bool:
    """Mosaic on the TPU, the same kernel bodies interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _row_block(q: int, most: int) -> int:
    """The largest block height <= ``most`` that divides q (q % 16 == 0)."""
    return next(b for b in (256, 128, 64, 32, _STRIP)
                if b <= most and q % b == 0)


def _out(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel output's type: under a ``shard_map`` that checks varying
    axes (the ring's), it varies over every mesh axis an operand varies
    over; elsewhere the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _plain(x: jax.Array) -> jax.Array:
    """``x`` as a value computed in the kernel. Under a checked ``shard_map``
    jax 0.9.0 leaves the operands' varying axes on a kernel's refs
    (``AbstractRef.update_vma`` does nothing) while whatever the body
    computes has none, so a loop whose carry starts as a ref's contents
    would change type in its first step. A bitcast to the same type moves
    nothing and gives the read the type of the loop's own values."""
    return jax.lax.bitcast_convert_type(x, x.dtype)


def _lane_bin_kernel(ids_ref, d_ref, *lists, depth: int):
    """One (rows, cols) block of the tile: insert its cols/128 column groups
    into the per-(row, lane) sorted lists of ``depth`` that the two output
    blocks hold across the column axis of the grid. A strip of rows keeps
    its 2·depth list entries in registers while the groups stream through
    ``depth`` compare-exchange stages; the candidate's id rides the same
    selects. ``lists`` is the two output blocks, after the two blocks of the
    incoming lists where the call carries them (aliased to the outputs):
    the row block's first column step then starts from those."""
    kd_ref, ki_ref = lists[-2:]
    rows, cols = d_ref.shape
    groups = cols // _LANES
    unroll = next(u for u in (4, 2, 1) if groups % u == 0)
    slots = [slice(j * _LANES, (j + 1) * _LANES) for j in range(depth)]
    lax = jax.lax

    first = pl.program_id(1)

    @pl.when(lax.eq(first, first.dtype.type(0)))
    def _():
        if len(lists) == 4:
            kd_ref[...] = lists[0][...]
            ki_ref[...] = lists[1][...]
        else:
            kd_ref[...] = lax.full(kd_ref.shape, _INF, kd_ref.dtype)
            ki_ref[...] = lax.full(ki_ref.shape, INVALID_ID, ki_ref.dtype)

    def strip(s, carry):
        r = pl.ds(pl.multiple_of(lax.mul(s, s.dtype.type(_STRIP)), _STRIP),
                  _STRIP)

        def insert(chunk, kept):
            kept_d, kept_i = list(kept[:depth]), list(kept[depth:])
            for u in range(unroll):
                g = lax.add(lax.mul(chunk, chunk.dtype.type(unroll)),
                            chunk.dtype.type(u))
                lanes = pl.ds(
                    pl.multiple_of(lax.mul(g, g.dtype.type(_LANES)), _LANES),
                    _LANES)
                v = d_ref[r, lanes]
                vi = lax.broadcast_in_dim(
                    ids_ref[:, lanes], v.shape, (0, 1))
                for j in range(depth):
                    # strict <: among equal values the earlier group (and
                    # the earlier tile's, in carried lists) stays ahead;
                    # NaN compares false and is never kept
                    lt = lax.lt(v, kept_d[j])
                    kept_d[j], v = (lax.select(lt, v, kept_d[j]),
                                    lax.select(lt, kept_d[j], v))
                    kept_i[j], vi = (lax.select(lt, vi, kept_i[j]),
                                     lax.select(lt, kept_i[j], vi))
            return (*kept_d, *kept_i)

        kept = lax.fori_loop(
            0, groups // unroll, insert,
            (*(_plain(kd_ref[r, sl]) for sl in slots),
             *(_plain(ki_ref[r, sl]) for sl in slots)),
        )
        for j, sl in enumerate(slots):
            kd_ref[r, sl] = kept[j]
            ki_ref[r, sl] = kept[depth + j]
        return carry

    lax.fori_loop(0, rows // _STRIP, strip, 0)


@jax.named_scope("bins")
def lane_bin_candidates(dists: jax.Array, ids: jax.Array, depth: int,
                        lists=None):
    """Reduce a (q, c) tile to (q, depth·128) candidates with no sort, no
    gather and no (q, c) id plane: view the columns as c/128 groups of 128
    lanes and, for every (row, lane), keep the ``depth`` smallest of its
    c/128 values in sorted order by inserting group after group through
    ``depth`` compare-exchange stages on the VPU, the candidate's global id
    (``ids`` (c,)) carried through the same selects. A selection-only Pallas
    kernel over (rows, cols) blocks of the distance tile that XLA's matmul
    fusion writes: XLA itself splits the unrolled network into over a
    hundred fusions (PERF.md §6, PR 27). ``q`` a multiple of 16, ``c`` of 128,
    ``depth`` below c/128.

    ``lists`` — ((q, depth·128) distances, (q, depth·128) int32 ids), what
    an earlier call returned — is what the lists start from in place of
    (+inf, ``INVALID_ID``): the tile is inserted into them, so a scan that
    carries them over the tiles of a stack ends with every (row, lane)'s
    ``depth`` smallest of the WHOLE stack (``backends/serial.py
    merge_tiles_into_carry``). They are aliased to the outputs: a caller
    that lets them die with the call has them updated in place.

    Returns ((q, depth·128) distances, (q, depth·128) ids); columns
    [j·128, (j+1)·128) hold every lane's (j+1)-th smallest, so the last 128
    are what the certificate reads."""
    q, c = dists.shape
    rows = _row_block(q, _BLOCK_ROWS)
    cols = next(
        w for w in range(min(c, _BLOCK_COLS), 0, -_LANES) if c % w == 0
    )
    out_block = pl.BlockSpec((rows, depth * _LANES), lambda i, j: (i, 0))
    ids = ids.astype(jnp.int32)[None, :]
    lists = tuple(lists or ())
    return pl.pallas_call(
        functools.partial(_lane_bin_kernel, depth=depth),
        grid=(q // rows, c // cols),
        in_specs=[
            pl.BlockSpec((1, cols), lambda i, j: (0, j)),
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            *(out_block for _ in lists),
        ],
        out_specs=[out_block, out_block],
        out_shape=[
            _out((q, depth * _LANES), dists.dtype, ids, dists, *lists),
            _out((q, depth * _LANES), jnp.int32, ids, dists, *lists),
        ],
        input_output_aliases={2: 0, 3: 1} if lists else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
    )(ids, dists, *lists)


def _lane_bin_finish_kernel(cd_ref, ci_ref, od_ref, oi_ref, flag_ref,
                            work_ref, acc_d_ref, acc_i_ref, *, k):
    """k passes of row-min / lowest-id-among-the-minima / knock-out over a
    block of candidate rows (what ``ops/pallas_knn._k_smallest_sweep`` does
    over a whole tile): no sort and no gather. Then the certificate. Each
    pass works on the whole block at once (see ``_FINISH_ROWS``), knocks
    out in place in ``work_ref`` and leaves its answer in lane j of the two
    (rows, 128) accumulators (hence k <= 128), so the passes are one loop
    body, not k copies of it to trace and lower."""
    lax = jax.lax

    def row_min(x):
        return lax.expand_dims(lax.reduce_min(x, (1,)), (1,))

    def to_width(col, like):
        return lax.broadcast_in_dim(col, like.shape, (0, 1))

    rows, width = cd_ref.shape
    inf = lax.full((rows, width), _INF, cd_ref.dtype)
    big = lax.full((rows, width), _I32_MAX, jnp.int32)
    col_inf = lax.full((rows, 1), _INF, cd_ref.dtype)
    col_invalid = lax.full((rows, 1), INVALID_ID, jnp.int32)
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    work_ref[...] = cd_ref[...]
    acc_d_ref[...] = lax.full(lane.shape, _INF, cd_ref.dtype)
    acc_i_ref[...] = lax.full(lane.shape, INVALID_ID, jnp.int32)

    def one_pass(j, _):
        d = work_ref[...]
        ids = ci_ref[...]
        m = row_min(d)
        is_min = lax.eq(d, to_width(m, d))
        mid = row_min(lax.select(is_min, ids, big))
        here = lax.eq(lane, lax.broadcast(
            lax.convert_element_type(j, jnp.int32), lane.shape))
        acc_d_ref[...] = lax.select(here, to_width(m, lane), acc_d_ref[...])
        # slots that hold +inf are by definition invalid
        acc_i_ref[...] = lax.select(here, to_width(
            lax.select(lax.lt(m, col_inf), mid, col_invalid), lane),
            acc_i_ref[...])
        hit = lax.bitwise_and(is_min, lax.eq(ids, to_width(mid, ids)))
        work_ref[...] = lax.select(hit, inf, d)
        return m

    m = lax.fori_loop(0, k, one_pass, col_inf)
    od_ref[...] = acc_d_ref[:, :k]
    oi_ref[...] = acc_i_ref[:, :k]
    # m is now tau, the k-th smallest candidate. Every element the bins
    # dropped from a lane is >= that lane's last kept value, so if each of
    # those is >= tau the candidates' k smallest ARE the tile's. Flagged:
    # some lane's last kept value is < tau — or tau is not finite (fewer
    # than k finite candidates: rare, and the full-width path then also
    # decides what a NaN row returns, as it always has).
    last = cd_ref[:, width - _LANES:]
    below = lax.convert_element_type(lax.lt(last, to_width(m, last)), jnp.int32)
    short = lax.expand_dims(lax.reduce_max(below, (1,)), (1,))
    finite = lax.convert_element_type(lax.lt(m, col_inf), jnp.int32)
    flag_ref[...] = lax.max(short, lax.sub(lax.full_like(finite, 1), finite))


@jax.named_scope("finish")
def lane_bin_finish(cand_d: jax.Array, cand_i: jax.Array, k: int):
    """k smallest of the narrow candidate rows, ascending, with their ids
    (equal distances: the lower id first; ids are distinct wherever the
    distance is finite, as a tile's are), and the exactness certificate.
    ``k`` at most 128. Returns ((q, k) vals, (q, k) ids, (q,) flagged)."""
    q, w = cand_d.shape
    rows = _row_block(q, _FINISH_ROWS)
    cand = pl.BlockSpec((rows, w), lambda i: (i, 0))
    out = pl.BlockSpec((rows, k), lambda i: (i, 0))
    vals, out_ids, flagged = pl.pallas_call(
        functools.partial(_lane_bin_finish_kernel, k=k),
        grid=(q // rows,),
        in_specs=[cand, cand],
        out_specs=[out, out, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[
            _out((q, k), cand_d.dtype, cand_d, cand_i),
            _out((q, k), jnp.int32, cand_d, cand_i),
            _out((q, 1), jnp.int32, cand_d, cand_i),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, w), cand_d.dtype),
            pltpu.VMEM((rows, _LANES), cand_d.dtype),
            pltpu.VMEM((rows, _LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=_interpret(),
    )(cand_d, cand_i)
    return vals, out_ids, flagged[:, 0] != 0


def lane_bin_lists(q: int, depth: int, dtype=jnp.float32):
    """Empty lists for ``q`` rows at depth ``depth``: ((q', depth·128)
    +inf, (q', depth·128) ``INVALID_ID``), q' = q up to whole strips. What
    a scan that carries the lists starts from."""
    shape = (q + -q % _STRIP, depth * _LANES)
    return (jnp.full(shape, _INF, dtype),
            jnp.full(shape, INVALID_ID, jnp.int32))


def lane_bin_insert(lists, dists: jax.Array, ids: jax.Array, depth: int):
    """``lists`` (:func:`lane_bin_lists`, or what an earlier call returned)
    with the (q, c) tile ``dists`` (ids (c,)) inserted, updated in place
    where they die with the call: the one thing a tile step of a carried
    scan selects. None: a tile's own lists, from empty."""
    pad = -dists.shape[0] % _STRIP  # the kernels walk whole strips
    if pad:  # zero rows flag nothing
        dists = jnp.pad(dists, ((0, pad), (0, 0)))
    return tuple(lane_bin_candidates(dists, ids, depth, lists))


def lane_bin_result(lists, q: int, k: int):
    """What the lists hold for their first ``q`` rows: ((q, k) vals
    ascending, (q, k) ids, (q,) flagged) — exact for every row that is not
    flagged, over everything that was inserted."""
    vals, out_ids, flagged = lane_bin_finish(*lists, k)
    return vals[:q], out_ids[:q], flagged[:q]


def lane_bin_select(dists: jax.Array, ids: jax.Array, k: int, depth: int):
    """Bins, finish and certificate of a (q, c) tile: ((q, k) vals ascending,
    (q, k) ids, (q,) flagged); exact for every row that is not flagged."""
    return lane_bin_result(
        lane_bin_insert(None, dists, ids, depth), dists.shape[0], k)
