"""One kernel that walks a tile stack: the one-pass distance dot, its masks,
the row bound's test and the lane-bin insertion for every corpus tile of a
(T, c_tile, d) stack in ONE Pallas call (``ops/topk.py`` has the selection's
mechanism, ``ops/lane_bin.py`` the kernels this one shares its test and its
network with, ``backends/serial.py _merge_carried`` the scan it replaces
where ``ops/topk.py fused_scan_engages``). Every caller of that merge's
one-pass branch reaches it by the one rule (``backends/serial.py
fused_rule``): the serial scan and the serving batch programs over a
resident stack and, on the TPU, the XLA ring's rounds over the block that
has just arrived, inside the ring's checked ``shard_map`` (the outputs
carry the operands' varying axes, ``ops/lane_bin.py _out``).

What leaves the tile step with it. The XLA scan's step is a slice of the
tile, a dot fusion that writes the (q, c_tile) distance tile, the *bins*
kernel that reads it back, and the lists (q, depth·128 distances and ids)
crossing HBM both ways, every step. Here the grid walks the tiles: a tile is
fetched from the stack where it rests by its ``BlockSpec`` index, narrowed
to bf16 in the kernel a piece at a time (``chunk_groups`` column groups,
1024 columns of an 8192-column tile: one chunk of the bound's test; both
operands are bf16 numbers in this branch: lossless; a narrowing inside a
Mosaic kernel cannot be hoisted out of the loop as XLA hoisted the whole
stack's), and met with the whole query tile on the MXU (DEFAULT precision,
float32 accumulation). The tile's distances live in VMEM scratch and
nowhere else; the lists and the bound live in VMEM scratch from the first
tile to the last and are written to HBM once, by an explicit copy at the
last grid step.

Two more forms of the same kernel, by the shapes (``ops/topk.py
fused_scan_engages``). A width off the 128-lane grid rests on the device
ROWS-MINOR, as (T, d, c_tile) (:func:`rests_rows_minor`): the kernel takes
that view of the stack, which costs nothing, and a tile comes a piece
(d x 1024 columns) a grid step — the piece is the dot's K x N operand as
it lies. A query tile taller than the bound rides (4096 rows) is walked in
row blocks, a leading grid axis: each block its own lists, bound and
distances from the first tile to the last, the stack read once a block.

Same values as the scan it replaces, bit for bit on whole-number rows:
``max(x_sq - 2 xy + y_sq, 0)`` in ``pairwise_sq_l2``'s order (the query
side comes in as ``-2 x`` in bf16, which is exact, and ``a - b`` is
``a + (-b)``); ``mask_tile``'s masks (a padding or tombstoned id < 0, folded
with ``y_sq`` into one vector a piece: ``x + inf`` is ``inf``;
``exclude_zero`` by the pair's scale; ``exclude_self`` by id); the bound
taken anew at ``bound_refreshes``' tiles from the lists' lane minima as
``lane_bin_bound`` takes it (the k-th smallest with multiplicity: which of
two equal minima is knocked out first does not change the value); chunks
tested and inserted in ascending column order a row, so ties keep the
earlier column and the earlier tile ahead.

A third form, by the program (``backends/serial.py fused_screen_rule``):
the SCREENED scan of float32 rows that are no bf16 numbers, on the lane
grid under L2. The dot is ``high``'s three bf16 products as ONE
``dot_general`` of K = 3 d — the query side's two pieces made once a batch,
a tile's a piece at a time in the kernel, laid side by side along K so that
the MXU's accumulator takes the sum —, everything that says k says the
screen's k', and the lists keep SLOTS. Its values are within
``backends/serial.py screen_eps(..., fused=True)`` of the six-pass ones;
none of them is returned (``_finish_screened``).

A fourth, by the operands (``backends/serial.py fused_rule`` with
``filtered``): the MASKED scan of a tagged index. The predicate's words of
a tile, a bit a (query row, slot), come in as a block beside the tile and
a slot whose bit is clear reads +inf — in the exact re-test as one more
mask, and in the test beside the dot too, so that a chunk is marked for a
MATCHING value under the bound: the masked XLA scan's lists and count, bit
for bit, with no plane of the mask, no slice and copy of the tile, no
distance tile and no *bins* in a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_knn_tpu.ops.lane_bin import (
    _FINISH_ROWS,
    _I32_MAX,
    _INF,
    _LANES,
    _STRIP,
    _as_i32,
    _hit_place,
    _hit_scratch,
    _hit_words,
    _insert_hit_chunks,
    _interpret,
    _lanes_of,
    _out,
    _pack_hits,
    _row_block,
    _rows_of,
    chunk_groups,
)
from mpi_knn_tpu.ops.topk import _ZERO_RTOL_DEFAULT
from mpi_knn_tpu.types import INVALID_ID

# the rows of the id and norm planes a block holds: a (1, c_tile) block of
# a (T, c_tile) plane is no legal block (the second-minor block dimension
# is a multiple of 8 or the whole), so the block is the 8 tiles around the
# one wanted and the kernel picks its row
_PLANE_ROWS = 8
# VMEM beyond the kernel's own buffers that the call asks for
_VMEM_HEADROOM = 24 << 20


def _fused_scan_kernel(due_ref, qn_ref, xsq_ref, qid_ref, c_ref, ids_ref,
                       ysq_ref, *refs, k: int,
                       depth: int, groups: int, exclude_self: bool,
                       exclude_zero: bool, zero_eps: float, blocked: bool,
                       rows_minor: bool, widened: bool = False,
                       passes: int = 1, slots: bool = False,
                       ranged: bool = False, filtered: bool = False,
                       sift: bool = True):
    """A grid step of :func:`fused_scan`: tile t against a block of query
    rows — the whole query tile on the grid (tiles,); one of its row
    blocks where ``blocked``, the grid's leading axis, the block's lists,
    bound and distances starting anew at its first tile; where
    ``rows_minor`` one 1024-column piece of tile t, the grid's last axis,
    the tile's own work done at its first piece and the insertion at its
    last. ``due_ref`` (T,) int32 in scalar memory: the tiles that take the
    bound anew first. ``qn_ref`` (q, d) bf16 holds -2 x, ``xsq_ref`` (q,
    128) the query norms in every lane, ``qid_ref`` (q, 128) the query ids
    (read under ``exclude_self``); ``c_ref`` (1, c_tile, d) is tile t
    (``rows_minor``: (1, d, 1024), a piece of it as it rests);
    ``ids_ref`` / ``ysq_ref`` (8, c_tile) the planes' rows around t. The
    lists (``kd_ref`` / ``ki_ref``), the bound (``b_ref``), the tile's
    dots and then distances (``d_ref`` (q, c_tile)) and the chunks' bits
    (``bits_ref`` (q, 128)) are scratch.

    The step, in the order the vector unit can afford it. (1) A piece at
    a time (a chunk's columns), the dot on the MXU and, on its result as
    it comes, the bound's test WITHOUT the masks and the clamp: ``z = x_sq
    - 2 xy + y_sq'`` at or under the bound — two adds, a compare and an OR
    a vreg, in one basic block with the dot, so the two units overlap; the
    clamp cannot change the answer (a bound is never negative) and a mask
    only takes values away, so the chunks this marks hold every chunk that
    is to be inserted. (2) The marked chunks alone (one in twelve, later
    in a scan) get their distances made whole in place — the clamp, the
    zero and self masks — and are tested again, exactly. (3) The network
    over the chunks that passed, as *bins* runs it.

    ``ranged`` (range search, ``backends/range_scan.py``): one operand
    more, ``rad_ref`` (q, 128), in every lane of a row the largest value
    UNDER the row's radius. The bound starts there and is never taken
    anew, so (2) tests every value against the radius itself; what passes
    is inserted as ever — a lane keeps its ``depth`` smallest, so a lane
    that more than ``depth`` values pass loses the largest — and COUNTED:
    the exact test's answers are added up a row and a tile (``tc_ref`` (q,
    128), a tile a lane) and leave by a copy every 128 tiles and at the
    last (``tc_out`` (T / 128, q, 128)), so the caller knows of every row
    how many values passed in each tile, whatever the lists kept.

    ``filtered`` (a tagged index's masked scan, ``backends/serial.py
    filter_words``): one operand more, ``words_ref`` (1, q, c_tile / 32)
    int32, the predicate's words of tile t for the block's rows — slot
    ``c`` of the tile is bit ``c // (c_tile / 32)`` of word ``c % (c_tile
    / 32)``, so the 128 columns of a column group are ONE bit of 128
    lane-aligned words: a slice, an AND and a compare, no lane moves. A
    slot whose bit is clear reads +inf: in (2) as one more of
    ``mask_tile``'s masks and, where ``sift``, in (1) too — three vector
    operations a vreg more beside the dot, and the chunks marked are those
    that hold a MATCHING value under the bound, not any (under a sparse
    predicate the bound is loose and most chunks hold one of the
    others)."""
    lax, i32 = jax.lax, jnp.int32
    # a byte stack's kernel (``widened``) has one operand more, the (1, d)
    # offset its bytes are centred by; a float32 stack's has none
    refs = list(refs)
    mu_ref = refs.pop(0) if widened else None
    rad_ref = refs.pop(0) if ranged else None
    words_ref = refs.pop(0) if filtered else None
    kd_out, ki_out, n_ref = refs[:3]
    del refs[:3]
    tc_out = refs.pop(0) if ranged else None
    (kd_ref, ki_ref, b_ref, d_ref, bits_ref,
     idrow_ref, ycol_ref, work_ref, hit_ref, word_ref, cnt_ref,
     sem, *more) = refs
    # the three-pass form's scratch: a piece's bf16 pieces side by side;
    # where the lists keep slots, the tile's slot numbers
    cat_ref = more.pop(0) if passes == 3 else None
    slot_ref = more.pop(0) if slots else None
    # the ranged form's: the rows' counts by tile, a strip's by lane
    tc_ref, c16_ref = more if ranged else (None, None)
    q, c_tile = d_ref.shape
    strips = q // _STRIP
    piece = groups * _LANES
    n_chunks = c_tile // piece
    tile_axis = 1 if blocked else 0
    t = _as_i32(pl.program_id(tile_axis))
    f32 = d_ref.dtype
    strip_shape = (_STRIP, _LANES)
    # (the grid is read here, outside every conditional: the interpreter
    # knows it nowhere else)
    b = _as_i32(pl.program_id(0)) if blocked else None
    j = _as_i32(pl.program_id(tile_axis + 1)) if rows_minor else None

    def last_tile():
        return lax.eq(t, _as_i32(lax.sub(pl.num_programs(tile_axis), 1)))

    # (on the grid (tiles,) it is read where it always was, after the
    # insertion: that program's text is PR 37's)
    leaves = last_tile() if rows_minor else None

    def at_piece(which: int):
        """What a tile step does once, ahead of piece ``which`` of its
        dot: a tile of a rows-minor stack takes one grid step a piece,
        and the others pass it by."""
        if not rows_minor:
            return lambda body: body()
        return pl.when(lax.eq(j, i32(which)))

    @at_piece(0)
    def _():
        @pl.when(lax.eq(t, i32(0)))
        def _():
            kd_ref[...] = lax.full(kd_ref.shape, _INF, f32)
            ki_ref[...] = lax.full(ki_ref.shape, INVALID_ID, i32)
            if ranged:
                b_ref[...] = rad_ref[...]
                tc_ref[...] = lax.full(tc_ref.shape, 0, i32)
            else:
                b_ref[...] = lax.full(b_ref.shape, _INF, f32)
            if blocked:  # one count for the blocks together
                @pl.when(lax.eq(b, i32(0)))
                def _():
                    cnt_ref[0] = i32(0)
            else:
                cnt_ref[0] = i32(0)

        # (a radius is not taken anew: the ranged form holds no such pass)
        @(pl.when(lax.ne(due_ref[t], i32(0))) if not ranged
          else (lambda body: None))
        def _():
            # lane_bin_bound's value, from the lists where they are: the
            # k-th smallest of a row's lane minima, by k passes of row-min
            # and knock-out over a block of rows
            rows = work_ref.shape[0]
            lane = lax.broadcasted_iota(i32, (rows, _LANES), 1)
            big = lax.full(lane.shape, _I32_MAX, i32)
            inf = lax.full(lane.shape, _INF, f32)

            def row_min(x):
                return lax.expand_dims(lax.reduce_min(x, (1,)), (1,))

            def wide(col):
                return lax.broadcast_in_dim(col, lane.shape, (0, 1))

            def block(i, carry):
                r = pl.ds(pl.multiple_of(
                    lax.mul(_as_i32(i), i32(rows)), rows), rows)
                work_ref[...] = kd_ref[r, :_LANES]

                def one_pass(_, m):
                    d = work_ref[...]
                    m = row_min(d)
                    first = row_min(lax.select(lax.eq(d, wide(m)), lane, big))
                    work_ref[...] = lax.select(
                        lax.eq(lane, wide(first)), inf, d)
                    return m

                m = lax.fori_loop(
                    0, k, one_pass, lax.full((rows, 1), _INF, f32))
                b_ref[r, :] = lax.min(b_ref[r, :], wide(m))
                return carry

            lax.fori_loop(0, q // rows, block, 0)

        # the tile's row of the planes; what is per column in one vector:
        # y_sq, +inf where the id says padding or tombstone
        row = pl.ds(lax.rem(t, i32(_PLANE_ROWS)), 1)
        idrow_ref[...] = ids_ref[row, :]
        ycol_ref[...] = lax.select(
            lax.lt(idrow_ref[...], lax.full(idrow_ref.shape, 0, i32)),
            lax.full(ycol_ref.shape, _INF, f32), ysq_ref[row, :])
        if slots:
            # what the lists keep of a column: its place in the stack
            # viewed flat, from the grid's own index
            slot_ref[...] = lax.add(
                lax.broadcasted_iota(i32, slot_ref.shape, 1),
                lax.broadcast(lax.mul(t, i32(c_tile)), slot_ref.shape))

        bits_ref[...] = lax.full(bits_ref.shape, 0, i32)

    def misses(rows, group):
        """Where the predicate's bit is clear, (rows, 128) bool: the rows'
        slots of column group ``group`` of the tile."""
        per = i32(words_ref.shape[2] // _LANES)
        words = words_ref[0, rows, _lanes_of(lax.rem(group, per))]
        bit = lax.shift_left(i32(1), lax.div(group, per))
        return lax.eq(lax.bitwise_and(words, lax.broadcast(bit, words.shape)),
                      lax.full(words.shape, 0, i32))

    def dot_and_test(j, carry):
        j = _as_i32(j)
        cols = pl.ds(pl.multiple_of(lax.mul(j, i32(piece)), piece), piece)
        qn = qn_ref[...]
        rows = c_ref[0] if rows_minor else c_ref[0, cols, :]
        if widened:
            # bytes at rest: widened here, a piece at a time, and centred
            # by the whole-number offset — whole numbers of magnitude <=
            # 255, bf16 numbers every one: what a float32 stack holds
            rows = lax.sub(
                lax.convert_element_type(
                    lax.convert_element_type(rows, i32), f32),
                lax.broadcast_in_dim(mu_ref[...], rows.shape, (0, 1)))
        if passes == 3:
            # float32 rows that are no bf16 numbers: the piece's two bf16
            # pieces, the first CUT from the bits (the upper half of a
            # float32 IS a bfloat16: no conversion for a compiler to see
            # through), the second what the cut left, rounded; laid side
            # by side along K as (hi, lo, hi) against the query side's
            # (hi, hi, lo), so ONE dot of K = 3 d is hi.hi + hi.lo + lo.hi
            # — ``high``'s three products, summed in the MXU's accumulator
            width = rows.shape[1]
            hi = lax.bitcast_convert_type(lax.bitwise_and(
                lax.bitcast_convert_type(rows, i32),
                lax.full(rows.shape, -65536, i32)), f32)
            cat_ref[:, :width] = lax.convert_element_type(hi, jnp.bfloat16)
            cat_ref[:, width:2 * width] = lax.convert_element_type(
                lax.sub(rows, hi), jnp.bfloat16)
            cat_ref[:, 2 * width:] = cat_ref[:, :width]
            narrow = cat_ref[...]
        else:
            narrow = lax.convert_element_type(rows, jnp.bfloat16)
        m = lax.dot_general(
            qn,
            narrow,
            dimension_numbers=(((1,), (0 if rows_minor else 1,)), ((), ())),
            preferred_element_type=f32,
            precision=lax.Precision.DEFAULT,
        )
        d_ref[:, cols] = m
        xs, bound = xsq_ref[...], b_ref[...]
        under = None
        for g in range(groups):
            group = lax.add(lax.mul(j, i32(groups)), i32(g))
            ys = ycol_ref[:, _lanes_of(group)]
            z = lax.add(
                lax.add(xs, lax.slice(m, (0, g * _LANES),
                                      (q, (g + 1) * _LANES))),
                lax.broadcast_in_dim(ys, xs.shape, (0, 1)))
            if filtered and sift:
                z = lax.select(misses(slice(None), group),
                               lax.full(xs.shape, _INF, f32), z)
            le = lax.le(z, bound)  # NaN compares false
            under = le if under is None else lax.bitwise_or(under, le)
        bits_ref[...] = lax.bitwise_or(bits_ref[...], lax.select(
            under, lax.broadcast(lax.shift_left(i32(1), j), xs.shape),
            lax.full(xs.shape, 0, i32)))
        return carry

    if rows_minor:
        dot_and_test(j, 0)
    else:
        lax.fori_loop(0, n_chunks, dot_and_test, 0)

    def value(r, g):
        """The strip's distances of column group g: ``pairwise_sq_l2``'s
        arithmetic and ``mask_tile``'s masks, left in ``d_ref`` for the
        network."""
        lanes = _lanes_of(g)
        xs = xsq_ref[r, :]
        ys = lax.broadcast_in_dim(ycol_ref[:, lanes], strip_shape, (0, 1))
        v = lax.max(lax.add(lax.add(xs, d_ref[r, lanes]), ys),
                    lax.full(strip_shape, 0, f32))
        invalid = None
        if exclude_zero:
            # mask_tile's threshold for float32, by the pair's scale
            thresh = (lax.full(strip_shape, zero_eps, f32) if zero_eps > 0.0
                      else lax.mul(
                          lax.full(strip_shape, _ZERO_RTOL_DEFAULT, f32),
                          lax.add(xs, ys)))
            invalid = lax.le(v, thresh)
        if exclude_self:
            own = lax.eq(lax.broadcast_in_dim(
                idrow_ref[:, lanes], strip_shape, (0, 1)), qid_ref[r, :])
            invalid = own if invalid is None else lax.bitwise_or(invalid, own)
        if filtered:
            out = misses(r, g)
            invalid = out if invalid is None else lax.bitwise_or(invalid, out)
        if invalid is not None:
            v = lax.select(invalid, lax.full(strip_shape, _INF, f32), v)
        d_ref[r, lanes] = v
        return v

    def make_whole(s, carry):
        s = _as_i32(s)
        r = _rows_of(s)
        word, bit = _hit_place(s, n_chunks)
        marked = lax.bitwise_and(
            lax.shift_right_logical(hit_ref[word], bit),
            i32((1 << n_chunks) - 1))

        @pl.when(lax.ne(marked, i32(0)))
        def _():
            bound = b_ref[r, :]
            bits_ref[r, :] = lax.full(strip_shape, 0, i32)
            if ranged:
                c16_ref[...] = lax.full(strip_shape, 0, i32)

            def chunk_of(chunk, carry):
                chunk = _as_i32(chunk)

                @pl.when(lax.ne(lax.bitwise_and(
                    lax.shift_right_logical(marked, chunk), i32(1)), i32(0)))
                def _():
                    bit = lax.broadcast(lax.shift_left(i32(1), chunk),
                                        strip_shape)

                    def group(u, bits):
                        g = lax.add(lax.mul(chunk, i32(groups)), _as_i32(u))
                        # <=: a tie with the bound is kept
                        return lax.select(lax.le(value(r, g), bound), bit,
                                          bits)

                    def counted(u, bits_and_count):
                        # ``group``, and the values that passed added up
                        # lane by lane
                        bits, count = bits_and_count
                        g = lax.add(lax.mul(chunk, i32(groups)), _as_i32(u))
                        under = lax.le(value(r, g), bound)
                        return (lax.select(under, bit, bits), lax.add(
                            count, lax.convert_element_type(under, i32)))

                    if ranged:
                        bits, count = lax.fori_loop(
                            0, groups, counted,
                            (lax.full(strip_shape, 0, i32),
                             lax.full(strip_shape, 0, i32)), unroll=True)
                        c16_ref[...] = lax.add(c16_ref[...], count)
                        bits_ref[r, :] = lax.bitwise_or(bits_ref[r, :], bits)
                    else:
                        bits_ref[r, :] = lax.bitwise_or(
                            bits_ref[r, :], lax.fori_loop(
                                0, groups, group,
                                lax.full(strip_shape, 0, i32), unroll=True))

                return carry

            lax.fori_loop(0, n_chunks, chunk_of, 0)
            if ranged:
                # the strip's rows' counts of this tile, into the tile's
                # lane of the rows' plane
                row = lax.broadcast_in_dim(
                    lax.reduce_sum(c16_ref[...], (1,)), strip_shape, (0,))
                here = lax.eq(
                    lax.broadcasted_iota(i32, strip_shape, 1),
                    lax.broadcast(lax.rem(t, i32(_LANES)), strip_shape))
                tc_ref[r, :] = lax.add(tc_ref[r, :], lax.select(
                    here, row, lax.full(strip_shape, 0, i32)))

        return carry

    @at_piece(n_chunks - 1)
    def _():
        _pack_hits(
            lambda r: bits_ref[r, :], hit_ref, word_ref, strips, n_chunks)
        lax.fori_loop(0, strips, make_whole, 0)
        _pack_hits(
            lambda r: bits_ref[r, :], hit_ref, word_ref, strips, n_chunks)
        cnt_ref[0] = lax.add(cnt_ref[0], _insert_hit_chunks(
            slot_ref if slots else idrow_ref, d_ref, kd_ref, ki_ref, hit_ref,
            strips, n_chunks, groups, depth))

        if ranged:
            @pl.when(lax.bitwise_or(
                lax.eq(lax.rem(t, i32(_LANES)), i32(_LANES - 1)),
                last_tile()))
            def _():
                copy = pltpu.make_async_copy(
                    tc_ref, tc_out.at[lax.div(t, i32(_LANES))], sem.at[2])
                copy.start()
                copy.wait()
                tc_ref[...] = lax.full(tc_ref.shape, 0, i32)

        @pl.when(last_tile() if leaves is None else leaves)
        def _():
            n_ref[0, 0] = cnt_ref[0]
            if blocked:  # the block's rows of the lists
                rows = pl.ds(pl.multiple_of(lax.mul(b, i32(q)), q), q)
                outs = kd_out.at[rows, :], ki_out.at[rows, :]
            else:
                outs = kd_out, ki_out
            copies = [pltpu.make_async_copy(kd_ref, outs[0], sem.at[0]),
                      pltpu.make_async_copy(ki_ref, outs[1], sem.at[1])]
            for copy in copies:
                copy.start()
            for copy in copies:
                copy.wait()


def _bf16_cut(x: jax.Array) -> jax.Array:
    """A float32 array CUT to bfloat16 numbers, as float32: the upper half
    of the bits, by a mask. The first piece of the three-pass split; what
    the cut leaves, ``x - cut``, is exact in float32. (A ``float32 ->
    bfloat16 -> float32`` round trip is not used: the TPU compiler keeps
    such a trip in float32 inside a fusion, and the second piece would be
    zero.)"""
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)


def rests_rows_minor(d: int) -> bool:
    """Whether a (T, c_tile, d) float32 stack the rule admits
    (``ops/topk.py fused_scan_engages``: d a multiple of 8, c_tile of 128)
    rests ROWS-MINOR on the device, as (T, d, c_tile) — the rows on the
    lanes, d on the sublanes, nothing padded (784 = 98 x 8) — and the
    kernel takes its tiles in that form: every width off the 128-lane
    grid, at every tile count. There the view ``swapaxes(1, 2)`` is the
    bytes at rest under another shape and the compiler moves nothing:
    ``tests/test_pallas.py -k rest_layout`` reads the order in programs
    compiled for the chip over a grid of shapes, ``-k one_pass_rule`` the
    bitcast in the cells' own."""
    return d % _LANES != 0


def fused_scan_vmem_bytes(q: int, c_tile: int, d: int, depth: int,
                          itemsize: int = 4, passes: int = 1,
                          ranged: bool = False, filtered: bool = False) -> int:
    """The VMEM :func:`fused_scan` holds for a block of (q, d) query rows
    against (c_tile, d) corpus tiles of ``itemsize`` bytes an element
    (float32, or a byte stack's 1), in bytes: the lists, the
    bound and the chunks' bits, the tile's distances, what it fetches of
    the tile in its two buffers (the tile; of a rows-minor stack a piece
    of it) and a piece's bf16 copy — of a byte stack also the piece
    widened to float32 ahead of it, and the offset's row in its two
    buffers —, the query side in its two buffers, the
    planes' rows. What the engage rule weighs and ``vmem_limit_bytes`` is
    set from. ``passes`` 3 (the three-pass form, whose lists keep slots):
    a piece's copy is its bf16 pieces side by side, three widths, with
    the float32 piece they are cut from and what the cut left; the query
    side is three widths too; the tile's slot numbers are one row more.
    ``ranged``: the rows' radii in their two buffers and their counts by
    tile, three (q, 128) planes. ``filtered``: a tile's words of the
    predicate, (q, c_tile / 32), in their two buffers."""
    piece = chunk_groups(c_tile) * _LANES
    lists = 2 * q * depth * _LANES * 4
    words = _hit_words(q // _STRIP, c_tile // piece) * (_STRIP // 2)
    bound_and_bits = (2 * q + words) * _LANES * 4
    dists = q * c_tile * 4
    fetched = piece if rests_rows_minor(d) else c_tile
    stack = 2 * fetched * d * itemsize + piece * d * 2 * passes
    if itemsize == 1:
        stack += piece * d * 4 + 2 * _PLANE_ROWS * d * 4
    query = 2 * (q * d * 2 * passes + 2 * q * _LANES * 4)
    planes = 2 * 2 * _PLANE_ROWS * c_tile * 4 + 2 * c_tile * 4
    if passes == 3:
        stack += 2 * piece * d * 4
        planes += c_tile * 4
    work = _row_block(q, _FINISH_ROWS) * _LANES * 4
    if ranged:
        work += (3 * q + _STRIP) * _LANES * 4
    if filtered:
        work += 2 * q * (c_tile // 32) * 4
    return lists + bound_and_bits + dists + stack + query + planes + work


def fused_scan(q_x: jax.Array, q_ids: jax.Array, q_sq: jax.Array,
               tiles: jax.Array, tile_ids: jax.Array, tile_sqs: jax.Array,
               due, *, k: int, depth: int, exclude_self: bool,
               exclude_zero: bool, zero_eps: float, block: int,
               offset: jax.Array | None = None, screen: bool = False,
               under: jax.Array | None = None,
               words: jax.Array | None = None, sift: bool = True):
    """The carried scan of ``backends/serial.py _merge_carried`` over a
    whole stack, in its one-pass branch and under the row bound, as one
    kernel: ``q_x`` (q, d) float32 query rows that are bf16 numbers (q a
    multiple of 16), ``q_ids`` (q,), ``q_sq`` (q,) their squared norms;
    ``tiles`` (T, c_tile, d) float32 rows that are bf16 numbers,
    ``tile_ids`` / ``tile_sqs`` (T, c_tile); ``due`` (T,) bool, the tiles
    ahead of which the bound is taken anew (``bound_refreshes``). Returns
    ((q, depth·128) distances, (q, depth·128) ids, chunks inserted): what
    the scan's lists and its count end as (for rows that hold a NaN the
    count may differ: a NaN is under no bound, +inf is under +inf).

    ``block`` (``ops/topk.py fused_scan_engages``' answer): the rows the
    kernel holds at a time, q or q halved. A taller query tile is
    walked in blocks of that height, a leading axis of the grid: each
    block walks the stack from the first tile to the last with its own
    lists and bound, which start anew at its first tile and leave at its
    last (the next block's first tile is fetched meanwhile: the grid is
    one pipeline), and the stack is read once a block — from 481 rows a
    block's dot outlasts its tile's fetch. Rows do not meet in the lists,
    the bound or a chunk (16 rows), so the blocks return the rows of the
    whole tile's answer.

    A width off the lane grid (:func:`rests_rows_minor`): the kernel takes
    the stack as (T, d, c_tile), the form it rests in, a piece of a tile
    (d x 1024 columns) a grid step: two whole tiles of 784 columns, 51 MB,
    would not fit beside the distances.

    A BYTE stack (``tiles`` uint8, ``offset`` (d,) float32 the
    whole-number offset its rows are centred by; ``ops/topk.py
    fused_scan_engages`` admits it on the lane grid, where it rests
    row-major under (32, 128) tiles): a tile is fetched as bytes, a quarter
    of the float32 tile's, and a piece is widened and centred in VMEM ahead
    of its narrowing to bf16 — the values a float32 stack of the same rows
    holds, so the same lists. ``tile_sqs`` are the CENTRED rows' norms.

    ``screen`` (``backends/serial.py fused_screen_rule``): the SCREENED
    scan of that merge — float32 rows of any value on the lane grid,
    ranked by ``high``'s three bf16 products (the query side's two pieces
    made here, once; a tile's a piece at a time in the kernel), ``k`` the
    screen's k' and ``depth`` its lists'; the lists keep a column's SLOT in
    the stack viewed flat (``t * c_tile + column``), not its id, which is
    read for its sign (and ``exclude_self``) alone. Every value is within
    ``backends/serial.py screen_eps(..., fused=True)`` of the six-pass
    one.

    ``under`` (range search, ``backends/range_scan.py``): (q,) float32,
    for every row the largest value UNDER its radius. The bound is that
    from the first tile to the last (``due`` is not read), ``k`` answers
    for nothing, and a fourth value is returned: (T / 128 rounded up, q,
    128) int32, for every row the values at or under its bound by tile
    (tile ``t`` in plane ``t // 128``, lane ``t % 128``) — all of them,
    whatever the lists kept. The whole query tile a block, a stack on the
    lane grid.

    ``words`` (a tagged index's masked scan): (T, q, c_tile / 32) int32,
    for every tile the words of the query rows' predicate
    (``backends/serial.py filter_words``' bits: a bit a (row, slot),
    c_tile / 32 a multiple of 128). A tile's words are fetched beside it
    (once a tile where the grid walks its pieces; a row block takes its
    rows of them) and a slot whose bit is clear reads +inf, as the XLA
    step's ``keep`` plane makes it: the masked scan's lists and count. A
    float32 stack in the one-pass form. ``sift`` False leaves the bit out of the test beside
    the dot (``scripts/bench_ops.py --scan-step --tags`` reads both: the
    same lists either way)."""
    q, d = q_x.shape
    widened = tiles.dtype.itemsize == 1
    if widened != (offset is not None):
        raise ValueError("a byte stack comes with its offset, and no other")
    if screen and (widened or rests_rows_minor(d)):
        raise ValueError("the three-pass form takes float32 rows on the "
                         "lane grid")
    ranged = under is not None
    if ranged and (screen or block != q or rests_rows_minor(d)):
        raise ValueError("the ranged form takes the whole query tile a "
                         "block, one pass, a stack on the lane grid")
    passes = 3 if screen else 1
    n_tiles, c_tile, _ = tiles.shape
    filtered = words is not None
    if filtered and (widened or screen or ranged or c_tile % (32 * _LANES)
                     or words.dtype != jnp.int32
                     or words.shape != (n_tiles, q, c_tile // 32)):
        raise ValueError("the filtered form takes a float32 stack in one "
                         "pass and (T, q, c_tile / 32) int32 words, c_tile "
                         "/ 32 a multiple of 128")
    groups = chunk_groups(c_tile)
    piece = groups * _LANES
    width = depth * _LANES
    strips = block // _STRIP
    f32 = jnp.float32
    operands = (q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs)
    # -2 x: a power of two scales a bf16 number exactly, so the dot returns
    # -2 xy to the bit and ``x_sq - 2 xy`` is one add
    qn = q_x * -2.0
    if screen:
        # (hi, hi, lo) along K: the pieces the kernel's (hi, lo, hi) meet
        hi = _bf16_cut(qn.astype(f32))
        qn = jnp.concatenate([hi, hi, qn - hi], axis=1)
    qn = qn.astype(jnp.bfloat16)
    xsq = jnp.broadcast_to(q_sq.astype(f32)[:, None], (q, _LANES))
    qid = jnp.broadcast_to(q_ids.astype(jnp.int32)[:, None], (q, _LANES))
    blocked, rows_minor = block != q, rests_rows_minor(d)

    def index(of):
        """A ``BlockSpec`` index map from ``of(block, tile, piece)``, for
        the axes the grid has."""
        def index_map(*ids):
            ids = list(ids[:-1])  # the last is the prefetched ``due``
            b = ids.pop(0) if blocked else 0
            return of(b, ids[0], ids[1] if rows_minor else 0)

        return index_map

    rows = index(lambda b, t, j: (b, 0))
    plane = pl.BlockSpec((_PLANE_ROWS, c_tile),
                         index(lambda b, t, j: (t // _PLANE_ROWS, 0)))
    grid = (n_tiles,)
    if rows_minor:
        # the stack where it rests, under the shape it rests in; a grid
        # step a piece of a tile
        tiles = jnp.swapaxes(tiles, 1, 2)
        tile = pl.BlockSpec((1, d, piece), index(lambda b, t, j: (t, 0, j)))
        grid += (c_tile // piece,)
    else:
        # the tile where it rests in the stack, by its index
        tile = pl.BlockSpec((1, c_tile, d), index(lambda b, t, j: (t, 0, 0)))
    if blocked:
        grid = (q // block, *grid)
    # (the ranged form's names are given only where it is asked for: a
    # k-NN program's text does not move)
    extra = (dict(ranged=True) if ranged
             else dict(filtered=True) if filtered else {})
    planes = -(-n_tiles // _LANES)
    kd, ki, n, *counts = pl.pallas_call(
        functools.partial(
            _fused_scan_kernel, k=k, depth=depth, groups=groups,
            exclude_self=exclude_self, exclude_zero=exclude_zero,
            zero_eps=zero_eps, blocked=blocked, rows_minor=rows_minor,
            widened=widened, passes=passes, slots=screen, **extra,
            **(dict(sift=sift) if filtered else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block, passes * d), rows),
                pl.BlockSpec((block, _LANES), rows),
                pl.BlockSpec((block, _LANES), rows),
                tile, plane, plane,
                *([pl.BlockSpec((1, d), index(lambda b, t, j: (0, 0)))]
                  if widened else []),
                *([pl.BlockSpec((block, _LANES), rows)] if ranged else []),
                *([pl.BlockSpec((1, block, c_tile // 32),
                                index(lambda b, t, j: (t, b, 0)))]
                  if filtered else []),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                *([pl.BlockSpec(memory_space=pl.ANY)] if ranged else []),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, width), f32),
                pltpu.VMEM((block, width), jnp.int32),
                pltpu.VMEM((block, _LANES), f32),
                pltpu.VMEM((block, c_tile), f32),
                pltpu.VMEM((block, _LANES), jnp.int32),
                pltpu.VMEM((1, c_tile), jnp.int32),
                pltpu.VMEM((1, c_tile), f32),
                pltpu.VMEM((_row_block(block, _FINISH_ROWS), _LANES), f32),
                # the words of the tile's (strip, chunk) bits; the count
                *_hit_scratch(strips, c_tile // piece),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((3 if ranged else 2,)),
                *([pltpu.VMEM((piece, passes * d), jnp.bfloat16),
                   pltpu.VMEM((1, c_tile), jnp.int32)] if screen else []),
                *([pltpu.VMEM((block, _LANES), jnp.int32),
                   pltpu.VMEM((_STRIP, _LANES), jnp.int32)]
                  if ranged else []),
            ],
        ),
        out_shape=[
            _out((q, width), f32, *operands),
            _out((q, width), jnp.int32, *operands),
            _out((1, 1), jnp.int32, *operands),
            *([_out((planes, q, _LANES), jnp.int32, *operands)]
              if ranged else []),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            # the arithmetic above, and room for what Mosaic keeps of its
            # own (a piece's dot as a value, spills)
            vmem_limit_bytes=fused_scan_vmem_bytes(
                block, c_tile, d, depth, tiles.dtype.itemsize, passes,
                **extra)
            + _VMEM_HEADROOM,
        ),
        interpret=_interpret(),
    )(jnp.asarray(due, jnp.int32), qn, xsq, qid, tiles,
      tile_ids.astype(jnp.int32), tile_sqs.astype(f32),
      *([offset.astype(f32)[None, :]] if widened else []),
      *([jnp.broadcast_to(under.astype(f32)[:, None], (q, _LANES))]
        if ranged else []),
      *([words] if filtered else []))
    return (kd, ki, n[0, 0], *counts)
