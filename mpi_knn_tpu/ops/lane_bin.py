"""The two kernels of the lane-bin selection (``ops/topk.py`` has the
mechanism, the engage rule and the fallback): *bins*, which thins a wide
distance tile to a few hundred candidates a row, and *finish*, which takes
the k smallest of those and certifies that nothing dropped could have
belonged. Selection-only Pallas kernels — no dot in either — over blocks of
the tile that XLA's matmul fusion writes; Mosaic on the TPU, the same
bodies interpreted elsewhere, so CPU tests run what the chip runs. Imports
nothing of ``ops/topk.py``, which imports this module when it first selects
from a wide tile (pallas is ~0.8 s to import).

Where a scan carries the lists over the tiles of a stack, a ROW BOUND can
ride beside them (``lane_bin_bound`` / ``lane_bin_candidates_under``; the
rule, ``ops/topk.py lane_bin_bound_rides``): (1) the bound is one value a
row that the row's FINAL k-th smallest cannot pass — the k-th smallest of
any k values the row has seen, here of its lane minima — taken anew from
the lists at a few of the scan's steps, and it only falls; (2) *bins* asks
of every chunk of the tile (a strip of 16 rows x 1024 columns) whether any
value is at or under its row's bound, a load, a compare and a select a vreg
in place of 25 operations, and runs the compare-exchange network, the id
broadcast and the lists' load and store for the chunks that hold one; (3)
the answer does not change: a dropped value is above a bound that is at or
above the final k-th smallest, hence larger than every list entry at or
under the final bound, so those entries keep their slots; the entries under
a bound never become fewer once it is taken (a lane evicts one only for a
smaller one), so k of them are there at the end and the k smallest
candidates, tau and the lanes' last values under tau — ``(vals, ids,
flagged)`` of ``lane_bin_result`` — are what they are without the test;
only slots above the final bound may hold other values; (4) the kernel
counts the chunks it inserted (``knn_select_bins_chunks_total``)."""

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


def _insert_group(ids_ref, d_ref, r, lanes, kept_d: list, kept_i: list):
    """One column group of the strip ``r`` through the compare-exchange
    stages of its lists (``kept_d`` / ``kept_i``, one vreg pair a slot,
    updated in place), the candidate's id riding the same selects."""
    lax = jax.lax
    v = d_ref[r, lanes]
    vi = lax.broadcast_in_dim(ids_ref[:, lanes], v.shape, (0, 1))
    for j in range(len(kept_d)):
        # strict <: among equal values the earlier group (and the earlier
        # tile's, in carried lists) stays ahead; NaN compares false and is
        # never kept
        lt = lax.lt(v, kept_d[j])
        kept_d[j], v = (lax.select(lt, v, kept_d[j]),
                        lax.select(lt, kept_d[j], v))
        kept_i[j], vi = (lax.select(lt, vi, kept_i[j]),
                         lax.select(lt, kept_i[j], vi))


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
                _insert_group(ids_ref, d_ref, r, lanes, kept_d, kept_i)
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


def _bits_set(x: jax.Array) -> jax.Array:
    """The set bits of a non-negative int32 scalar, by halving sums: Mosaic
    counts the bits of vectors only."""
    lax, i32 = jax.lax, jnp.int32

    def fold(x, shift, mask):
        return lax.add(lax.bitwise_and(x, i32(mask)), lax.bitwise_and(
            lax.shift_right_logical(x, i32(shift)), i32(mask)))

    for shift, mask in ((1, 0x55555555), (2, 0x33333333), (4, 0x0F0F0F0F),
                        (8, 0x00FF00FF), (16, 0x0000FFFF)):
        x = fold(x, shift, mask)
    return x


# the column groups a chunk of the testing kernel holds: a strip of 16 rows
# x 8 groups is 16 vregs to test (a load, a compare and a select each)
# against 400 vector operations to insert at depth 5 (4 and 16 groups read
# the same step on the chip: PERF.md §6, PR 35)
_CHUNK_GROUPS = 8


def _rows_of(s):
    """The rows of strip ``s`` of a block."""
    return pl.ds(pl.multiple_of(
        jax.lax.mul(s, s.dtype.type(_STRIP)), _STRIP), _STRIP)


def _lanes_of(g):
    """The lanes of column group ``g`` of a block."""
    return pl.ds(pl.multiple_of(
        jax.lax.mul(g, g.dtype.type(_LANES)), _LANES), _LANES)


def _as_i32(i):  # a loop index is int64 under jax_enable_x64
    return jax.lax.convert_element_type(i, jnp.int32)


def _hit_place(s, n_chunks: int):
    """(word, first bit) of strip ``s``'s chunks in the hit words: the
    chunks of 32 // n_chunks strips share a 32-bit word."""
    per_word = 32 // n_chunks
    return (jax.lax.div(s, s.dtype.type(per_word)),
            jax.lax.mul(jax.lax.rem(s, s.dtype.type(per_word)),
                        s.dtype.type(n_chunks)))


def _hit_words(strips: int, n_chunks: int) -> int:
    """The 32-bit words that hold a block's (strip, chunk) bits."""
    return -(-strips // (32 // n_chunks))


def _bound_bits(value, b_ref, n_chunks: int, chunk_groups: int):
    """The bound's test of a strip, for :func:`_pack_hits`: rows -> (16,
    128) int32 with bit ``chunk`` set wherever ``value(rows, group)`` (the
    strip's (16, 128) values of a column group) of one of the chunk's
    ``chunk_groups`` groups is at or under the row's bound (``b_ref``
    (rows, 128)): a compare and a select a vreg."""
    lax, i32 = jax.lax, jnp.int32

    def strip_bits(r):
        bound = b_ref[r, :]
        zero = lax.full(bound.shape, 0, i32)

        def chunk_of(chunk, bits):
            chunk = _as_i32(chunk)

            def group(u, bit):
                g = lax.add(lax.mul(chunk, i32(chunk_groups)), _as_i32(u))
                # <=: a tie with the bound is kept; NaN compares false
                return lax.select(lax.le(value(r, g), bound),
                                  lax.broadcast(lax.shift_left(i32(1), chunk),
                                                bound.shape), bit)

            return lax.bitwise_or(bits, lax.fori_loop(
                0, chunk_groups, group, zero, unroll=True))

        return lax.fori_loop(0, n_chunks, chunk_of, zero, unroll=True)

    return strip_bits


def _pack_hits(strip_bits, hit_ref, word_ref, strips: int, n_chunks: int):
    """A block's (strip, chunk) answers from vector to scalar memory:
    ``strip_bits(rows)`` is (16, 128) int32 with bit ``chunk`` set in any
    element that says yes; the answers are packed a bit a (strip, chunk)
    into 32-bit words, gathered a word's eight rows in ``word_ref``
    (:func:`_hit_words` x 8, 128: a select a word a strip in registers was
    2500 vector operations a 64-strip block, PERF.md §6, PR 37), and the
    words cross to scalar memory (``hit_ref``) by ONE chain of rotations
    and one transfer a word. (A reduction a chunk cost as much as the
    network it was to save, and a chain a strip more: PERF.md §6, PR 35.)"""
    lax, i32 = jax.lax, jnp.int32
    half = _STRIP // 2
    n_words = _hit_words(strips, n_chunks)
    word_ref[...] = lax.full(word_ref.shape, 0, i32)

    def test(s, carry):
        s = _as_i32(s)
        bits = strip_bits(_rows_of(s))
        bits = lax.bitwise_or(lax.slice(bits, (0, 0), (half, _LANES)),
                              lax.slice(bits, (half, 0), (_STRIP, _LANES)))
        word, bit = _hit_place(s, n_chunks)
        rows = pl.ds(pl.multiple_of(lax.mul(word, i32(half)), half), half)
        word_ref[rows, :] = lax.bitwise_or(
            word_ref[rows, :],
            lax.shift_left(bits, lax.broadcast(bit, bits.shape)))
        return carry

    lax.fori_loop(0, strips, test, 0)
    # OR over a word's elements by rotations, the words stacked so that a
    # rotation is one operation for them all (a chain a word was 320
    # operations to trace and lower, twice a kernel: 0.4 s of a program's
    # set-up on the chip's host, PERF.md §6, PR 37). Rows rotate across the
    # words' borders, and a word's last row still gathers its own rows alone
    x = word_ref[...]
    for axis, size in ((1, _LANES), (0, half)):
        shift = size // 2
        while shift:
            x = lax.bitwise_or(x, pltpu.roll(x, shift, axis))
            shift //= 2
    for w in range(n_words):
        row = (w + 1) * half - 1
        hit_ref[w] = lax.squeeze(
            lax.slice(x, (row, 0), (row + 1, 1)), (0, 1))


def _hit_scratch(strips: int, n_chunks: int):
    """The scratch of :func:`_pack_hits` for a block of ``strips`` strips:
    the words in scalar memory and their rows in vector memory."""
    n_words = _hit_words(strips, n_chunks)
    return [pltpu.SMEM((n_words,), jnp.int32),
            pltpu.VMEM((n_words * (_STRIP // 2), _LANES), jnp.int32)]


def _insert_hit_chunks(ids_ref, d_ref, kd_ref, ki_ref, hit_ref, strips: int,
                       n_chunks: int, chunk_groups: int, depth: int):
    """What follows :func:`_pack_hits`: a strip with a bit set in
    ``hit_ref`` loads its lists (``kd_ref`` / ``ki_ref``), runs the
    compare-exchange network over the chunks of ``d_ref`` whose bits are
    set (ids from ``ids_ref`` (1, cols)), and stores them; the other strips
    and chunks cost a scalar test. Returns the chunks inserted (int32)."""
    lax, i32 = jax.lax, jnp.int32
    # a chunk's groups in one basic block where they are eight: the chains
    # of so short a loop would not fill the VLIW schedule
    unroll = next(u for u in (8, 4, 2, 1) if chunk_groups % u == 0)
    slots = [slice(j * _LANES, (j + 1) * _LANES) for j in range(depth)]

    def strip(s, inserted):
        s = _as_i32(s)
        r = _rows_of(s)
        word, bit = _hit_place(s, n_chunks)
        bits = lax.bitwise_and(
            lax.shift_right_logical(hit_ref[word], bit),
            i32((1 << n_chunks) - 1))

        def chunk_of(chunk, carry):
            chunk = _as_i32(chunk)

            @pl.when(lax.ne(lax.bitwise_and(
                lax.shift_right_logical(bits, chunk), i32(1)), i32(0)))
            def _():
                def insert(step, kept):
                    # the network is traced once and unrolled as it is
                    # lowered: the same block, an eighth of the tracing
                    def group(u, kept):
                        kept_d, kept_i = (list(kept[:depth]),
                                          list(kept[depth:]))
                        g = lax.add(
                            lax.mul(chunk, i32(chunk_groups)),
                            lax.add(lax.mul(_as_i32(step), i32(unroll)),
                                    _as_i32(u)))
                        _insert_group(ids_ref, d_ref, r, _lanes_of(g),
                                      kept_d, kept_i)
                        return (*kept_d, *kept_i)

                    return lax.fori_loop(0, unroll, group, kept, unroll=True)

                # the lists go through memory a chunk: vregs carried through
                # a conditional cost several times this load and store
                kept = lax.fori_loop(
                    0, chunk_groups // unroll, insert,
                    (*(_plain(kd_ref[r, sl]) for sl in slots),
                     *(_plain(ki_ref[r, sl]) for sl in slots)),
                )
                for j, sl in enumerate(slots):
                    kd_ref[r, sl] = kept[j]
                    ki_ref[r, sl] = kept[depth + j]

            return carry

        @pl.when(lax.ne(bits, i32(0)))
        def _():
            lax.fori_loop(0, n_chunks, chunk_of, 0)

        return lax.add(inserted, _bits_set(bits))

    return lax.fori_loop(0, strips, strip, i32(0))


def _lane_bin_bounded_kernel(ids_ref, d_ref, b_ref, ld_ref, li_ref, kd_ref,
                             ki_ref, n_ref, hit_ref, word_ref, *, depth: int,
                             chunk_groups: int):
    """:func:`_lane_bin_kernel` under a row bound: ``b_ref`` (rows, 128)
    holds, in every lane of a row, a value that the row's final k-th
    smallest cannot pass. First the test (:func:`_bound_bits`: a load, a
    compare and a select a vreg; :func:`_pack_hits`), then the network
    over the chunks that hold a value at or under the bound
    (:func:`_insert_hit_chunks`).
    ``n_ref`` (1, 1), scalar memory: the chunks inserted, summed over the
    grid (a sum outside the kernel is one more XLA operation a step: 0.8 us
    of a 57 us step, PERF.md §6, PR 35)."""
    rows, cols = d_ref.shape
    strips = rows // _STRIP
    n_chunks = cols // _LANES // chunk_groups
    lax = jax.lax
    block, first = pl.program_id(0), pl.program_id(1)

    @pl.when(lax.eq(first, first.dtype.type(0)))
    def _():
        kd_ref[...] = ld_ref[...]
        ki_ref[...] = li_ref[...]

    @pl.when(lax.eq(lax.add(block, first), first.dtype.type(0)))
    def _():
        n_ref[0, 0] = jnp.int32(0)

    _pack_hits(
        _bound_bits(lambda r, g: d_ref[r, _lanes_of(g)], b_ref, n_chunks,
                    chunk_groups),
        hit_ref, word_ref, strips, n_chunks)
    n_ref[0, 0] = lax.add(n_ref[0, 0], _insert_hit_chunks(
        ids_ref, d_ref, kd_ref, ki_ref, hit_ref, strips, n_chunks,
        chunk_groups, depth))


def _tile_blocks(q: int, c: int) -> tuple[int, int]:
    """The (rows, cols) block of the bins kernels over a (q, c) tile."""
    return _row_block(q, _BLOCK_ROWS), next(
        w for w in range(min(c, _BLOCK_COLS), 0, -_LANES) if c % w == 0)


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
    rows, cols = _tile_blocks(q, c)
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


def chunk_groups(c: int) -> int:
    """The column groups a chunk of the testing kernel holds for c-column
    tiles: the divisor of a column block's groups nearest to
    :data:`_CHUNK_GROUPS` (8 for every width that is a multiple of 1024)."""
    groups = _tile_blocks(_STRIP, c)[1] // _LANES
    return min((g for g in range(1, groups + 1) if groups % g == 0),
               key=lambda g: max(g, _CHUNK_GROUPS) / min(g, _CHUNK_GROUPS))


def lane_bin_chunks(q: int, c: int) -> int:
    """The chunks the testing kernel makes of a (q, c) tile: what a call's
    inserted and skipped chunks add up to."""
    return (q + -q % _STRIP) // _STRIP * (c // _LANES // chunk_groups(c))


def lane_bin_no_bound(q: int, dtype=jnp.float32) -> jax.Array:
    """The bound that skips nothing, (q', 128) +inf for the rows of
    :func:`lane_bin_lists`: what a scan starts from."""
    return jnp.full((q + -q % _STRIP, _LANES), _INF, dtype)


@jax.named_scope("bins")
def lane_bin_candidates_under(bound: jax.Array, dists: jax.Array,
                              ids: jax.Array, depth: int, lists):
    """:func:`lane_bin_candidates` into ``lists`` under a row bound:
    ``bound`` (q, 128) holds in every lane of a row an upper bound on the
    row's FINAL k-th smallest value (the k-th smallest of any k values the
    row has seen is one: :func:`lane_bin_bound`). A chunk — a strip of 16
    rows x :func:`chunk_groups` column groups — goes through the network
    only if it holds a value at or under its row's bound. Returns
    (distances, ids, inserted): the lists and, int32, the chunks that were
    inserted, of :func:`lane_bin_chunks`.

    Every value the test drops is above a bound that is at or above the
    final k-th smallest, so it is larger than every list entry at or under
    the final bound and changes no rank among them: those entries sit in
    the same slots as without the test, and (vals, ids, flagged) of
    :func:`lane_bin_result` are the same (``ops/topk.py``); slots above the
    final bound may hold other values."""
    q, c = dists.shape
    rows, cols = _tile_blocks(q, c)
    out_block = pl.BlockSpec((rows, depth * _LANES), lambda i, j: (i, 0))
    ids = ids.astype(jnp.int32)[None, :]
    operands = (ids, dists, bound, *lists)
    kd, ki, inserted = pl.pallas_call(
        functools.partial(_lane_bin_bounded_kernel, depth=depth,
                          chunk_groups=chunk_groups(c)),
        grid=(q // rows, c // cols),
        in_specs=[
            pl.BlockSpec((1, cols), lambda i, j: (0, j)),
            pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
            pl.BlockSpec((rows, _LANES), lambda i, j: (i, 0)),
            out_block, out_block,
        ],
        out_specs=[out_block, out_block,
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            _out((q, depth * _LANES), dists.dtype, *operands),
            _out((q, depth * _LANES), jnp.int32, *operands),
            _out((1, 1), jnp.int32, *operands),
        ],
        scratch_shapes=_hit_scratch(
            rows // _STRIP, cols // _LANES // chunk_groups(c)),
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=_interpret(),
    )(*operands)
    return kd, ki, inserted[0, 0]


def _lane_bin_finish_kernel(cd_ref, ci_ref, od_ref, oi_ref, flag_ref,
                            work_ref, acc_d_ref, acc_i_ref, *, k):
    """k passes of row-min / lowest-id-among-the-minima / knock-out over a
    block of candidate rows: no sort and no gather. Then the certificate. Each
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


def _finish(cand_d: jax.Array, cand_i: jax.Array, k: int, w: int):
    """The finish kernel over the first ``w`` columns of the candidate
    rows: ((q, k) vals, (q, k) ids, (q, 1) flags)."""
    q = cand_d.shape[0]
    rows = _row_block(q, _FINISH_ROWS)
    cand = pl.BlockSpec((rows, w), lambda i: (i, 0))
    out = pl.BlockSpec((rows, k), lambda i: (i, 0))
    return pl.pallas_call(
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


@jax.named_scope("finish")
def lane_bin_finish(cand_d: jax.Array, cand_i: jax.Array, k: int):
    """k smallest of the narrow candidate rows, ascending, with their ids
    (equal distances: the lower id first; ids are distinct wherever the
    distance is finite, as a tile's are), and the exactness certificate.
    ``k`` at most 128. Returns ((q, k) vals, (q, k) ids, (q,) flagged)."""
    vals, out_ids, flagged = _finish(cand_d, cand_i, k, cand_d.shape[1])
    return vals, out_ids, flagged[:, 0] != 0


@jax.named_scope("bound")
def lane_bin_bound(lists, k: int) -> jax.Array:
    """(q, 128), in every lane of a row the k-th smallest of the row's lane
    minima (the lists' first 128 columns; +inf while fewer than k lanes
    hold a value): k of the values the row has seen are at or under it, so
    the row's final k-th smallest is too. The finish kernel over one
    column group instead of ``depth``: the k-th smallest of ALL the
    candidates is the same value unless two of the k smallest share a lane,
    and is never larger."""
    vals = _finish(*lists, k, _LANES)[0]
    return jnp.broadcast_to(vals[:, k - 1:], (vals.shape[0], _LANES))


def lane_bin_lists(q: int, depth: int, dtype=jnp.float32):
    """Empty lists for ``q`` rows at depth ``depth``: ((q', depth·128)
    +inf, (q', depth·128) ``INVALID_ID``), q' = q up to whole strips. What
    a scan that carries the lists starts from."""
    shape = (q + -q % _STRIP, depth * _LANES)
    return (jnp.full(shape, _INF, dtype),
            jnp.full(shape, INVALID_ID, jnp.int32))


def lane_bin_insert(lists, dists: jax.Array, ids: jax.Array, depth: int,
                    bound: jax.Array | None = None):
    """``lists`` (:func:`lane_bin_lists`, or what an earlier call returned)
    with the (q, c) tile ``dists`` (ids (c,)) inserted, updated in place
    where they die with the call: the one thing a tile step of a carried
    scan selects. None: a tile's own lists, from empty.

    ``bound`` ((q', 128), the lists' rows: :func:`lane_bin_bound`) takes
    the testing kernel (:func:`lane_bin_candidates_under`) and returns
    (distances, ids, chunks inserted); without it the lists alone."""
    pad = -dists.shape[0] % _STRIP  # the kernels walk whole strips
    if pad:  # zero rows flag nothing
        dists = jnp.pad(dists, ((0, pad), (0, 0)))
    if bound is not None:
        return lane_bin_candidates_under(bound, dists, ids, depth, lists)
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
