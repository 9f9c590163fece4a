"""One kernel that walks a clustered store's probed lists: a grid of work
items, each ONE list's padded bucket — fetched from ``buckets`` /
``bucket_ids`` / ``bucket_sqs`` where they rest, by the work item's list
index — against a group of at most ``group`` query rows that probe it
(``ivf/search.py bucket_major_tile`` makes the work items and finishes what
this returns; ``ops/fused_scan.py`` is the pattern: a ``BlockSpec`` index
map that reads a scalar-prefetched table).

A step: the group's (group, d) rows against the (cap, d) bucket on the MXU
at ``highest`` (float32 in six bf16 passes) — or, where the caller hands
the one-pass rule's verdict (``onepass``: the store and the batch's query
rows are bf16 numbers, ``ops/distance.py bf16_exact``) and it is true, in
ONE bf16 x bf16 pass over both narrowed in VMEM, accumulated in float32:
the six passes' keys, bit for bit, since the pieces the other five
multiply are zero. The kernel then holds both dots and a scalar read from
scalar memory picks a work item's side; without a verdict it holds the
six-pass dot alone. Then ``max(q_sq - 2 q.x + x_sq, 0)``
and ``mask_tile``'s masks (an id < 0 is padding or a dead slot, the zero
mask by the pair's scale, the self mask by id) — the (group, cap) block of
masked distances lives in VMEM scratch and nowhere else — and the k
nearest slots of each row taken from it by k passes of row-minimum and
knock-out (the earlier slot first among equal distances, as ``lax.top_k``
orders them; -1 once nothing finite is left): what leaves is (group, 128)
slot numbers, k of them meant.
Consecutive work items of one list name the same block, so the pipeline
fetches a bucket once however many groups probe it; the work items past
the last real one name the last real list again (nothing is fetched) and
compute nothing.

The distances are KEYS: they rank a list's slots for each query row that
probes it, and the caller's exact finish computes every distance it
returns anew. (What was measured on the way, PERF.md section 6, PR 42: the
blocks written out and selected by one ``lax.top_k`` over the pairs' rows
— the kernel fetch-bound, 15.1 ms of a 36.9 ms batch at the cell's shapes,
the ``top_k`` 12.3; the passes here — 23.9 of 30.6, compute-bound: a pass
is two lane reductions deep; one fused sweep a pass, and per-lane lists
under a certificate, each slower than these passes and taken out. PR 46,
the kernel alone at the cell's shapes: a work item 5.84 us in six passes,
3.91 in one, 3.89 with no branch in the kernel at all.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_knn_tpu.ops.lane_bin import (
    _I32_MAX,
    _INF,
    _LANES,
    _as_i32,
    _interpret,
    _out,
)
from mpi_knn_tpu.ops.topk import _ZERO_RTOL_DEFAULT

# the rows of the id and norm planes a block holds: a (1, cap) block of a
# (P, cap) plane is no legal block, so the block is the 8 lists around the
# one wanted and the kernel picks its row (ops/fused_scan.py _PLANE_ROWS)
_PLANE_ROWS = 8
# VMEM beyond the kernel's own buffers that the call asks for: the dot's
# result, the masks and a pass's compares as values
_VMEM_HEADROOM = 16 << 20


def bucket_walk_vmem_bytes(group: int, cap: int, d: int,
                           itemsize: int = 4, onepass: bool = False) -> int:
    """The VMEM :func:`bucket_walk` holds, in bytes: a bucket, its rows of
    the two planes, the group's query rows (and ids) and slot numbers, each
    in the pipeline's two buffers, and the group's block of distances;
    ``onepass``: the kernel carries the one-pass dot too, whose operands
    are bfloat16 copies of a bucket and of a group's rows."""
    bucket = cap * d * itemsize
    planes = 2 * _PLANE_ROWS * cap * 4
    group_side = group * (d + 2 * _LANES) * 4
    narrowed = (cap + group) * d * 2 if onepass else 0
    return 2 * (bucket + planes + group_side) + group * cap * 4 + narrowed


def _walk_kernel(lists_ref, walked_ref, *refs, k: int, exclude_self: bool,
                 exclude_zero: bool, zero_eps: float, onepass: bool):
    """A grid step of :func:`bucket_walk`: work item w. ``lists_ref`` (W,)
    and ``walked_ref`` (1,) int32 in scalar memory: each work item's list,
    and how many of them are real; under ``onepass`` a third, ``one_ref``
    (1,) int32: whether this call's operands are bf16 numbers, so that the
    dot takes one pass. ``q_ref`` (1, group, d) the group's
    query rows, ``qid_ref`` (1, group, 128) their ids in every lane (an
    operand under ``exclude_self`` alone); ``x_ref`` (1, cap, d) the list's
    bucket as it rests, ``ids_ref`` / ``xsq_ref`` (8, cap) the planes' rows
    around the list; ``out_ref`` (1, group, 128) int32, the k nearest
    slots of each row in its first k lanes; ``d_ref`` (group, cap) scratch,
    the masked distances."""
    lax, f32, i32 = jax.lax, jnp.float32, jnp.int32
    one_ref, refs = (refs[0], refs[1:]) if onepass else (None, refs)
    q_ref = refs[0]
    qid_ref = refs[1] if exclude_self else None
    x_ref, ids_ref, xsq_ref, out_ref, d_ref = refs[-5:]
    w = _as_i32(pl.program_id(0))

    @pl.when(lax.lt(w, walked_ref[0]))
    def _():
        row = pl.ds(lax.rem(lists_ref[w], i32(_PLANE_ROWS)), 1)
        q = q_ref[0]
        shape = d_ref.shape

        def keys(narrow: bool):
            """The block of masked distances into ``d_ref``, from the
            six-pass dot — or, ``narrow``, from ONE pass over the group's
            rows and the bucket narrowed to bfloat16, which loses nothing
            of a bf16 number. Everything after the dot is one code."""
            x = lax.convert_element_type(x_ref[0], f32)
            lhs, rhs, precision = q, x, lax.Precision.HIGHEST
            if narrow:
                lhs, rhs = (lax.convert_element_type(a, jnp.bfloat16)
                            for a in (q, x))
                precision = lax.Precision.DEFAULT
            xy = lax.dot_general(
                lhs, rhs,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=f32,
                precision=precision,
            )
            q_sq = lax.broadcast_in_dim(
                lax.reduce_sum(lax.mul(q, q), (1,)), shape, (0,))
            ids = lax.broadcast_in_dim(ids_ref[row, :], shape, (0, 1))
            x_sq = lax.broadcast_in_dim(xsq_ref[row, :], shape, (0, 1))
            v = lax.max(
                lax.add(lax.sub(q_sq, lax.mul(lax.full(shape, 2.0, f32), xy)),
                        x_sq),
                lax.full(shape, 0.0, f32))
            invalid = lax.lt(ids, lax.full(shape, 0, i32))
            if exclude_zero:
                # mask_tile's threshold for float32, by the pair's scale
                thresh = (lax.full(shape, zero_eps, f32) if zero_eps > 0.0
                          else lax.mul(
                              lax.full(shape, _ZERO_RTOL_DEFAULT, f32),
                              lax.add(q_sq, x_sq)))
                invalid = lax.bitwise_or(invalid, lax.le(v, thresh))
            if exclude_self:
                own = lax.broadcast_in_dim(
                    lax.slice(qid_ref[0], (0, 0), (shape[0], 1)), shape,
                    (0, 1))
                invalid = lax.bitwise_or(invalid, lax.eq(ids, own))
            inf = lax.full(shape, _INF, f32)
            d_ref[...] = lax.select(invalid, inf, v)
            return inf

        if onepass:
            # the whole fill on either side: the dot's result then feeds
            # the masks where it is made (around the dot alone the branch
            # cost 0.17 us a work item on the chip, here 0.02)
            def side(narrow: bool):
                def fill():
                    keys(narrow)
                return fill

            lax.cond(lax.ne(one_ref[0], i32(0)), side(True), side(False))
            inf = lax.full(shape, _INF, f32)
        else:
            inf = keys(False)

        col = lax.broadcasted_iota(i32, shape, 1)
        lane = lax.broadcasted_iota(i32, out_ref.shape[1:], 1)

        def nearest(j, slots):
            """The row's nearest slot left, knocked out of the block; -1
            where nothing finite is left (a slot is never named twice; a
            NaN is not finite and equal to no minimum)."""
            d = d_ref[...]
            least = lax.reduce_min(d, (1,))
            first = lax.reduce_min(lax.select(
                lax.eq(d, lax.broadcast_in_dim(least, shape, (0,))), col,
                lax.full(shape, _I32_MAX, i32)), (1,))
            d_ref[...] = lax.select(
                lax.eq(col, lax.broadcast_in_dim(first, shape, (0,))), inf, d)
            first = lax.select(
                lax.lt(least, lax.full(shape[:1], _INF, f32)), first,
                lax.full(shape[:1], -1, i32))
            return lax.select(
                lax.eq(lane, lax.broadcast(_as_i32(j), lane.shape)),
                lax.broadcast_in_dim(first, lane.shape, (0,)), slots)

        out_ref[0] = lax.fori_loop(0, k, nearest, lax.full(lane.shape, 0, i32))


def bucket_walk(item_lists: jax.Array, walked: jax.Array,
                group_rows: jax.Array, group_ids: jax.Array | None,
                buckets: jax.Array, bucket_ids: jax.Array,
                bucket_sqs: jax.Array, *, k: int, exclude_zero: bool,
                zero_eps: float, onepass: jax.Array | None = None):
    """The k nearest slots of every work item's rows in its list:
    ``item_lists`` (W,) int32 the list of each work item (one list's items
    side by side), ``walked`` int32 how many are real; ``group_rows`` (W,
    group, d) float32 the groups' query rows, ``group_ids`` (W, group)
    their ids where the self mask applies, else None; ``buckets`` (P, cap,
    d), ``bucket_ids`` / ``bucket_sqs`` (P, cap) the store at rest. Returns
    (W, group, 128) int32: a row's k nearest slots of the list by masked
    distance, ascending, in the first k lanes (k <= 128), -1 past a list's
    unmasked slots where it has fewer; the blocks of the work items past
    ``walked`` hold nothing meant.

    ``onepass`` is the one-pass rule's verdict on this call's operands, a
    bool scalar made on the device (``ops.distance.bf16_exact`` of
    ``buckets`` and of the batch's query rows): the kernel then holds both
    dots and every work item takes the one the scalar names — one bf16 x
    bf16 pass where it is true, which for bf16 numbers returns the six
    passes' keys. None: the kernel with the six-pass dot alone."""
    n_items, group, d = group_rows.shape
    cap = buckets.shape[1]
    f32 = jnp.float32
    exclude_self = group_ids is not None
    operands = (group_rows, buckets, bucket_ids, bucket_sqs)

    scalars = [item_lists.astype(jnp.int32),
               jnp.reshape(walked, (1,)).astype(jnp.int32)]
    if onepass is not None:
        scalars.append(jnp.reshape(onepass, (1,)).astype(jnp.int32))

    # (index maps take the grid index, then the prefetched scalars)
    def item(w, *scalars):
        return w, 0, 0

    plane = pl.BlockSpec(
        (_PLANE_ROWS, cap),
        lambda w, lists, *_: (lists[w] // _PLANE_ROWS, 0))
    specs, args = [pl.BlockSpec((1, group, d), item)], [group_rows.astype(f32)]
    if exclude_self:
        specs.append(pl.BlockSpec((1, group, _LANES), item))
        args.append(jnp.broadcast_to(
            group_ids.astype(jnp.int32)[:, :, None],
            (n_items, group, _LANES)))
    return pl.pallas_call(
        functools.partial(
            _walk_kernel, k=k, exclude_self=exclude_self,
            exclude_zero=exclude_zero, zero_eps=zero_eps,
            onepass=onepass is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_items,),
            in_specs=[
                *specs,
                # the bucket where it rests in the store, by the list's index
                pl.BlockSpec(
                    (1, cap, d), lambda w, lists, *_: (lists[w], 0, 0)),
                plane, plane,
            ],
            out_specs=pl.BlockSpec((1, group, _LANES), item),
            scratch_shapes=[pltpu.VMEM((group, cap), f32)],
        ),
        out_shape=_out((n_items, group, _LANES), jnp.int32, *operands),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=bucket_walk_vmem_bytes(
                group, cap, d, buckets.dtype.itemsize,
                onepass is not None) + _VMEM_HEADROOM,
        ),
        interpret=_interpret(),
    )(*scalars, *args, buckets, bucket_ids.astype(jnp.int32),
      bucket_sqs.astype(f32))
