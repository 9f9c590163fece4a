"""Two-stage clustered search: centroid score → static-shape probe gather
→ exact masked rerank (the sublinear rung of the DESIGN.md ladder).

Per query tile:

1. **centroid score** — one small exact dot against the (P, d) centroid
   table (``ops.distance.pairwise_sq_l2``, HIGHEST: the routing decision
   must not be noisier than the data), followed by a static-shape
   ``lax.top_k`` of the ``nprobe`` nearest partitions;
2. **probe gather** — whole padded buckets for each probed partition:
   ``(q_tile, nprobe, bucket_cap, d)`` rows + ids + precomputed norms.
   The gather is the ONLY place corpus payload enters the program, and
   its size is nprobe·bucket_bytes per query row — NOT the corpus (the
   bound lint rule R2 budgets and R6 ties to the exact dot);
3. **exact finish** — ``ops.rerank.rerank_exact_topk``: HIGHEST batched
   distance dot over the gathered candidates with the full ``mask_tile``
   padding/self/zero semantics re-applied on exact values, exact top-k.
   Under ``precision_policy="mixed"`` a bf16 DEFAULT compress dot first
   overfetches 4k of the gathered candidates (same recipe and masking
   split as ``ops/rerank.py``) and only the survivors hit the exact dot —
   the policies compose because stage 3 IS the shared rerank pipeline.

**Bucket-major** (PR 42). Where the store's form allows
(:func:`bucket_major_engages`: float32 rows, no scale table, the exact
policy, d on the lane grid, the bucket a block where it rests) stages 2
and 3 go over the LISTS a batch touches instead of over its query rows
(:func:`bucket_major_tile`): the batch's probe table is turned over on the
device, a kernel fetches each touched list once from the store where it
rests and keeps each (query row, list) pair's k nearest slots
(``ops/bucket_walk.py``), and the exact finish runs over a row's
nprobe·k survivors. Same answers, the same counts; the per-row gather
below is what every other store and policy keeps. The walk's keys come
from ONE bf16 x bf16 pass where the store and the batch's query rows are
bf16 numbers (``ops/distance.py bf16_exact``: whole numbers up to 256 in
magnitude are, and a whole-number corpus is centred by a whole-number
mean, ``ivf/index.py``) — the dense scan's one-pass rule (PR 29), decided
by the data: the store's fact is read once at the build
(``IVFIndex.onepass``), the query rows' in every batch, and the six-pass
dot ranks everything else as it did. The keys alone follow the rounded
mean: the finish of such a store takes what the rounding took off
(``IVFIndex.mean_frac``) from its operands again, and returns distances
computed from the numbers the unrounded mean leaves.

Bucket padding slots carry id −1 → ``mask_tile`` forces them to +inf, so
ragged partitions cost padded FLOPs but never wrong answers. Every point
lives in exactly one partition, so probed candidates are duplicate-free
and ``nprobe == partitions`` is a full exact scan (the degenerate case
the parity tests pin against the serial backend).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.distance import bf16_exact, pairwise_sq_l2, sq_norms
from mpi_knn_tpu.ops.quant import dequantize_rows
from mpi_knn_tpu.ops.rerank import (
    mixed_applies,
    overfetch_width,
    rerank_exact_topk,
)
from mpi_knn_tpu.ops.topk import (
    init_topk_tiles,
    mask_tile,
    merge_topk,
    preselect_smallest,
)
from mpi_knn_tpu.parallel.partition import pad_rows_any, pad_to_multiple


def _compress_keys_batched(q_x, q_sq, rows, row_sqs):
    """Per-query compressed distance keys over gathered candidate rows —
    the batched form of ``ops.rerank.compress_tile``: bf16-rounded
    operands, single-pass DEFAULT dot, f32 accumulation. Keys only, never
    output values."""
    xy = jax.lax.dot_general(
        q_x.astype(jnp.bfloat16),
        rows.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT,
    )
    return q_sq[:, None] - 2.0 * xy + row_sqs


def finish_candidates(q_x, q_ids, q_sq, rows, ids, sqs, cfg: KNNConfig):
    """Stage-3 finish over gathered candidates — shared by the
    single-device tile body and the sharded routed tile
    (``ivf/sharded.py``), so the two paths can never drift: under
    ``precision_policy="mixed"`` a bf16 DEFAULT compress dot overfetches
    4k of the (q_tile, v, d) candidates (id-based masks on compressed
    keys, zero-by-value deferred — the ops/rerank.py masking split), then
    the survivors hit the shared exact HIGHEST rerank top-k."""
    v = ids.shape[1]
    if cfg.precision_policy == "mixed" and mixed_applies(cfg.k, v):
        keys = _compress_keys_batched(q_x, q_sq, rows, sqs)
        keys = mask_tile(
            keys,
            ids,
            query_ids=q_ids if cfg.exclude_self else None,
            exclude_self=cfg.exclude_self,
            exclude_zero=False,
        )
        pos = preselect_smallest(keys, overfetch_width(cfg.k, v))
        rows = jnp.take_along_axis(rows, pos[:, :, None], axis=1)
        ids = jnp.take_along_axis(ids, pos, axis=1)
        sqs = jnp.take_along_axis(sqs, pos, axis=1)
    return rerank_exact_topk(
        q_x,
        q_ids,
        q_sq,
        rows,
        ids,
        sqs,
        cfg.k,
        metric="l2",
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps,
    )


@jax.named_scope("knn.ivf/score")
def score_centroids(q_x, centroids, centroid_sqs, nprobe: int):
    """Stage-1 routing decision, shared with the sharded path: exact
    HIGHEST centroid score + static-shape top-nprobe. Returns
    (q_sq, (q_tile, nprobe) partition ids)."""
    q_sq = sq_norms(q_x)
    cd = pairwise_sq_l2(
        q_x, centroids, x_sq=q_sq, y_sq=centroid_sqs,
        precision=jax.lax.Precision.HIGHEST,
    )
    _, probe = jax.lax.top_k(-cd, nprobe)
    return q_sq, probe


def ivf_query_tile(
    q_x: jax.Array,  # (q_tile, d)
    q_ids: jax.Array,  # (q_tile,)
    centroids: jax.Array,  # (P, d) f32
    centroid_sqs: jax.Array,  # (P,)
    buckets: jax.Array,  # (P, cap, d) at-rest dtype — int8 code lanes
    # (packed for int4) when the store is quantized
    bucket_ids: jax.Array,  # (P, cap) int32, -1 padding
    bucket_sqs: jax.Array,  # (P, cap) f32 norms of the dequantized store
    bucket_scales: jax.Array | None,  # (P, cap) f32 per-row scales
    cfg: KNNConfig,
    nprobe: int,
):
    """One query tile through the two-stage search → ((q_tile, k) dists
    ascending, ids, what the tile probed: :func:`tile_probe`). The single
    tile body behind the one-shot wrapper, the serving engine's
    bucket-cache cells, and the lint lowering.

    A quantized store (``cfg.dtype`` int8/int4) changes exactly one
    thing: the probe gather moves CODE lanes (1/4–1/8 the f32 bytes —
    what R2's quantized gather budget prices) plus the tiny scale table,
    and the candidates are dequantized right after the gather — the
    asymmetric distance (exact f32 queries vs dequantized candidates)
    then runs through the same compress/rerank finish as every other
    store."""
    acc = jnp.float32
    q_x = q_x.astype(acc)
    dim = centroids.shape[1]  # logical d (buckets may hold packed lanes)
    q_sq, probe = score_centroids(q_x, centroids, centroid_sqs, nprobe)
    cap = buckets.shape[1]
    v = nprobe * cap
    with jax.named_scope("knn.ivf/gather"):
        rows = jnp.take(buckets, probe, axis=0).reshape(
            -1, v, buckets.shape[2])
        ids = jnp.take(bucket_ids, probe, axis=0).reshape(-1, v)
        sqs = jnp.take(bucket_sqs, probe, axis=0).reshape(-1, v)
        if bucket_scales is not None:
            scl = jnp.take(bucket_scales, probe, axis=0).reshape(-1, v)
            rows = dequantize_rows(rows, scl, cfg.dtype, dim)
        rows = rows.astype(acc)
    d, i = finish_candidates(q_x, q_ids, q_sq, rows, ids, sqs, cfg)
    return d, i, tile_probe(probe, ids, centroids.shape[0])


def tile_probe(probe, ids, partitions: int):
    """What one query tile probed row-major, from what its program holds
    anyway: ``(live rows among the gathered slots, (P,) bool partitions
    probed, (P,) int32 their live rows, work items walked: none, and in
    one pass: none)``. ``ids`` are the gathered slots' ids (q_tile,
    nprobe * cap), -1 where a slot is
    empty or dead; a partition probed by several rows of the tile is marked
    once."""
    live = (ids >= 0).reshape(*probe.shape, -1).sum(-1, dtype=jnp.int32)
    flat = probe.reshape(-1)
    return (
        jnp.sum(live, dtype=jnp.int32),
        jnp.zeros(partitions, jnp.bool_).at[flat].set(True),
        jnp.zeros(partitions, jnp.int32).at[flat].max(live.reshape(-1)),
        jnp.int32(0),
        jnp.int32(0),
    )


PROBE_FIELDS = 7  # the width of a batch's probe counts (probe_counts)


def probe_counts(q_rows: int, nprobe: int, cap: int, live, seen, part_live,
                 walked, walked_onepass):
    """A batch's ``TileCounts.ivf_probe`` from its tiles' counts
    (:func:`tile_probe` / :func:`bucket_major_tile`'s, stacked): int32
    ``[probes issued (query rows x nprobe, padding rows of the batch
    included: they probe too), bucket_cap (a probe scans that many slots),
    live rows among the probed slots, distinct partitions the batch
    touched, live rows of those, work items walked, work items walked in
    one bf16 pass]``. The fourth and fifth
    are what any implementation has to read once a batch; the sixth is the
    bucket-major program's (list, group of <= ``PROBE_GROUP`` query rows)
    steps, 0 from the row-major program: it says which of the two answered
    the batch, and probes over (work items x ``PROBE_GROUP``) is the
    groups' fill; the seventh is the sixth where the one-pass rule held
    for the batch (the store's fact and the batch's query rows: all of a
    batch's work items take one side), else 0. (Slots are left to the
    reader, probes x bucket_cap: a
    1024-row batch scans 1e8 of them and an int32 sum on the device would
    not hold a large one's.)"""
    return jnp.stack([
        jnp.int32(q_rows * nprobe),
        jnp.int32(cap),
        jnp.sum(live, dtype=jnp.int32),
        jnp.sum(jnp.any(seen, axis=0), dtype=jnp.int32),
        jnp.sum(jnp.max(part_live, axis=0), dtype=jnp.int32),
        jnp.sum(walked, dtype=jnp.int32),
        jnp.sum(walked_onepass, dtype=jnp.int32),
    ])


# the query rows a work item of the bucket-major walk holds: a vreg's eight
# sublanes. A list probed by n rows of a batch is ceil(n / PROBE_GROUP)
# work items; the cell's batches probe a touched list 4.1 times on
# average, and the kernel's selection, which bounds it, costs by the rows
# of a group whether they are filled or not: 16 would double that for 7 %
# fewer work items (PERF.md section 6, PR 42)
PROBE_GROUP = 8
# the kernel's share of VMEM: two buffers of a bucket and of what goes
# with it (ops/bucket_walk.py bucket_walk_vmem_bytes)
_WALK_VMEM_BYTES = 64 << 20


def bucket_major_items(q_rows: int, nprobe: int, partitions: int) -> int:
    """The work items W of a bucket-major batch of ``q_rows`` rows, a
    static count: a list probed by n rows is ceil(n / PROBE_GROUP) items,
    and over the lists that sums to at most (lists touched) + (probes //
    PROBE_GROUP) whatever the skew — no probe can overflow a group, and
    none is ever dropped."""
    probes = q_rows * nprobe
    return min(partitions, probes) + probes // PROBE_GROUP


def bucket_major_engages(q_rows: int, nprobe: int, partitions: int,
                         cap: int, dim: int, dtype: str = "float32",
                         precision_policy: str = "exact",
                         onepass: bool = False) -> bool:
    """Whether a batch of ``q_rows`` rows is answered BUCKET-MAJOR
    (:func:`bucket_major_tile`), by the static shapes and the store's form
    alone — the rule ``ops/topk.py fused_scan_engages`` is for the dense
    scan:

    - *the store's form*: float32 rows at rest with no scale table
      (``dtype``: a quantised store's candidates are dequantised after
      the gather, a bfloat16 bucket is a (16, 128)-tiled block; both keep
      the row-major program), ranked exactly (``precision_policy``:
      ``mixed`` is the row-major finish's overfetch);
    - *the bucket is a block where it rests*: ``dim`` on the 128-lane grid
      (a (P, cap, d) float32 store then rests row-major on the v5e:
      ``ops/topk.py fused_scan_engages`` has the readings) and ``cap`` a
      multiple of 8, and two buffers of it with what goes with it fit the
      kernel's share of VMEM (``onepass``: asked of the kernel that holds
      both dots, which keeps bfloat16 copies of a bucket and a group
      besides — what ``ivf/index.py store_onepass`` asks before it grants
      a store the fact);
    - (the caller's: k slots fit a list and the kernel's 128 lanes).

    It is one algorithm at every batch height: its work items follow
    ``q_rows`` (:func:`bucket_major_items`) and it fetches a list at most
    as often as the row-major gather copies it. Where it says no, the
    row-major tile body answers, as it stands."""
    if (dtype != "float32" or precision_policy != "exact"
            or dim % 128 or cap % 8 or q_rows < 1):
        return False
    from mpi_knn_tpu.ops.bucket_walk import bucket_walk_vmem_bytes

    return bucket_walk_vmem_bytes(
        PROBE_GROUP, cap, dim, onepass=onepass) <= _WALK_VMEM_BYTES


def _engages(cfg: KNNConfig, q_rows: int, nprobe: int, partitions: int,
             cap: int, dim: int) -> bool:
    return cfg.k <= min(cap, 128) and bucket_major_engages(
        q_rows, nprobe, partitions, cap, dim, cfg.dtype,
        cfg.precision_policy)


def invert_probe(probe: jax.Array, partitions: int):
    """The (Q, nprobe) probe table turned over on the device: its (query
    row, list) pairs sorted by list (stable: a list's rows ascend) and
    each list's run cut into work items of at most ``PROBE_GROUP`` rows.
    Returns ``(item_lists (W,) int32 — the list of each work item, the
    last real list's again past the real ones; item_rows (W, PROBE_GROUP)
    int32 — the query row of each slot, -1 where a group is short;
    pair_slot (Q, nprobe) int32 — where each pair's answer lies,
    work item x PROBE_GROUP + slot; counts (P,) int32 — the rows that
    probe each list; walked — the real work items)``, W =
    :func:`bucket_major_items`. No scatter: sorted runs are found by
    counting and everything else is a gather."""
    i32 = jnp.int32
    q_rows, nprobe = probe.shape
    n = q_rows * nprobe
    items = bucket_major_items(q_rows, nprobe, partitions)
    lists, order = jax.lax.sort(
        (probe.reshape(-1).astype(i32), jnp.arange(n, dtype=i32)),
        num_keys=1, is_stable=True)
    # (compare_all: one fused compare-and-count; the default's binary
    # search is a loop of 14 small gathers, 0.44 ms a search on the v5e)
    starts = jnp.searchsorted(
        lists, jnp.arange(partitions, dtype=i32), side="left",
        method="compare_all").astype(i32)
    counts = jnp.diff(starts, append=jnp.full(1, n, i32))
    items_of = (counts + (PROBE_GROUP - 1)) // PROBE_GROUP
    ends = jnp.cumsum(items_of, dtype=i32)
    bases = ends - items_of  # a list's first work item
    walked = ends[-1]
    w = jnp.arange(items, dtype=i32)
    real = w < walked
    item_lists = jnp.where(
        real,
        jnp.minimum(jnp.searchsorted(
            ends, w, side="right", method="compare_all").astype(i32),
            partitions - 1),
        lists[-1])
    # the item's first pair in the sorted order, and its slots' pairs
    first = starts[item_lists] + (w - bases[item_lists]) * PROBE_GROUP
    pos = first[:, None] + jnp.arange(PROBE_GROUP, dtype=i32)[None, :]
    valid = real[:, None] & (
        pos < (starts + counts)[item_lists][:, None])
    at = jnp.clip(pos, 0, n - 1)
    item_rows = jnp.where(valid, order[at] // nprobe, -1)
    # the sort's inverse: each pair's rank in its list's run
    rank = jnp.arange(n, dtype=i32) - starts[lists]
    slot_sorted = (bases[lists] + rank // PROBE_GROUP) * PROBE_GROUP + (
        rank % PROBE_GROUP)
    _, pair_slot = jax.lax.sort((order, slot_sorted), num_keys=1)
    return (item_lists, item_rows, pair_slot.reshape(q_rows, nprobe),
            counts, walked)


def bucket_major_tile(
    q_x: jax.Array,  # (Q, d) one batch (or one tile of a tall one)
    q_ids: jax.Array,  # (Q,)
    centroids: jax.Array,
    centroid_sqs: jax.Array,
    buckets: jax.Array,  # (P, cap, d) float32
    bucket_ids: jax.Array,
    bucket_sqs: jax.Array,
    cfg: KNNConfig,
    nprobe: int,
    onepass: jax.Array | None = None,  # the store's side of the one-pass rule
    mean_frac: jax.Array | None = None,  # (d,) what the mean's rounding took
):
    """:func:`ivf_query_tile`'s answer by a walk over the touched LISTS
    instead of over the query rows: score as there; the probe table turned
    over (:func:`invert_probe`); every work item's list fetched ONCE from
    the store where it rests and met with its group of query rows on the
    MXU (``ops/bucket_walk.py``, scope ``knn.ivf/gather``: still the only
    place corpus payload enters the program), which keeps the k nearest
    slots of each (query row, list) pair; and every row's nprobe x k
    survivors gathered from the store by slot and finished by
    :func:`finish_candidates` — the distances returned are computed by the
    code that computes the row-major program's. Same ids: a row's k
    nearest over its lists are among each list's k nearest.

    ``onepass`` (``IVFIndex.onepass``: a bool scalar on the device, TRUE
    while every element of the store is a bf16 number; None: the store
    did not qualify, and the kernel holds the six-pass dot alone) is met
    here with the same test of this batch's query rows: where both hold,
    the walk's keys come from one bf16 pass — the same keys, bit for bit,
    so the same slots. ``mean_frac`` (``IVFIndex.mean_frac``, beside a
    fact alone: the corpus mean less the whole-number ``mu`` the store and
    the query rows were centred by) is taken off the survivors and the
    query rows ahead of the finish — L2 does not see the common
    translation — so the finish reads the operands, fractional again,
    that the unrounded mean left it, and their norms anew.

    Returns (dists, ids, (live pairs, (P,) lists probed, (P,) their live
    rows, work items walked, those walked in one pass))."""
    from mpi_knn_tpu.ops.bucket_walk import bucket_walk

    acc, i32 = jnp.float32, jnp.int32
    q_x = q_x.astype(acc)
    q_rows, k = q_x.shape[0], cfg.k
    partitions, cap, dim = buckets.shape
    q_sq, probe = score_centroids(q_x, centroids, centroid_sqs, nprobe)
    with jax.named_scope("knn.ivf/score"):
        item_lists, item_rows, pair_slot, counts, walked = invert_probe(
            probe, partitions)
        if onepass is not None:
            onepass = onepass & bf16_exact(q_x)
    with jax.named_scope("knn.ivf/gather"):
        at = jnp.maximum(item_rows, 0)
        slots = bucket_walk(
            item_lists, walked, jnp.take(q_x, at, axis=0),
            jnp.take(q_ids, at, axis=0) if cfg.exclude_self else None,
            buckets, bucket_ids, bucket_sqs, k=k,
            exclude_zero=cfg.exclude_zero, zero_eps=cfg.zero_eps,
            onepass=onepass)
        # each pair's k slots, where its work item left them; -1 where a
        # list holds fewer than k unmasked ones
        slots = jnp.take(slots.reshape(-1, slots.shape[-1]),
                         pair_slot.reshape(-1), axis=0)[:, :k]
        short = (slots < 0).reshape(q_rows, nprobe * k)
        slots = (probe.reshape(-1, 1).astype(i32) * cap
                 + jnp.maximum(slots, 0)).reshape(q_rows, nprobe * k)
        live = jnp.sum(bucket_ids >= 0, axis=1, dtype=i32)
    with jax.named_scope("knn.rerank"):
        rows = jnp.take(buckets.reshape(-1, dim), slots, axis=0).astype(acc)
        ids = jnp.where(
            short, -1, jnp.take(bucket_ids.reshape(-1), slots, axis=0))
        if mean_frac is None:
            sqs = jnp.take(bucket_sqs.reshape(-1), slots, axis=0)
        else:
            q_x, rows = q_x - mean_frac, rows - mean_frac
            q_sq, sqs = sq_norms(q_x), None  # the finish takes the rows'
    d, i = finish_candidates(q_x, q_ids, q_sq, rows, ids, sqs, cfg)
    seen = counts > 0
    return d, i, (
        jnp.sum(counts * live, dtype=i32), seen,
        jnp.where(seen, live, 0), walked,
        i32(0) if onepass is None else jnp.where(onepass, walked, 0))


def ivf_serve_chunk(
    q_tiles: jax.Array,  # (QT, q_tile, d) one padded query batch
    qid_tiles: jax.Array,  # (QT, q_tile)
    carry_d: jax.Array,  # (QT, q_tile, k) per-batch scratch (donatable)
    carry_i: jax.Array,
    probed: jax.Array,  # (PROBE_FIELDS,) int32 zeros (donatable)
    centroids: jax.Array,
    centroid_sqs: jax.Array,
    buckets: jax.Array,
    bucket_ids: jax.Array,
    bucket_sqs: jax.Array,
    bucket_scales: jax.Array | None,
    onepass: jax.Array | None,  # ``IVFIndex.onepass``
    mean_frac: jax.Array | None,  # ``IVFIndex.mean_frac``
    cfg: KNNConfig,
    nprobe: int,
):
    """One serving batch against a resident :class:`~mpi_knn_tpu.ivf.index.
    IVFIndex` — the engine's uniform (queries, query_ids, carry_d,
    carry_i, probed, <resident arrays…>) convention, the scratch donated
    (``donate_argnums=(2, 3, 4)``). The tile results merge into the (all-inf)
    donated scratch — a bit-exact no-op merge whose sole purpose is giving
    the scratch buffers an output to alias."""

    partitions, cap, _ = buckets.shape
    bucket_major = bucket_scales is None and _engages(
        cfg, q_tiles.shape[1], nprobe, partitions, cap, centroids.shape[1])

    def per_tile(args):
        q_x, q_ids, cd_, ci_ = args
        if bucket_major:
            d, i, probed = bucket_major_tile(
                q_x, q_ids, centroids, centroid_sqs, buckets, bucket_ids,
                bucket_sqs, cfg, nprobe, onepass, mean_frac)
        else:
            d, i, probed = ivf_query_tile(
                q_x, q_ids, centroids, centroid_sqs, buckets, bucket_ids,
                bucket_sqs, bucket_scales, cfg, nprobe,
            )
        return (*merge_topk(cd_, ci_, d.astype(cd_.dtype), i,
                            method="exact"), probed)

    tiles = (q_tiles, qid_tiles, carry_d, carry_i)
    if bucket_major and q_tiles.shape[0] == 1:  # one tile: no loop
        d, i, tiles = jax.tree.map(
            lambda x: x[None], per_tile(tuple(x[0] for x in tiles)))
    else:
        d, i, tiles = jax.lax.map(per_tile, tiles)
    return d, i, probed + probe_counts(
        q_tiles.shape[0] * q_tiles.shape[1], nprobe, buckets.shape[1],
        *tiles)


_ivf_serve_jit = jax.jit(
    ivf_serve_chunk, static_argnames=("cfg", "nprobe")
)


def ivf_query_shapes(cfg: KNNConfig, nprobe: int, bucket_cap: int,
                     dim: int, nq: int) -> tuple[int, int]:
    """(q_tile, q_pad) for an IVF batch, by what its tile program
    materialises — the hard per-step bound ``cfg.max_tile_elems`` that
    ``cap_corpus_tile`` enforces for the dense backends, applied to the
    IVF path's dominant intermediate; q_tile halves until that fits:

    - *bucket-major* (:func:`bucket_major_engages`): the survivors the
      finish gathers, q_tile x nprobe x k x dim. A batch is one query tile
      wherever that fits (the cell's 1024 rows: 2.1e7): the walk fetches
      a bucket where it rests, and no per-row gather exists to bound;
    - *row-major*: the probe gather, q_tile x nprobe x bucket_cap x dim.

    What one query row needs is fixed by the index layout, so when even a
    single-row tile exceeds the budget there is nothing left to shrink —
    that is refused loudly (the convention), never silently materialized."""
    q_tile = min(cfg.query_tile, pad_to_multiple(nq, 8))
    partitions = cfg.partitions or 1

    def elems(rows: int) -> int:
        if _engages(cfg, rows, nprobe, partitions, bucket_cap, dim):
            return rows * nprobe * cfg.k * dim
        return rows * max(1, nprobe * bucket_cap * dim)

    while q_tile > 1 and elems(q_tile) > cfg.max_tile_elems:
        q_tile = max(1, q_tile // 2)
    if elems(q_tile) > cfg.max_tile_elems:
        raise ValueError(
            f"one query row's probe gather (nprobe={nprobe} × bucket_cap="
            f"{bucket_cap} × d={dim}: {elems(q_tile)} elems) exceeds "
            f"max_tile_elems={cfg.max_tile_elems}; lower nprobe/partitions "
            "(bigger partitions mean bigger buckets), raise "
            "max_tile_elems, or use a dense backend for full scans"
        )
    return q_tile, pad_to_multiple(nq, q_tile)


def prepare_query_tiles(index, queries, query_ids, cfg: KNNConfig,
                        assume_centered: bool = False):
    """Host-side half of :func:`search_ivf`: center with the index's
    stored mean, pad and tile one query batch for the jitted search.
    Exposed so callers that time the COMPUTE (bench.py's IVF rows) can
    prepare once, keep the tiles device-resident, and run reps against
    them — the dense bench's timer placement. Returns
    (q_tiles, qid_tiles, q_pad, q_tile)."""
    queries = np.asarray(queries)
    nq = queries.shape[0]
    if query_ids is None:
        q_ids = np.full(nq, -1, dtype=np.int32)
    else:
        q_ids = np.asarray(query_ids, dtype=np.int32)
    if cfg.center and index.mu is not None and not assume_centered:
        queries = queries - index.mu
    q_tile, q_pad = ivf_query_shapes(
        cfg, cfg.nprobe, index.bucket_cap, index.dim, nq
    )
    qt = q_pad // q_tile
    q_tiles = pad_rows_any(queries, q_pad, dtype=jnp.float32).reshape(
        qt, q_tile, index.dim
    )
    qid_tiles = pad_rows_any(
        q_ids, q_pad, fill=-1, dtype=jnp.int32
    ).reshape(qt, q_tile)
    return q_tiles, qid_tiles, q_pad, q_tile


def run_query_tiles(index, q_tiles, qid_tiles, cfg: KNNConfig):
    """Device half of :func:`search_ivf`: fresh all-inf carries + the
    jitted two-stage search over prepared tiles. Returns padded
    (QT, q_tile, k) device arrays (not synchronized)."""
    qt, q_tile = q_tiles.shape[0], q_tiles.shape[1]
    carry_d, carry_i = init_topk_tiles(qt, q_tile, cfg.k, dtype=jnp.float32)
    return _ivf_serve_jit(
        q_tiles, qid_tiles, carry_d, carry_i,
        jnp.zeros(PROBE_FIELDS, jnp.int32), index.centroids,
        index.centroid_sqs, index.buckets,
        index.bucket_ids, index.bucket_sqs, index.bucket_scales,
        index.onepass, index.mean_frac, cfg, cfg.nprobe,
    )[:2]


def search_ivf(index, queries, query_ids=None, config=None,
               assume_centered=False, **overrides):
    """One-shot query batch against an :class:`IVFIndex` (no executable
    cache — the serving engine owns that): center with the index's stored
    mean, tile, run the jitted two-stage search, strip padding. Returns
    ((q, k) dists ascending, (q, k) ids) as numpy arrays.
    ``assume_centered`` skips the centering step for queries already in
    the index's centered frame (the nprobe auto-tuner's held-out corpus
    rows, which come back out of the bucket store)."""
    cfg = index.compatible_cfg((config or index.cfg).replace(**overrides))
    nq = np.shape(queries)[0]
    q_tiles, qid_tiles, q_pad, _ = prepare_query_tiles(
        index, queries, query_ids, cfg, assume_centered=assume_centered
    )
    d, i = run_query_tiles(index, q_tiles, qid_tiles, cfg)
    return (
        np.asarray(d.reshape(q_pad, cfg.k)[:nq]),
        np.asarray(i.reshape(q_pad, cfg.k)[:nq]),
    )
