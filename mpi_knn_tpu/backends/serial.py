"""Serial (single-device) backend — the ground-truth execution path,
replacing the reference's serial driver (SURVEY.md C5,
``/root/reference/knn-serial.c:36-133``).

Same math as the distributed backends, unsharded: the (q × c) distance
problem is tiled into MXU-sized blocks; a ``lax.scan`` streams corpus tiles
through VMEM while a per-query top-k carry is merged tile by tile, and a
``lax.map`` walks query tiles so peak memory is
O(query_tile × corpus_tile + q × k) instead of the reference's full
m × NN neighbour matrix on the *stack* (~28.8 MB of VLAs,
``/root/reference/knn-serial.c:54-55``).

``knn_chunk_update`` is the single jitted core: the plain serial path calls
it once over all corpus tiles; the resumable driver (backends.resumable)
calls it per checkpoint round with the carry threaded through; the ring
backends run ``knn_tile_step`` against each rotating block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.distance import pairwise_dist, sq_norms
from mpi_knn_tpu.ops.rerank import compress_rerank_tile
from mpi_knn_tpu.ops.topk import (
    cascade_smallest_k,
    init_topk_tiles,
    mask_tile,
    smallest_k,
)
from mpi_knn_tpu.parallel.partition import (
    make_global_ids,
    pad_rows_any,
    pad_to_multiple,
)


@jax.named_scope("knn.dist")
def masked_dist_tile(
    q_x: jax.Array,
    q_ids: jax.Array,
    q_sq: jax.Array | None,
    blk: jax.Array,
    blk_ids: jax.Array,
    blk_sq: jax.Array | None,
    cfg: KNNConfig,
) -> jax.Array:
    """(q_tile × c_tile) masked distances: metric kernel → padding/self/zero
    exclusion masks. The compute half shared by both merge schedules and the
    ring backends."""
    d = pairwise_dist(
        q_x,
        blk,
        metric=cfg.metric,
        x_sq=q_sq,
        y_sq=blk_sq,
        precision=cfg.matmul_precision,
    )
    if cfg.metric == "l2" and q_sq is not None and blk_sq is not None:
        pair_scale = q_sq[:, None] + blk_sq[None, :]
    else:
        # cosine distances live in [0, 2]; constant scale for the zero test
        pair_scale = jnp.asarray(2.0, dtype=d.dtype)
    return mask_tile(
        d,
        blk_ids,
        query_ids=q_ids if cfg.exclude_self else None,
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps,
        scale=pair_scale,
    )


def local_tile_topk(
    q_x: jax.Array,
    q_ids: jax.Array,
    q_sq: jax.Array | None,
    blk: jax.Array,
    blk_ids: jax.Array,
    blk_sq: jax.Array | None,
    cfg: KNNConfig,
    out_dtype,
):
    """One corpus tile's (q, k) survivors — the per-tile reduction both
    merge schedules share, switched on ``cfg.precision_policy``:

    - "exact": one distance pass at ``cfg.matmul_precision`` (HIGHEST by
      default for f32), then ``smallest_k`` per ``cfg.topk_method`` — with
      the 1-D tile id vector, so "exact" can take the lane-bin selection
      (ops/topk.py) instead of sorting the tile;
    - "mixed": the compress-and-rerank two-pass pipeline (ops/rerank.py) —
      a DEFAULT-precision bf16 compress dot overfetches 4k candidates, a
      HIGHEST rerank of the gathered survivors finishes exactly. The tile's
      contribution to any downstream merge is exact-f32 either way, so the
      carry/checkpoint algebra is policy-independent.
    """
    if cfg.precision_policy == "mixed":
        ld, li = compress_rerank_tile(
            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg
        )
        return ld.astype(out_dtype), li
    d = masked_dist_tile(q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg)
    with jax.named_scope("knn.select"):
        return smallest_k(
            d.astype(out_dtype),
            blk_ids,
            cfg.k,
            method=cfg.topk_method,
            recall_target=cfg.recall_target,
            block=cfg.topk_block,
        )


def knn_tile_step(
    q_x: jax.Array,
    q_ids: jax.Array,
    q_sq: jax.Array | None,
    blk: jax.Array,
    blk_ids: jax.Array,
    blk_sq: jax.Array | None,
    carry_d: jax.Array,
    carry_i: jax.Array,
    cfg: KNNConfig,
):
    """One fused (query_tile × corpus_tile) step: distances → masks → merged
    top-k, streamed into the carry. The ring backends' per-round body (a
    rotating block is inherently stream-merged)."""
    if cfg.precision_policy == "mixed":
        # two-pass tile reduction to k exact survivors first, then a narrow
        # (2k-wide) merge into the carry — the carry itself stays exact
        ld, li = local_tile_topk(
            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, carry_d.dtype
        )
        all_d = jnp.concatenate([carry_d, ld], axis=-1)
        all_i = jnp.concatenate([carry_i, li], axis=-1)
    else:
        d = masked_dist_tile(q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg)
        all_d = jnp.concatenate([carry_d, d.astype(carry_d.dtype)], axis=-1)
        with jax.named_scope("knn.ids"):
            tile_ids = jnp.broadcast_to(blk_ids[None, :], d.shape)
        all_i = jnp.concatenate([carry_i, tile_ids], axis=-1)
    with jax.named_scope("knn.merge"):
        return smallest_k(
            all_d,
            all_i,
            cfg.k,
            method=cfg.topk_method,
            recall_target=cfg.recall_target,
            block=cfg.topk_block,
        )


@functools.partial(jax.jit, static_argnames=("cfg",))
def knn_chunk_update(
    q_tiles: jax.Array,  # (QT, q_tile, d)
    qid_tiles: jax.Array,  # (QT, q_tile)
    chunk_tiles: jax.Array,  # (T, c_tile, d) corpus tiles to merge in
    chunk_ids: jax.Array,  # (T, c_tile)
    carry_d: jax.Array,  # (QT, q_tile, k)
    carry_i: jax.Array,
    cfg: KNNConfig,
):
    """Merge a chunk of corpus tiles into the per-query top-k carry: scan
    over corpus tiles inside a map over query tiles. The one compiled core
    behind both the serial backend and the resumable driver — the serving
    path's :func:`serve_chunk` IS this body with the chunk norms hoisted
    to index state, so the two can never drift."""
    acc = jnp.float64 if q_tiles.dtype == jnp.float64 else jnp.float32
    if cfg.metric == "l2":
        chunk_sq = jax.vmap(sq_norms)(chunk_tiles)
    else:
        chunk_sq = jnp.zeros(chunk_tiles.shape[:2], dtype=acc)
    return serve_chunk(
        q_tiles, qid_tiles, carry_d, carry_i,
        chunk_tiles, chunk_ids, chunk_sq, cfg,
    )


def serve_chunk(
    q_tiles: jax.Array,  # (QT, q_tile, d) one padded query batch
    qid_tiles: jax.Array,  # (QT, q_tile)
    carry_d: jax.Array,  # (QT, q_tile, k) per-batch scratch (donatable)
    carry_i: jax.Array,
    tiles: jax.Array,  # (T, c_tile, d) RESIDENT corpus tiles
    tile_ids: jax.Array,  # (T, c_tile)
    tile_sqs: jax.Array,  # (T, c_tile) norms precomputed at index build
    cfg: KNNConfig,
):
    """One serving batch against a device-resident corpus index: the
    queries-vs-corpus generalization of :func:`knn_chunk_update` with the
    corpus-side work hoisted out of the batch entirely — tiles, global ids
    AND squared norms arrive precomputed (``serve.CorpusIndex`` builds them
    once), so the per-batch program is only the distance matmuls, masks and
    the top-k merge. The serving engine (``serve.engine``) AOT-compiles
    this per row bucket with ``carry_d``/``carry_i`` donated; argument
    order therefore keeps the batch-owned buffers first and the resident
    index last."""

    def per_query_tile(args):
        q_x, q_ids, cd, ci = args
        q_sq = sq_norms(q_x) if cfg.metric == "l2" else None
        return merge_tiles_into_carry(
            q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs, cd, ci, cfg
        )

    return jax.lax.map(per_query_tile, (q_tiles, qid_tiles, carry_d, carry_i))


def merge_tiles_into_carry(
    q_x: jax.Array,  # (q_tile, d)
    q_ids: jax.Array,  # (q_tile,)
    q_sq: jax.Array | None,
    tiles: jax.Array,  # (T, c_tile, d)
    tile_ids: jax.Array,  # (T, c_tile)
    tile_sqs: jax.Array,  # (T, c_tile)
    carry_d: jax.Array,  # (q_tile, k)
    carry_i: jax.Array,
    cfg: KNNConfig,
):
    """Merge a stack of corpus tiles into one query tile's top-k carry, per
    ``cfg.merge_schedule``. The single implementation behind the serial
    chunk scan and the ring backends' per-round block loop (the schedules
    must match or the ring's per-round cost diverges from serial's).

    - "twolevel": level 1 — independent local top-k per corpus tile (no
      carry dependence between scan steps, so XLA can pipeline the sort of
      tile t with the matmul of tile t+1); level 2 — ONE narrow cascade
      merge over the incoming carry plus every tile's k survivors,
      (n_tiles+1)·k columns instead of a (carry ‖ c_tile)-wide reduction
      per tile. Measured faster on v5e (BASELINE.md r3), now the default.
    - "stream": carry threaded through the tile scan — the reference's
      accumulate-as-you-go shape (``knn-serial.c:86-91``), batched.

    Under ``cfg.precision_policy="mixed"`` the per-tile reduction in BOTH
    schedules is the compress-and-rerank pipeline (ops/rerank.py): the wide
    DEFAULT-precision dot and the 4k overfetch happen inside the tile, the
    HIGHEST rerank finishes it, and what reaches the merges here is already
    exact — the schedules, the cascade, and the ring's per-round streaming
    merge are untouched by the policy.
    """
    if cfg.merge_schedule == "twolevel":

        def local(_, tile):
            blk, blk_ids, blk_sq = tile
            # per-tile reduction honors cfg.precision_policy (exact single
            # pass vs compress-and-rerank); either way k exact-f32
            # survivors per tile feed the level-2 cascade
            return None, local_tile_topk(
                q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, carry_d.dtype
            )

        _, (ld, li) = jax.lax.scan(local, None, (tiles, tile_ids, tile_sqs))
        n_tiles = ld.shape[0]
        q_rows = carry_d.shape[0]
        with jax.named_scope("knn.merge"):
            ld = jnp.moveaxis(ld, 0, 1).reshape(q_rows, n_tiles * cfg.k)
            li = jnp.moveaxis(li, 0, 1).reshape(q_rows, n_tiles * cfg.k)
            return cascade_smallest_k(
                jnp.concatenate([carry_d, ld], axis=-1),
                jnp.concatenate([carry_i, li], axis=-1),
                cfg.k,
                # survivors-of-survivors must merge exactly or recall
                # decays multiplicatively; "block" is exact,
                # "approx"/"bf16" are not
                method=(
                    cfg.topk_method
                    if cfg.topk_method in ("exact", "block")
                    else "exact"
                ),
                block=cfg.topk_block,
            )

    def step(carry, tile):
        blk, blk_ids, blk_sq = tile
        return (
            knn_tile_step(q_x, q_ids, q_sq, blk, blk_ids, blk_sq, *carry, cfg),
            None,
        )

    out, _ = jax.lax.scan(step, (carry_d, carry_i), (tiles, tile_ids, tile_sqs))
    return out


def cap_corpus_tile(q_tile: int, c_tile: int, max_tile_elems: int) -> int:
    """Shrink c_tile until q_tile × c_tile <= max_tile_elems — the hard
    bound on the per-step distance block a backend may materialize. The cap
    is rounded down to a 128 multiple while that keeps it >= 128 (MXU lane
    alignment); rounding down only ever shrinks, so the bound stays hard.
    Shared by the serial and ring backends so the memory plan is one policy."""
    cap = max(1, max_tile_elems // max(q_tile, 1))
    if cap >= 128:
        cap = cap // 128 * 128
    return min(c_tile, cap)


def effective_tiles(cfg: KNNConfig, m: int, nq: int) -> tuple[int, int]:
    """Clamp configured tiles to the (aligned) problem size so small inputs
    don't pay full-tile padding compute, and to ``cfg.max_tile_elems`` so a
    "whole corpus per tile" request can't materialize an HBM-busting
    (q_tile × c_tile) distance block at SIFT1M scale."""
    q_tile = min(cfg.query_tile, pad_to_multiple(nq, 8))
    c_tile = min(cfg.corpus_tile, pad_to_multiple(m, 128))
    return q_tile, cap_corpus_tile(q_tile, c_tile, cfg.max_tile_elems)


@jax.named_scope("knn.retile")
def prepare_tiles(corpus, queries, query_ids, cfg: KNNConfig, q_tile, c_tile):
    """Pad + reshape corpus/query arrays into device tile stacks. Host numpy
    inputs are padded on host then transferred once; device inputs are padded
    with on-device ops (no device→host round trip)."""
    m, dim = corpus.shape
    nq = queries.shape[0]
    dtype = jnp.dtype(cfg.dtype)

    c_pad = pad_to_multiple(m, c_tile)
    q_pad = pad_to_multiple(nq, q_tile)

    corpus_tiles = pad_rows_any(corpus, c_pad, dtype=dtype).reshape(-1, c_tile, dim)
    corpus_tile_ids = jnp.asarray(make_global_ids(m, c_pad).reshape(-1, c_tile))
    q_tiles = pad_rows_any(queries, q_pad, dtype=dtype).reshape(-1, q_tile, dim)
    qid_tiles = pad_rows_any(query_ids, q_pad, fill=-1, dtype=jnp.int32).reshape(
        -1, q_tile
    )
    return q_tiles, qid_tiles, corpus_tiles, corpus_tile_ids, q_pad


def all_knn_serial(
    corpus: np.ndarray,
    queries: np.ndarray,
    query_ids: np.ndarray,
    cfg: KNNConfig,
):
    """Host-side wrapper: pad to tile multiples, run the jitted core, strip
    padding. Returns ((q, k) dists, (q, k) ids) device arrays."""
    nq = queries.shape[0]
    q_tile, c_tile = effective_tiles(cfg, corpus.shape[0], nq)
    q_tiles, qid_tiles, corpus_tiles, corpus_tile_ids, q_pad = prepare_tiles(
        corpus, queries, query_ids, cfg, q_tile, c_tile
    )

    acc = jnp.float64 if q_tiles.dtype == jnp.float64 else jnp.float32
    carry_d, carry_i = init_topk_tiles(q_pad // q_tile, q_tile, cfg.k,
                                       dtype=acc)

    best_d, best_i = knn_chunk_update(
        q_tiles, qid_tiles, corpus_tiles, corpus_tile_ids, carry_d, carry_i, cfg
    )
    return (
        best_d.reshape(q_pad, cfg.k)[:nq],
        best_i.reshape(q_pad, cfg.k)[:nq],
    )
