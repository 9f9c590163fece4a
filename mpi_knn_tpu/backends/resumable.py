"""Resumable serial execution: the serial backend's corpus-tile stream driven
from the host in rounds, with the top-k carry checkpointed between rounds
(SURVEY.md §6 "Checkpoint / resume").

Math is identical to backends.serial — it calls the same jitted
``knn_chunk_update`` core — but the corpus scan is cut into host-visible
chunks so a killed run restarts from the last saved round rather than from
zero. Used for long runs (SIFT1M-scale) and by the CLI's --checkpoint-dir.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.topk import init_topk
from mpi_knn_tpu.backends.serial import (
    effective_tiles,
    knn_chunk_update,
    prepare_tiles,
)
from mpi_knn_tpu.utils.logs import log
from mpi_knn_tpu.utils.checkpoint import (
    KNNCheckpoint,
    fingerprint,
    load_checkpoint,
    save_checkpoint,
)


def all_knn_resumable(
    corpus: np.ndarray,
    queries: np.ndarray,
    query_ids: np.ndarray,
    cfg: KNNConfig,
    checkpoint_dir=None,
    save_every: int = 8,
    progress_cb=None,
):
    """Serial all-kNN with host-driven rounds of `save_every` corpus tiles.

    If checkpoint_dir holds a state matching this (data, config), computation
    resumes after the last completed round. Returns ((q, k) dists, ids).
    """
    corpus = np.asarray(corpus)
    queries = np.asarray(queries)
    # identity of the run = the data as the caller provided it
    fp = fingerprint(corpus, queries, cfg)
    all_pairs = queries is corpus or (
        queries.shape == corpus.shape and np.shares_memory(queries, corpus)
    )
    onepass = None
    if cfg.center and cfg.metric == "l2":
        from mpi_knn_tpu.ops.distance import (
            center_for_l2,
            offset_is_whole,
            onepass_fact,
        )

        corpus, queries, fact, mu = center_for_l2(corpus, queries, all_pairs)
        onepass = onepass_fact(cfg, fact)
        if offset_is_whole(mu):
            # a whole-number corpus is centred by its ROUNDED mean; a
            # carry saved under the plain mean differs by fp noise
            fp += ":ctr-whole"

    nq = queries.shape[0]
    q_tile, c_tile = effective_tiles(cfg, corpus.shape[0], nq)
    q_tiles, qid_tiles, corpus_tiles, corpus_tile_ids, q_pad = prepare_tiles(
        corpus, queries, query_ids, cfg, q_tile, c_tile
    )
    tiles = corpus_tiles.shape[0]
    qt_count = q_pad // q_tile

    acc = jnp.float64 if q_tiles.dtype == jnp.float64 else jnp.float32
    start_tile = 0
    carry_d, carry_i = init_topk(q_pad, cfg.k, dtype=acc)
    carry_d = carry_d.reshape(qt_count, q_tile, cfg.k)
    carry_i = carry_i.reshape(qt_count, q_tile, cfg.k)

    if checkpoint_dir is not None:
        state = load_checkpoint(checkpoint_dir, fp)
        if state is not None:
            start_tile = state.tiles_done
            carry_d = jnp.asarray(state.carry_d, dtype=acc)
            carry_i = jnp.asarray(state.carry_i)
            log.info("resuming serial stream at tile %d/%d from %s",
                     start_tile, tiles, checkpoint_dir)

    for t0 in range(start_tile, tiles, save_every):
        t1 = min(t0 + save_every, tiles)
        # with the fact a third output counts the one-pass query tiles
        carry_d, carry_i, *_ = knn_chunk_update(
            q_tiles,
            qid_tiles,
            corpus_tiles[t0:t1],
            corpus_tile_ids[t0:t1],
            carry_d,
            carry_i,
            cfg,
            onepass,
        )
        if checkpoint_dir is not None:
            carry_d.block_until_ready()
            save_checkpoint(
                checkpoint_dir,
                KNNCheckpoint(
                    carry_d=np.asarray(carry_d),
                    carry_i=np.asarray(carry_i),
                    tiles_done=t1,
                    fingerprint=fp,
                ),
            )
        if progress_cb is not None:
            progress_cb(t1, tiles)

    best_d = carry_d.reshape(q_pad, cfg.k)[:nq]
    best_i = carry_i.reshape(q_pad, cfg.k)[:nq]
    return best_d, best_i
