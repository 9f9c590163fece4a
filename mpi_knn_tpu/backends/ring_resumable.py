"""Resumable ring execution: the ppermute ring driven one round at a time
from the host, with the sharded top-k carry checkpointed between rounds
(SURVEY.md §6 "Checkpoint / resume" — "the ring carry saved every R rounds;
resume continues rotation at round r").

The reference's failure model is all-or-nothing: any rank death aborts the
MPI job and every rank's partial neighbor lists are lost (stdout-only
results, ``/root/reference/knn-serial.c:130``; barriers turn hangs total,
``mpi-knn-parallel_blocking.c:111-243``). Here one jitted ring *round* is a
pure function from (block, carry) to (next block, merged carry); the host
loop owns the round cursor. A checkpoint is just (carry, rounds_done,
fingerprint): the rotating block needs no saving because after r rounds
device i holds corpus block (i − r) mod P — reconstructed on resume by
rolling the padded corpus r blocks forward before sharding. Under
``cfg.ring_schedule="bidir"`` the same single cursor reconstructs BOTH
resident travelers (forward at i−r, backward at i+r: the corpus rolled r
blocks each way), the loop runs ⌊P/2⌋+1 rounds instead of P, and the
schedule is folded into the checkpoint fingerprint so uni and bidir
carries — whose rounds_done mean different merged-block prefixes — can
never cross-resume.

``stop_after_rounds`` is the fault-injection hook (SURVEY.md §6 "failure
detection / fault injection"): tests kill the run at an arbitrary round and
assert the resumed result is bit-identical to an uninterrupted one.

``cfg.precision_policy="mixed"`` changes nothing here by construction: the
compress/rerank passes complete inside each round's tile reduction (the
rerank runs against the resident block before the round returns), so the
checkpointed carry is the same exact-f32 (q, k) layout in either policy and
a checkpoint written under one policy is invalidated only by the config
fingerprint — never by a layout mismatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.backends.ring import (
    _query_spec,
    _ring_knn_local,
    bidir_rounds,
    blocking_undefined_on_mesh_error,
    parse_ring_mesh,
    quantize_ring_block,
    ring_tiles,
)
from mpi_knn_tpu.ops.topk import init_topk
from mpi_knn_tpu.parallel.distributed import fetch_global
from mpi_knn_tpu.parallel.mesh import make_ring_mesh
from mpi_knn_tpu.parallel.partition import (
    make_global_ids,
    pad_rows,
    pad_rows_any,
)
from mpi_knn_tpu.utils.logs import log
from mpi_knn_tpu.utils.checkpoint import (
    KNNCheckpoint,
    fingerprint,
    load_checkpoint,
    save_checkpoint,
)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "overlap", "mesh", "axis", "q_tile", "c_tile", "q_axis",
        "rotate",
    ),
)
def _ring_one_round(
    queries,
    query_ids,
    block,
    block_ids,
    carry_d,
    carry_i,
    cfg,
    overlap,
    mesh,
    axis,
    q_tile,
    c_tile,
    q_axis=None,
    rotate=True,
    block_scale=None,
):
    """One ring round: merge the currently-held block into the carry and
    rotate the block one hop. Same schedule semantics as the scan step in
    backends.ring (overlap=True lets XLA put the ICI transfer under the
    matmul; False sequences compute before the send). The host passes
    ``rotate=False`` on the final round: in the scan path the last permute
    is dead code XLA eliminates, but here the block is a live jit output and
    would pay a real ICI transfer for nothing.

    Under ``cfg.ring_transfer_dtype="int8"`` the block is int8 codes and
    ``block_scale`` its per-row scale vector (quantized once by the driver
    before the round loop); the rotated scales are returned alongside the
    rotated codes — (nxt, nxt_scale, nxt_ids, carry_d, carry_i)."""
    quantized = cfg.ring_transfer_dtype == "int8"
    qspec = _query_spec(q_axis, axis)
    cspec = P(axis)
    if not quantized:

        def body(q, qid, blk, bids, cd, ci):
            one = functools.partial(
                _ring_knn_local,
                cfg=cfg,
                overlap=overlap,
                axis=axis,
                q_tile=q_tile,
                c_tile=c_tile,
                vary_axes=tuple(mesh.axis_names),
                single_round=True,
                carry_in=(cd, ci),
                rotate=rotate,
            )
            return one(q, qid, blk, bids)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(qspec, qspec, cspec, cspec, qspec, qspec),
            out_specs=(cspec, cspec, qspec, qspec),
        )
        return fn(queries, query_ids, block, block_ids, carry_d, carry_i)

    def body_q(q, qid, blk, bscl, bids, cd, ci):
        one = functools.partial(
            _ring_knn_local,
            cfg=cfg,
            overlap=overlap,
            axis=axis,
            q_tile=q_tile,
            c_tile=c_tile,
            vary_axes=tuple(mesh.axis_names),
            single_round=True,
            carry_in=(cd, ci),
            rotate=rotate,
        )
        return one(q, qid, blk, bids, block_scale=bscl)

    fn = jax.shard_map(
        body_q,
        mesh=mesh,
        in_specs=(qspec, qspec, cspec, cspec, cspec, qspec, qspec),
        out_specs=(cspec, cspec, cspec, qspec, qspec),
    )
    return fn(
        queries, query_ids, block, block_scale, block_ids, carry_d, carry_i
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "overlap", "mesh", "axis", "q_tile", "c_tile", "q_axis",
        "rotate", "merge_bwd",
    ),
)
def _ring_one_round_bidir(
    queries,
    query_ids,
    fblock,
    fblock_ids,
    bblock,
    bblock_ids,
    carry_d,
    carry_i,
    cfg,
    overlap,
    mesh,
    axis,
    q_tile,
    c_tile,
    q_axis=None,
    rotate=True,
    merge_bwd=False,
    fblock_scale=None,
    bblock_scale=None,
):
    """One bidirectional ring round: merge the forward traveler (block
    i−r), merge the backward traveler (block i+r) unless the round is
    degenerate (``merge_bwd=False``: round 0, and the antipodal round at
    even P), then rotate both travelers one hop in opposite directions.
    ``merge_bwd`` is static — the host knows the round plan, so the
    degenerate rounds compile to genuinely single-merge programs rather
    than masked double merges. Int8 transfer threads both travelers'
    scale vectors and returns them rotated (8-tuple instead of 6)."""
    quantized = cfg.ring_transfer_dtype == "int8"
    qspec = _query_spec(q_axis, axis)
    cspec = P(axis)
    if not quantized:

        def body(q, qid, fb, fids, bb, bids, cd, ci):
            one = functools.partial(
                _ring_knn_local,
                cfg=cfg,
                overlap=overlap,
                axis=axis,
                q_tile=q_tile,
                c_tile=c_tile,
                vary_axes=tuple(mesh.axis_names),
                single_round=True,
                carry_in=(cd, ci),
                rotate=rotate,
                merge_bwd=merge_bwd,
            )
            return one(q, qid, fb, fids, block_bwd=bb, block_bwd_ids=bids)

        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(qspec, qspec, cspec, cspec, cspec, cspec, qspec,
                      qspec),
            out_specs=(cspec, cspec, cspec, cspec, qspec, qspec),
        )
        return fn(
            queries, query_ids, fblock, fblock_ids, bblock, bblock_ids,
            carry_d, carry_i,
        )

    def body_q(q, qid, fb, fscl, fids, bb, bscl, bids, cd, ci):
        one = functools.partial(
            _ring_knn_local,
            cfg=cfg,
            overlap=overlap,
            axis=axis,
            q_tile=q_tile,
            c_tile=c_tile,
            vary_axes=tuple(mesh.axis_names),
            single_round=True,
            carry_in=(cd, ci),
            rotate=rotate,
            merge_bwd=merge_bwd,
        )
        return one(
            q, qid, fb, fids, block_scale=fscl, block_bwd=bb,
            block_bwd_ids=bids, block_bwd_scale=bscl,
        )

    fn = jax.shard_map(
        body_q,
        mesh=mesh,
        in_specs=(qspec, qspec, cspec, cspec, cspec, cspec, cspec, cspec,
                  qspec, qspec),
        out_specs=(cspec, cspec, cspec, cspec, cspec, cspec, qspec, qspec),
    )
    return fn(
        queries, query_ids, fblock, fblock_scale, fblock_ids,
        bblock, bblock_scale, bblock_ids, carry_d, carry_i,
    )


def all_knn_ring_resumable(
    corpus,
    queries,
    query_ids,
    cfg: KNNConfig,
    mesh: Mesh | None = None,
    overlap: bool = True,
    checkpoint_dir=None,
    save_every: int = 1,
    stop_after_rounds: int | None = None,
    progress_cb=None,
):
    """Ring all-kNN with host-driven rounds and carry checkpoints.

    Returns ((q, k) dists, (q, k) ids); with ``stop_after_rounds`` set it
    returns the partial carry after that many rounds (fault injection —
    a subsequent call with the same checkpoint_dir completes the run).
    """
    if mesh is None:
        mesh = make_ring_mesh(cfg.num_devices, axis_name=cfg.mesh_axis)
    q_axis, axis, dp, ring_n = parse_ring_mesh(mesh)
    if not overlap and q_axis is not None:
        # same hard error as the scan-based driver (VERDICT r5 weak #3):
        # blocking on a dp×ring mesh would silently run the overlap schedule
        raise blocking_undefined_on_mesh_error(mesh.axis_names)
    bidir = cfg.ring_schedule == "bidir"
    # bidir: ⌊P/2⌋+1 host rounds; after r of them device i holds the
    # forward traveler (i−r) AND the backward traveler (i+r) — one cursor,
    # two reconstructible block positions
    rounds_total, bwd_limit = (
        bidir_rounds(ring_n) if bidir else (ring_n, 0)
    )

    corpus = corpus if isinstance(corpus, jax.Array) else np.asarray(corpus)
    all_pairs = queries is corpus
    queries = queries if isinstance(queries, jax.Array) else np.asarray(queries)
    # run identity: data + config + mesh topology (a different ring size
    # changes block layout, so a carry from another mesh must not resume).
    # fingerprint() samples the WHOLE array stridedly (device-side for jax
    # arrays), so content changes anywhere in the corpus invalidate resume.
    # The ring schedule is part of cfg (hashed by fingerprint()) AND spelled
    # out here: a uni carry means "blocks 0..r−1 of the uni order merged", a
    # bidir carry means "the two-cursor prefix merged" — the same
    # rounds_done under the other schedule would silently skip/duplicate
    # blocks, so the two must never cross-resume.
    # ring_fusion (one legal value left, config.py) keeps its term in the
    # suffix for as long as the field exists.
    fp = (
        fingerprint(corpus, queries, cfg)
        + f":ring{ring_n}x{dp}:{int(overlap)}:{cfg.ring_schedule}"
        + f":{cfg.ring_fusion}"
    )
    if cfg.center and cfg.metric == "l2":
        # centering accumulates the corpus mean in f32 on the device path
        # but f64 on the host path (center_for_l2), so carries from the two
        # residencies differ by fp noise near ties. Fold the residency into
        # the run identity so a cross-residency resume restarts cleanly
        # instead of silently merging mixed-centering carries (ADVICE r1).
        fp += f":ctr-{'dev' if isinstance(corpus, jax.Array) else 'host'}"

        from mpi_knn_tpu.ops.distance import center_for_l2, offset_is_whole

        corpus, queries, _, mu = center_for_l2(corpus, queries, all_pairs)
        if offset_is_whole(mu):
            # a whole-number corpus is centred by its ROUNDED mean; a
            # carry saved under the plain mean differs by fp noise. (The
            # rounds here keep the configured dot: exact on such rows.)
            fp += ":whole"

    m, dim = corpus.shape
    nq = queries.shape[0]
    dtype = jnp.dtype(cfg.dtype)

    # same tiling policy as the scan-based ring (shared helper — a drift
    # here would make a saved carry's layout stop matching)
    q_tile, c_tile, q_pad, c_pad = ring_tiles(cfg, m, nq, dp, ring_n)

    acc = jnp.float64 if dtype == jnp.float64 else jnp.float32
    start_round = 0
    carry_d, carry_i = init_topk(q_pad, cfg.k, dtype=acc)

    if checkpoint_dir is not None:
        if jax.process_count() > 1:
            # Multi-host: only process 0 writes checkpoints, so only process
            # 0's read DECIDES. Letting every process trust its own local
            # read (non-shared dir, torn file -> corruption-tolerant None)
            # could start processes at different rounds — mismatched
            # collectives hang or corrupt instead of erroring. Broadcast
            # (rounds_done, carry) from process 0 so all hosts agree.
            from jax.experimental import multihost_utils

            state = (
                load_checkpoint(checkpoint_dir, fp)
                if jax.process_index() == 0
                else None
            )
            done0 = np.int32(0 if state is None else state.tiles_done)
            start_round = int(multihost_utils.broadcast_one_to_all(done0))
            if start_round > 0:
                shape = (q_pad, cfg.k)
                cd = (
                    np.asarray(state.carry_d, dtype=acc)
                    if state is not None
                    else np.zeros(shape, dtype=acc)
                )
                ci = (
                    np.asarray(state.carry_i, dtype=np.int32)
                    if state is not None
                    else np.zeros(shape, dtype=np.int32)
                )
                carry_d = jnp.asarray(
                    multihost_utils.broadcast_one_to_all(cd), dtype=acc
                )
                carry_i = jnp.asarray(
                    multihost_utils.broadcast_one_to_all(ci)
                )
        else:
            state = load_checkpoint(checkpoint_dir, fp)
            if state is not None:
                start_round = state.tiles_done  # field reused as rounds_done
                carry_d = jnp.asarray(state.carry_d, dtype=acc)
                carry_i = jnp.asarray(state.carry_i)
        if start_round:
            log.info("resuming ring at round %d/%d from %s",
                     start_round, rounds_total, checkpoint_dir)

    # after r rounds device i holds block (i − r) mod ring_n: roll the padded
    # corpus r blocks forward so sharding lands blocks correctly on resume.
    # The bidir schedule's backward traveler sits at (i + r) — the SAME
    # cursor, rolled the other way — so a one-integer checkpoint still
    # reconstructs both resident blocks exactly.
    # Host inputs are rolled in numpy BEFORE the transfer (no extra device
    # copy); a device-resident corpus pays one transient on-device duplicate
    # (jnp.roll), acceptable because such a corpus already fits one device.
    shift = start_round * (c_pad // ring_n)

    def _rolled(arr, s):
        """Padded corpus (or ids) rolled s rows forward, residency-aware."""
        if isinstance(arr, jax.Array):
            out = pad_rows_any(arr, c_pad, dtype=dtype)
            return jnp.roll(out, s, axis=0) if s else out
        out = pad_rows(np.asarray(arr), c_pad)
        if s:
            out = np.roll(out, s, axis=0)
        return jnp.asarray(out, dtype=dtype)

    corpus_ids_np = make_global_ids(m, c_pad)
    corpus_ids = jnp.asarray(np.roll(corpus_ids_np, shift) if shift else
                             corpus_ids_np)
    corpus_p = _rolled(corpus, shift)
    if bidir:
        bwd_ids = jnp.asarray(np.roll(corpus_ids_np, -shift) if shift else
                              corpus_ids_np)
        bwd_p = _rolled(corpus, -shift) if shift else corpus_p
    queries_p = pad_rows_any(queries, q_pad, dtype=dtype)
    qids_p = pad_rows_any(query_ids, q_pad, fill=-1, dtype=jnp.int32)

    c_sharding = NamedSharding(mesh, P(axis))
    q_sharding = NamedSharding(mesh, _query_spec(q_axis, axis))
    corpus_scale = bwd_scale = None
    if cfg.ring_transfer_dtype == "int8":
        # quantize BEFORE the round loop (the shard-time contract of
        # backends.ring): per-row quantization commutes with the resume
        # roll, and the codes are a deterministic function of the f32
        # corpus — so a resumed run reconstructs bit-identical travelers
        # by re-rolling and re-quantizing, with the one-integer checkpoint
        # cursor unchanged. The scale vectors thread through every round
        # alongside the codes.
        corpus_p, corpus_scale = quantize_ring_block(corpus_p)
        if bidir:
            if shift:
                bwd_p, bwd_scale = quantize_ring_block(bwd_p)
            else:
                bwd_p, bwd_scale = corpus_p, corpus_scale
    elif cfg.ring_transfer_dtype is not None:
        # cast BEFORE the round loop so every _ring_one_round call sees the
        # same block dtype — the in-body cast would otherwise retrace and
        # recompile the whole sharded round between round 0 (compute dtype)
        # and round 1 (transfer dtype). Resume reconstructs the block from
        # the f32 corpus and re-casts here, so the values match a
        # never-interrupted run exactly (the cast is deterministic).
        corpus_p = corpus_p.astype(jnp.dtype(cfg.ring_transfer_dtype))
        if bidir:
            bwd_p = bwd_p.astype(jnp.dtype(cfg.ring_transfer_dtype))
    block = jax.device_put(corpus_p, c_sharding)
    block_ids = jax.device_put(corpus_ids, c_sharding)
    block_scale = (
        jax.device_put(corpus_scale, c_sharding)
        if corpus_scale is not None else None
    )
    if bidir:
        block_b = jax.device_put(bwd_p, c_sharding)
        block_b_ids = jax.device_put(bwd_ids, c_sharding)
        block_b_scale = (
            jax.device_put(bwd_scale, c_sharding)
            if bwd_scale is not None else None
        )
    queries_p = jax.device_put(queries_p, q_sharding)
    qids_p = jax.device_put(qids_p, q_sharding)
    carry_d = jax.device_put(carry_d, q_sharding)
    carry_i = jax.device_put(carry_i, q_sharding)

    total = rounds_total if stop_after_rounds is None else min(
        rounds_total, start_round + stop_after_rounds
    )
    quantized = cfg.ring_transfer_dtype == "int8"
    for r in range(start_round, total):
        if bidir:
            out = _ring_one_round_bidir(
                queries_p,
                qids_p,
                block,
                block_ids,
                block_b,
                block_b_ids,
                carry_d,
                carry_i,
                cfg,
                overlap,
                mesh,
                axis,
                q_tile,
                c_tile,
                q_axis=q_axis,
                rotate=(r + 1 < rounds_total),
                # degenerate rounds (r=0; the antipodal round at even P)
                # merge the forward traveler only — see ring.bidir_rounds
                merge_bwd=(1 <= r < bwd_limit),
                fblock_scale=block_scale,
                bblock_scale=block_b_scale if bidir else None,
            )
            if quantized:
                (block, block_scale, block_ids, block_b, block_b_scale,
                 block_b_ids, carry_d, carry_i) = out
            else:
                (block, block_ids, block_b, block_b_ids,
                 carry_d, carry_i) = out
        else:
            out = _ring_one_round(
                queries_p,
                qids_p,
                block,
                block_ids,
                carry_d,
                carry_i,
                cfg,
                overlap,
                mesh,
                axis,
                q_tile,
                c_tile,
                q_axis=q_axis,
                rotate=(r + 1 < rounds_total),
                block_scale=block_scale,
            )
            if quantized:
                block, block_scale, block_ids, carry_d, carry_i = out
            else:
                block, block_ids, carry_d, carry_i = out
        done = r + 1
        if checkpoint_dir is not None and (
            done % save_every == 0 or done == rounds_total
        ):
            carry_d.block_until_ready()
            # multi-host: the carry spans processes; allgather the full array
            # (every process sees it), then only process 0 writes — the
            # checkpoint dir is assumed shared/visible on resume
            cd_h, ci_h = fetch_global(carry_d), fetch_global(carry_i)
            if jax.process_index() == 0:
                save_checkpoint(
                    checkpoint_dir,
                    KNNCheckpoint(
                        carry_d=cd_h,
                        carry_i=ci_h,
                        tiles_done=done,
                        fingerprint=fp,
                    ),
                )
        log.debug("ring round %d/%d done", done, rounds_total)
        if progress_cb is not None:
            progress_cb(done, rounds_total)

    return carry_d[:nq], carry_i[:nq]
