"""Distributed ring backends — the TPU-native replacement for the reference's
MPI corpus-rotation ring (SURVEY.md C7/C8).

The reference hand-rolls a ring from blocking point-to-point sends with
role-ordered deadlock avoidance (``/root/reference/mpi-knn-parallel_blocking.c:122-214``)
and a "non-blocking" variant that posts Isend/Irecv but MPI_Waits *before*
computing, achieving no overlap (``mpi-knn-parallel_non_blocking.c:229-233``,
SURVEY.md Q7). Both also carry a rotation off-by-one: each rank computes
against its own block twice and never sees its ring-predecessor's block
(SURVEY.md Q1), so distributed results never matched serial.

Here the ring is ``jax.lax.ppermute`` over a 1-D device mesh inside
``shard_map`` — the permute embeds natively in the ICI torus; deadlock freedom
and progress are the XLA runtime's problem, and SPMD dataflow replaces every
``MPI_Barrier``. The rotation is written correctly: P compute steps, each
against a distinct block (own block + P−1 received), property-tested equal to
the serial backend.

Two variants, matching the reference's pair but with the overlap done right:

- ``overlap=False`` ("ring", blocking parity): each scan step *computes, then
  permutes*, with an ``optimization_barrier`` threading the compute outputs so
  the collective truly waits for the compute — the reference's blocking
  schedule, kept as a pedagogical baseline and as the A side of the overlap
  A/B benchmark. Machine-checked in HLO (``tests/test_hlo_overlap.py``);
  enforced on the 1-D ring (the reference's layout) — see the in-step note
  for why a multi-axis mesh pins only the block.
- ``overlap=True`` ("ring-overlap"): the permute of block b+1 is issued in the
  same scan step that computes distances against block b, with no dependency
  between them — XLA schedules the ICI DMA under the MXU matmul. This is the
  double-buffered pipeline the reference's non-blocking variant intended.

Orthogonally, ``cfg.ring_schedule`` picks the rotation pattern:

- ``"uni"`` (default): the reference's one-directional ring — P rounds, each
  block moving rank → rank+1, using half of each full-duplex ICI link.
- ``"bidir"``: every block circulates in BOTH torus directions at once (a
  +1 and a −1 ``ppermute`` in the same scan step), so at round r a device
  holds blocks i−r and i+r and merges both; the scan runs ⌊P/2⌋+1 rounds
  instead of P. Total block-hops are conserved but travel concurrently over
  the two link directions, halving the exposed communication critical path
  (EQuARX's bidirectional-ring AllReduce moves data the same way, PAPERS.md).
  Degenerate rounds merge once — round 0 both travelers are the own block;
  at even P the antipodal block arrives from both sides on the last round —
  via a ``lax.cond`` on the (device-invariant) round index, so no distance
  work is duplicated. Bit-identity to serial and to the uni schedule is
  property-tested at every mesh size; the round count and the
  counter-directed permute pair are machine-checked from the lowered HLO
  (``tests/test_hlo_overlap.py``, lint rule R4).

Memory per device is O(m/P · d) for the rotating block plus the O(q_local · k)
carry — the corpus-ring is the same skeleton ring-attention uses for long
sequences, applied to a corpus axis (SURVEY.md §2a), and corpus capacity
scales linearly with devices.

``cfg.precision_policy="mixed"`` composes with the ring for free: the
compress-and-rerank pipeline lives inside the shared per-tile reduction
(backends.serial.local_tile_topk via merge_tiles_into_carry), so each
round's compress dot and exact rerank both run against the RESIDENT block
— nothing about the rotation, the collective schedule, or the carry type
changes, and the carry stays exact f32 across rounds.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.distance import (
    bf16_exact,
    center_corpus,
    onepass_applies,
    onepass_fact,
    sq_norms,
)
from mpi_knn_tpu.ops.quant import (
    dequantize_rows,
    quantize_rows,
    row_wire_bytes,
)
from mpi_knn_tpu.ops.topk import init_topk, lane_bin_bound_rides
from mpi_knn_tpu.backends.serial import (
    PreparedCorpus,
    cap_corpus_tile,
    TileCounts,
    carried_depth,
    dist_steps,
    fused_rule,
    tile_counts,
    merge_tiles_into_carry,
    onepass_rule,
)
from mpi_knn_tpu.parallel.mesh import make_ring_mesh
from mpi_knn_tpu.parallel.partition import pad_rows_any, pad_to_multiple
from mpi_knn_tpu.types import INVALID_ID


# HLO scopes of the ring's own operations, stable like ``knn.dist``: the
# trace's collective-permutes and a round's compute carry these names
PERMUTE_SCOPE = "knn.ring/permute"
ROUND_SCOPE = "knn.ring/round"


def bidir_rounds(num_dev: int) -> tuple[int, int]:
    """Round plan of the bidirectional schedule: ``(rounds, bwd_limit)``.

    ``rounds = ⌊P/2⌋ + 1`` scan steps; the backward traveler merges on
    rounds ``1 <= r < bwd_limit`` with ``bwd_limit = ⌈P/2⌉``. Outside that
    window the round is degenerate and merges ONCE: at r=0 both travelers
    are the own block, and for even P the antipodal block (r = P/2) arrives
    from both directions simultaneously. Blocks merged per device:
    ``1 + 2·(bwd_limit−1) + (1 if P even and P>1 else 0) = P`` — every
    block exactly once, same as the P-round uni schedule."""
    return num_dev // 2 + 1, -(-num_dev // 2)


def blocking_undefined_on_mesh_error(mesh_axes) -> ValueError:
    """The one wording for the 2-D-mesh × blocking-schedule hard error,
    shared by both ring drivers and the trace-time backstop (VERDICT r5
    weak #3: the blocking barrier can pin only the rotating block on a
    multi-axis mesh — varying-axes typing, see the in-step note — so
    'blocking' there would silently run the overlap schedule)."""
    return ValueError(
        "the blocking schedule (backend='ring' / overlap=False) is "
        f"undefined on a multi-axis mesh (axes {tuple(mesh_axes)}): the "
        "optimization barrier can pin only the rotating block there, so "
        "the requested compute-then-send sequencing would silently run as "
        "the overlap schedule. The 1-D ring is the only defined blocking "
        "A/B object — use backend='ring-overlap' with --dp, or drop --dp."
    )


def _ring_knn_local(
    queries: jax.Array,  # (q_local, d) this device's query rows
    query_ids: jax.Array,  # (q_local,)
    block: jax.Array,  # (b, d) this device's corpus shard (int8 codes
    # when cfg.ring_transfer_dtype == "int8" — quantized at shard time)
    block_ids: jax.Array,  # (b,)
    cfg: KNNConfig,
    overlap: bool,
    axis: str,
    q_tile: int,  # divides q_local
    c_tile: int,  # divides b
    vary_axes: tuple = (),  # all manual axes (for marking the carry varying)
    single_round: bool = False,  # run ONE round and return the rotated block
    carry_in=None,  # ((q_local, k) dists, ids) to continue from (resume)
    rotate: bool = True,  # single-round only: skip the ppermute on the last
    # round (the scan path gets this for free via dead-code elimination; a
    # live jit output would actually pay the ICI transfer)
    block_scale=None,  # (b,) f32 per-row scales of an int8-quantized block
    block_bwd=None,  # bidir single-round only: the backward traveler
    block_bwd_ids=None,
    block_bwd_scale=None,  # bidir int8 single-round only
    merge_bwd: bool = False,  # bidir single-round only: merge the backward
    # traveler too (False on the degenerate rounds — r=0 and, for even P,
    # the antipodal round)
    onepass=None,  # whole rotations of the XLA float ring only: the corpus
    # side of the one-pass rule (backends.serial.masked_dist_tile), one
    # replicated bool scalar; adds a third output, this device's
    # backends.serial.TileCounts, a row each: its tile steps by the branch
    # they took ((1, 2); (1, 4) where the one-pass branch is the kernel that
    # walks the arriving stack, backends.serial.dist_steps) and, where the
    # rounds' scans carry the lane-bin lists, its (query tile, round) merges
    # by what became of the carried selection and, under a row bound, the
    # chunks of its distance tiles, (1, 2) each
):
    """Per-device body under shard_map: rotate corpus blocks around the ring,
    merging each into the local top-k carry.

    The per-device (q_local × b) problem is itself tiled — queries via
    ``lax.map`` over q_tile rows, the incoming block via ``lax.scan`` over
    c_tile rows — so device memory stays O(q_tile·c_tile + q_local·k + b·d)
    regardless of shard size, same as the serial backend's streaming.
    ``cfg.ring_schedule="bidir"`` adds a second resident block (the
    backward traveler) — still O(b·d), now ×2.

    ``cfg.ring_transfer_dtype="int8"`` blocks arrive PRE-QUANTIZED (the
    host wrappers run ``ops.quant.quantize_rows`` once at shard time —
    quantizing in here would re-pay the reduction per serve batch and, in
    the overlap schedule, hang it off the permutes' backward slice) with
    their per-row scale vector riding alongside: every schedule permutes
    (codes, scales, ids) together — R4 counts 3 permutes per direction —
    and each round dequantizes codes·scale directly into the compress dot
    (the convert/multiply pair lint rule R3 demands). The exact HIGHEST
    rerank finish of the mixed pipeline is untouched; it just reranks the
    dequantized rows, which is what the recall gate measures.

    With ``single_round=True`` (the resumable driver,
    backends.ring_resumable) exactly one round runs and the rotated block(s)
    are returned alongside the merged carry, so the host owns the round
    cursor."""
    num_dev = jax.lax.axis_size(axis)
    bidir = cfg.ring_schedule == "bidir"
    quantized = cfg.ring_transfer_dtype == "int8"
    # send to the next rank, wrap at the end — the reference's ring direction
    # (rank -> rank+1, mpi-knn-parallel_blocking.c:131); bidir adds the
    # counter-rotating permute so both ICI link directions carry a block
    perm = [(i, (i + 1) % num_dev) for i in range(num_dev)]
    perm_bwd = [(i, (i - 1) % num_dev) for i in range(num_dev)]

    if not overlap and set(vary_axes or (axis,)) != {axis}:
        # trace-time backstop for the wrapper-level check: on a multi-axis
        # mesh the barrier below could pin only the block (an
        # optimization_barrier unifies its outputs' varying sets, and this
        # JAX has no varying->invarying pcast for the carry), i.e. the
        # blocking schedule would silently BE the overlap schedule. Refuse
        # rather than mislabel — tests/test_mesh2d.py asserts this.
        raise blocking_undefined_on_mesh_error(vary_axes)

    if quantized:
        if block.dtype != jnp.int8 or block_scale is None:
            raise ValueError(
                "int8 ring transfer expects the block pre-quantized at "
                "shard time (int8 codes + the per-row scale vector) — the "
                "host wrappers quantize once via ops.quant.quantize_rows"
            )
    elif cfg.ring_transfer_dtype is not None:
        # circulate the block at the transfer dtype (bf16 halves the bytes
        # every ppermute moves over ICI); cast ONCE here — rounding does not
        # compound per hop — and upcast per round inside compute()
        block = block.astype(jnp.dtype(cfg.ring_transfer_dtype))
        if block_bwd is not None:
            block_bwd = block_bwd.astype(jnp.dtype(cfg.ring_transfer_dtype))

    q_local, dim = queries.shape
    b = block.shape[0]
    acc = jnp.float64 if queries.dtype == jnp.float64 else jnp.float32

    def _rot(x, p):
        """ppermute one traveler part; scale slots are None when the
        transfer is not quantized (None = empty pytree, nothing moves)."""
        if x is None:
            return None
        with jax.named_scope(PERMUTE_SCOPE):
            return jax.lax.ppermute(x, axis, p)

    def tiled(x):
        """(b, ...) -> (b / c_tile, c_tile, ...): a traveller as the stack
        of tiles that ``compute`` walks. The ring rotates the stack, not
        the rows: the v5e compiler keeps a (b, d) scan carry rows-minor and
        then pays, in EVERY round, a copy of the whole block into the tile
        stack's layout — at 1 048 576 x 784 rows a chip two more blocks alive
        (13.2 GiB of temporaries, which does not fit) and a fifth of a
        second; as a stack the block is laid out once, ahead of the scan
        (6.9 GiB; PERF.md §6, PR 28), and the kernel that walks a stack
        (``ops/fused_scan.py``, the one-pass branch of a round's merge
        where ``backends.serial.fused_rule`` engages) reads the arriving
        block where it lands."""
        if x is None:
            return x
        return x.reshape(b // c_tile, c_tile, *x.shape[1:])

    def rows(x):
        """``tiled``'s inverse, for the travellers a single round returns."""
        if x is None:
            return x
        return x.reshape(b, *x.shape[2:])

    q_tiles = queries.reshape(q_local // q_tile, q_tile, dim)
    qid_tiles = query_ids.reshape(q_local // q_tile, q_tile)
    # each query tile's verdict holds for every round: the travelling
    # blocks are parts of the one corpus the fact speaks for
    q_one = None if onepass is None else (
        onepass & jax.vmap(bf16_exact)(q_tiles))
    # the rounds' operands vary over the mesh under the ring's checked
    # ``shard_map``, as ``merge_tiles_into_carry`` reads it
    varying = bool(jax.typeof(queries).vma | jax.typeof(block).vma)
    # whether the one-pass branch of a round's merge is the kernel that
    # walks the arriving stack (the rule ``merge_tiles_into_carry`` asks of
    # the same shapes): its steps count as ``fused`` and it counts chunks
    kernel_walks = q_one is not None and bool(fused_rule(
        cfg, q_tile, c_tile, dim, varying))
    block, block_ids, block_scale = map(
        tiled, (block, block_ids, block_scale))
    block_bwd, block_bwd_ids, block_bwd_scale = map(
        tiled, (block_bwd, block_bwd_ids, block_bwd_scale))

    if carry_in is not None:
        carry_d = carry_in[0].reshape(q_local // q_tile, q_tile, cfg.k)
        carry_i = carry_in[1].reshape(q_local // q_tile, q_tile, cfg.k)
    else:
        carry_d, carry_i = init_topk(q_local, cfg.k, dtype=acc)
        carry_d = carry_d.reshape(q_local // q_tile, q_tile, cfg.k)
        carry_i = carry_i.reshape(q_local // q_tile, q_tile, cfg.k)
        # the carry starts replicated but each device's top-k diverges; mark
        # it device-varying over every manual mesh axis (ring always; dp too
        # on a 2-D mesh, where per-device queries differ) so the scan carry
        # type is stable from step 0
        vary = tuple(vary_axes) or (axis,)
        carry_d = jax.lax.pcast(carry_d, vary, to="varying")
        carry_i = jax.lax.pcast(carry_i, vary, to="varying")

    @jax.named_scope(ROUND_SCOPE)
    def compute(blk, blk_ids, blk_scl, cd, ci):
        """Tiled (q_local × b) step: all query tiles against all block
        tiles. Returns the carry and, third, what the round's scans counted
        where they carried the lane-bin lists (``backends.serial
        merge_tiles_into_carry``), a pair: one verdict a query tile
        (flagged rows were answered again) and one ``[inserted, skipped]``
        a query tile (the chunks of its distance tiles in *bins*); else
        (None, None)."""
        if blk_scl is not None:
            # the int8 dequant: ONE convert out of the code domain and ONE
            # multiply by the block's scale vector, feeding every distance
            # dot of the round (the contract lint rule R3 checks); norms
            # below are recomputed from the dequantized rows, so distances
            # are exact w.r.t. the quantized values
            blk = dequantize_rows(blk, blk_scl, "int8", dim)
        # the travellers are tile stacks already (``tiled`` above)
        blk_tiles = blk.astype(queries.dtype)  # no-op unless ring_transfer_dtype
        # cosine: no corpus-side state travels with a block, so a round's
        # tile steps normalise their own operands (masked_dist_tile)
        blk_sq = jax.vmap(sq_norms)(blk_tiles) if cfg.metric == "l2" else None

        def per_query_tile(args):
            q_x, q_ids, cd0, ci0, one = args
            q_sq = sq_norms(q_x) if cfg.metric == "l2" else None
            # within a round the block's tiles merge per cfg.merge_schedule
            # (same code path as serial); the cross-ROUND merge is inherently
            # streaming — each rotation step merges into the carry
            return merge_tiles_into_carry(
                q_x, q_ids, q_sq, blk_tiles, blk_ids, blk_sq,
                cd0, ci0, cfg, one,
            )

        cd, ci, *counted = jax.lax.map(
            per_query_tile, (q_tiles, qid_tiles, cd, ci, q_one))
        return cd, ci, counted

    def step(state, _):
        # ``tally``: the whole rotation's count of re-scanned merges and
        # its ``[inserted, skipped]`` chunks, where they are kept (below),
        # else nothing
        blk, scl, blk_ids, cd, ci, *tally = state
        if overlap:
            # permute and compute both depend only on the incoming block —
            # XLA overlaps the ICI transfer with the distance matmul (the
            # quantized scale vector rides the same schedule)
            nxt = _rot(blk, perm)
            nscl = _rot(scl, perm)
            nxt_ids = _rot(blk_ids, perm)
            cd, ci, counted = compute(blk, blk_ids, scl, cd, ci)
        else:
            # blocking parity: the collective is sequenced *after* the compute
            # via an explicit barrier, modelling the reference's
            # compute-then-Send/Recv schedule. The carry MUST thread through
            # the barrier too: a barrier over (blk, blk_ids) alone creates no
            # data dependence from the compute to the permute, and XLA may
            # schedule them concurrently — i.e. "blocking" would silently be
            # the overlap schedule (caught by tests/test_hlo_overlap.py,
            # which found exactly that bug in the pre-r5 code). On a
            # multi-axis mesh this threading is type-impossible (the raise
            # above), so reaching here means the 1-D ring.
            cd, ci, counted = compute(blk, blk_ids, scl, cd, ci)
            blk, scl, blk_ids, cd, ci = jax.lax.optimization_barrier(
                (blk, scl, blk_ids, cd, ci)
            )
            nxt = _rot(blk, perm)
            nscl = _rot(scl, perm)
            nxt_ids = _rot(blk_ids, perm)
        return (nxt, nscl, nxt_ids, cd, ci,
                *(n + jnp.sum(new, axis=0, dtype=jnp.int32)
                  for n, new in zip(tally, counted))), None

    rounds, bwd_limit = bidir_rounds(num_dev)

    def finish(cd, ci, rescanned=None, chunks=None):
        out = cd.reshape(q_local, cfg.k), ci.reshape(q_local, cfg.k)
        if q_one is None:
            return out
        # a query tile meets every corpus tile once a rotation, whatever
        # the schedule, and merges once a round
        merges = num_dev * q_one.size
        return *out, TileCounts(
            dist_steps(q_one, num_dev * (b // c_tile),
                       fused=kernel_walks).reshape(1, -1),
            None if rescanned is None else jnp.stack(
                [merges - rescanned, rescanned]).reshape(1, 2),
            None if chunks is None else chunks.reshape(1, 2),
        )

    def bidir_step(state, r):
        """One full-duplex round: the forward traveler (block i−r) always
        merges; the backward traveler (block i+r) merges only on the
        non-degenerate rounds (``lax.cond`` on the device-invariant round
        index, so degenerate rounds pay ONE block's distance work, not a
        masked two). Both permutes are issued every round — the pipeline
        must keep both travelers moving even when one of them is not merged
        this round."""
        fblk, fscl, fids, bblk, bscl, bids, cd, ci = state
        do_bwd = jnp.logical_and(r >= 1, r < bwd_limit)

        def merge_bwd_traveler(cd, ci):
            return compute(bblk, bids, bscl, cd, ci)[:2]

        def skip(cd, ci):
            return cd, ci

        def merge(cd, ci):
            # the forward traveler merges unconditionally — only the
            # backward merge is round-dependent, so the heavy per-tile
            # reduction is traced once per branch role, not duplicated
            # across both cond branches
            cd, ci, _ = compute(fblk, fids, fscl, cd, ci)
            return jax.lax.cond(do_bwd, merge_bwd_traveler, skip, cd, ci)

        if overlap:
            # all permutes depend only on the incoming blocks; the two
            # directions ride the two halves of each full-duplex ICI link
            nfb = _rot(fblk, perm)
            nfs = _rot(fscl, perm)
            nfi = _rot(fids, perm)
            nbb = _rot(bblk, perm_bwd)
            nbs = _rot(bscl, perm_bwd)
            nbi = _rot(bids, perm_bwd)
            cd, ci = merge(cd, ci)
        else:
            cd, ci = merge(cd, ci)
            (fblk, fscl, fids, bblk, bscl, bids, cd, ci) = (
                jax.lax.optimization_barrier(
                    (fblk, fscl, fids, bblk, bscl, bids, cd, ci)
                )
            )
            nfb = _rot(fblk, perm)
            nfs = _rot(fscl, perm)
            nfi = _rot(fids, perm)
            nbb = _rot(bblk, perm_bwd)
            nbs = _rot(bscl, perm_bwd)
            nbi = _rot(bids, perm_bwd)
        return (nfb, nfs, nfi, nbb, nbs, nbi, cd, ci), None

    if single_round:
        if bidir:
            if block_bwd is None or block_bwd_ids is None:
                raise ValueError(
                    "bidir single-round needs the backward traveler "
                    "(block_bwd/block_bwd_ids)"
                )
            if quantized and block_bwd_scale is None:
                raise ValueError(
                    "bidir int8 single-round needs the backward traveler's "
                    "scale vector (block_bwd_scale)"
                )
            carry_d, carry_i, _ = compute(
                block, block_ids, block_scale, carry_d, carry_i
            )
            if merge_bwd:
                carry_d, carry_i, _ = compute(
                    block_bwd, block_bwd_ids, block_bwd_scale,
                    carry_d, carry_i,
                )
            if rotate:
                if not overlap:
                    (block, block_scale, block_ids, block_bwd,
                     block_bwd_scale, block_bwd_ids,
                     carry_d, carry_i) = jax.lax.optimization_barrier(
                        (block, block_scale, block_ids, block_bwd,
                         block_bwd_scale, block_bwd_ids,
                         carry_d, carry_i)
                    )
                nfb = _rot(block, perm)
                nfs = _rot(block_scale, perm)
                nfi = _rot(block_ids, perm)
                nbb = _rot(block_bwd, perm_bwd)
                nbs = _rot(block_bwd_scale, perm_bwd)
                nbi = _rot(block_bwd_ids, perm_bwd)
            else:
                nfb, nfs, nfi = block, block_scale, block_ids
                nbb, nbs, nbi = block_bwd, block_bwd_scale, block_bwd_ids
            nfb, nfs, nfi, nbb, nbs, nbi = map(
                rows, (nfb, nfs, nfi, nbb, nbs, nbi))
            out_d = carry_d.reshape(q_local, cfg.k)
            out_i = carry_i.reshape(q_local, cfg.k)
            if quantized:
                # the rotated scale vectors are live state the resumable
                # driver must thread to the next round (arity differs from
                # the float path; the drivers branch on the static cfg)
                return nfb, nfs, nfi, nbb, nbs, nbi, out_d, out_i
            return nfb, nfi, nbb, nbi, out_d, out_i
        if rotate:
            (nxt, nscl, nxt_ids, carry_d, carry_i), _ = step(
                (block, block_scale, block_ids, carry_d, carry_i), None
            )
        else:
            carry_d, carry_i, _ = compute(
                block, block_ids, block_scale, carry_d, carry_i
            )
            nxt, nscl, nxt_ids = block, block_scale, block_ids
        nxt, nscl, nxt_ids = map(rows, (nxt, nscl, nxt_ids))
        out_d = carry_d.reshape(q_local, cfg.k)
        out_i = carry_i.reshape(q_local, cfg.k)
        if quantized:
            return nxt, nscl, nxt_ids, out_d, out_i
        return nxt, nxt_ids, out_d, out_i

    if bidir:
        # ⌊P/2⌋+1 steps, both travelers starting as the own block. The last
        # step's permutes are unused; XLA dead-code-eliminates them. The
        # round index rides as the scan xs so the degenerate-round cond is
        # part of the one compiled step body (the HLO scan trip count IS
        # the round count — machine-checked in tests/test_hlo_overlap.py).
        (_, _, _, _, _, _, carry_d, carry_i), _ = jax.lax.scan(
            bidir_step,
            (block, block_scale, block_ids,
             block, block_scale, block_ids, carry_d, carry_i),
            jnp.arange(rounds),
        )
        return finish(carry_d, carry_i)

    # P steps: own block once, then each of the P-1 received blocks — the
    # correct rotation the reference missed (SURVEY.md Q1). The final
    # permute's output is unused; XLA dead-code-eliminates it.
    # where the device's counts go out (``onepass``) and the rounds' scans
    # carry the lists, the rotation counts its re-scanned merges and, where
    # the row bound rides them too (beside the scan's lists, or inside the
    # kernel that walks the stack), the chunks its *bins* inserted and
    # skipped
    tally = ()
    if q_one is not None and carried_depth(
            cfg, q_tile, c_tile, varying) is not None:
        tally = tuple(
            jax.lax.pcast(jnp.zeros(shape, jnp.int32),
                          tuple(vary_axes) or (axis,), to="varying")
            for shape in ((), (2,))[:1 + (kernel_walks or lane_bin_bound_rides(
                q_tile, c_tile, jnp.dtype(carry_d.dtype).itemsize))])
    (_, _, _, carry_d, carry_i, *tally), _ = jax.lax.scan(
        step, (block, block_scale, block_ids, carry_d, carry_i, *tally),
        None, length=num_dev
    )
    return finish(carry_d, carry_i, *tally)


def parse_ring_mesh(mesh: Mesh):
    """Single source of truth for mesh-axis interpretation, shared with the
    resumable driver: returns (q_axis, ring_axis, dp, ring_n). 1-D = pure
    ring; 2-D = (dp, ring) with the ring on the minor axis; anything else is
    rejected (silently treating a 3-D mesh as a ring would merge each block
    into the carry multiple times — wrong results, not an error)."""
    if len(mesh.axis_names) == 2:
        q_axis, axis = mesh.axis_names
        dp, ring_n = mesh.devices.shape
    elif len(mesh.axis_names) == 1:
        q_axis, axis = None, mesh.axis_names[0]
        dp, ring_n = 1, mesh.devices.size
    else:
        raise ValueError(
            f"mesh must be 1-D (ring) or 2-D (dp × ring), got axes "
            f"{mesh.axis_names}"
        )
    return q_axis, axis, dp, ring_n


def ring_tiles(cfg: KNNConfig, m: int, nq: int, dp: int, ring_n: int):
    """Per-device tile sizes and padded global sizes for a (dp × ring) run —
    one policy for the scan-based and resumable ring drivers (divergence
    would make a checkpointed carry's layout stop matching)."""
    num_dev = dp * ring_n
    c_tile = min(cfg.corpus_tile, -(-m // ring_n))
    q_tile = min(cfg.query_tile, -(-nq // num_dev))
    c_tile = cap_corpus_tile(q_tile, c_tile, cfg.max_tile_elems)
    c_pad = pad_to_multiple(m, ring_n * c_tile)
    q_pad = pad_to_multiple(nq, num_dev * q_tile)
    return q_tile, c_tile, q_pad, c_pad


def _query_spec(q_axis, axis):
    """Single source of truth for the query PartitionSpec: queries shard over
    EVERY mesh axis (each device owns a distinct query slice — total work
    nq·m splits over all devices) while the corpus shards over the ring axis
    only. The host-side device_put and the shard_map in_specs must agree or
    XLA silently reshards the padded query array before every run."""
    return P((q_axis, axis)) if q_axis else P(axis)


def ring_wire_bytes_per_batch(
    cfg: KNNConfig, c_pad: int, dim: int, ring_n: int
) -> int:
    """Bytes ONE full rotation moves over the interconnect, summed over all
    devices — static per (config, corpus layout), priced at the WIRE dtype
    (f32/bf16 rows, or int8 codes + the f32 scale vector) plus the s32 id
    row that always rides along. This is the number the serving engine
    stamps into the ``ring_transfer_wire_bytes`` gauge at lower time (no
    device reads), so the bf16/int8 byte cuts are visible in
    ``mpi-knn metrics`` next to the recall they paid."""
    b = c_pad // ring_n
    itemsize = jnp.dtype(cfg.ring_transfer_dtype or cfg.dtype).itemsize \
        if cfg.ring_transfer_dtype != "int8" else 4
    block_bytes = b * row_wire_bytes(
        dim, cfg.ring_transfer_dtype if cfg.ring_transfer_dtype == "int8"
        else None, itemsize,
    ) + b * 4  # the global-id row
    if cfg.ring_schedule == "bidir":
        rounds, _ = bidir_rounds(ring_n)
        hops = 2 * (rounds - 1) * ring_n  # both travelers, last round DCE'd
    else:
        hops = (ring_n - 1) * ring_n
    return hops * block_bytes


def quantize_ring_block(corpus_p: jax.Array):
    """The shard-time int8 quantization of a padded corpus: (c_pad, d)
    float rows → ((c_pad, d) int8 codes, (c_pad,) f32 scales). One place —
    the one-shot driver, the resumable driver and the serve index build
    must produce bit-identical codes or a resumed/served run would diverge
    from a fresh one."""
    return quantize_rows(corpus_p, "int8")


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "overlap", "mesh", "axis", "q_tile", "c_tile", "q_axis"
    ),
)
def _ring_knn_sharded(
    queries,
    query_ids,
    corpus,
    corpus_ids,
    cfg,
    overlap,
    mesh,
    axis,
    q_tile,
    c_tile,
    q_axis=None,
    corpus_scale=None,
    onepass=None,
):
    """Shard-mapped ring. On a 1-D mesh queries and corpus share the ring
    axis (the reference's layout). On a 2-D (dp × ring) mesh queries shard
    over `q_axis` (data parallel) while the corpus rings over `axis` — each
    dp group runs an independent ring over its replica of the corpus.
    ``corpus_scale`` is the per-row scale vector of an int8-quantized
    corpus (``ring_transfer_dtype="int8"``; quantized at shard time by the
    host wrapper), sharded like the corpus. ``onepass`` is
    :func:`_ring_knn_local`'s, replicated; with it the third output holds
    one row of each of its counts a device."""
    body = functools.partial(
        _ring_knn_local,
        cfg=cfg,
        overlap=overlap,
        axis=axis,
        q_tile=q_tile,
        c_tile=c_tile,
        vary_axes=tuple(mesh.axis_names),
    )
    qspec = _query_spec(q_axis, axis)
    cspec = P(axis)
    # the optional operands, as keywords of the one body
    extra = {
        name: (value, spec)
        for name, value, spec in (
            ("block_scale", corpus_scale, cspec), ("onepass", onepass, P()))
        if value is not None
    }

    def local(q, qi, c, cids, *rest):
        return body(q, qi, c, cids, **dict(zip(extra, rest)))

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, qspec, cspec, cspec,
                  *(spec for _, spec in extra.values())),
        out_specs=(qspec, qspec, *((qspec,) if "onepass" in extra else ())),
    )
    return fn(queries, query_ids, corpus, corpus_ids,
              *(value for value, _ in extra.values()))


def ring_serve_sharded(
    queries,
    query_ids,
    carry_d,
    carry_i,
    corpus,
    corpus_ids,
    corpus_scale,  # (c_pad,) f32 scales of an int8 index, else None
    cfg,
    overlap,
    mesh,
    axis,
    q_tile,
    c_tile,
    q_axis=None,
):
    """Queries-vs-resident-corpus ring batch: the full rotation of
    :func:`_ring_knn_sharded` run against a corpus that STAYS sharded on
    the mesh across batches (``serve.CorpusIndex``), with the per-batch
    top-k scratch threaded in from outside via ``carry_in`` so the serving
    engine can AOT-compile this per row bucket and donate the scratch
    (the donated buffers alias the sharded outputs — lint rule R5 reads
    that contract back from the module header). Batch-owned arrays first,
    resident index after, mirroring ``backends.serial.serve_chunk``."""
    body = functools.partial(
        _ring_knn_local,
        cfg=cfg,
        overlap=overlap,
        axis=axis,
        q_tile=q_tile,
        c_tile=c_tile,
        vary_axes=tuple(mesh.axis_names),
    )

    qspec = _query_spec(q_axis, axis)
    cspec = P(axis)
    if corpus_scale is None:

        def with_carry(q, qi, cd, ci, c, cids):
            return body(q, qi, c, cids, carry_in=(cd, ci))

        fn = jax.shard_map(
            with_carry,
            mesh=mesh,
            in_specs=(qspec, qspec, qspec, qspec, cspec, cspec),
            out_specs=(qspec, qspec),
        )
        return fn(queries, query_ids, carry_d, carry_i, corpus, corpus_ids)

    def with_carry_scale(q, qi, cd, ci, c, cids, cscl):
        return body(q, qi, c, cids, carry_in=(cd, ci), block_scale=cscl)

    fn = jax.shard_map(
        with_carry_scale,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, qspec, cspec, cspec, cspec),
        out_specs=(qspec, qspec),
    )
    return fn(
        queries, query_ids, carry_d, carry_i, corpus, corpus_ids,
        corpus_scale,
    )


@functools.partial(jax.jit, static_argnames=("sharding", "m", "c_pad"))
def _global_ids_on(sharding: NamedSharding, m: int, c_pad: int) -> jax.Array:
    """The corpus's global id row (``make_global_ids``' rule), made where
    its shards live: at 4 M rows the host array was 16 MB to build and send
    on every call of a sliced job."""
    row = jnp.arange(c_pad, dtype=jnp.int32)
    return jax.lax.with_sharding_constraint(
        jnp.where(row < m, row, INVALID_ID), sharding)


def ring_form(cfg: KNNConfig, m: int, dim: int, nq: int,
              mesh: Mesh | None, backend: str) -> dict:
    """The form of a :class:`RingCorpus` for ``nq``-row calls
    (``backends.serial.serial_form``'s counterpart): builds and validates
    the mesh, and derives the tile and the padding from the query rows
    too."""
    if mesh is None:
        mesh = make_ring_mesh(cfg.num_devices, axis_name=cfg.mesh_axis)
    q_axis, _, dp, ring_n = parse_ring_mesh(mesh)
    if backend == "ring" and q_axis is not None:
        # VERDICT r5 weak #3: on a dp×ring mesh the blocking barrier can pin
        # only the block, so "blocking" would silently run the overlap
        # schedule — a hard error, not a silent mislabel (see DESIGN.md §3)
        raise blocking_undefined_on_mesh_error(mesh.axis_names)
    # pad both corpus and query axes so each device's shard divides cleanly
    # into on-device tiles (the reference silently required P | m,
    # SURVEY.md Q6 — we pad + mask). Tiles shrink to the shard size for
    # small problems so padding never exceeds P·tile rows; the per-tile
    # memory cap (cfg.max_tile_elems) is applied inside ring_tiles.
    _, c_tile, _, c_pad = ring_tiles(cfg, m, nq, dp, ring_n)
    return dict(
        backend=backend, m=m, dim=dim, dtype=cfg.dtype, metric=cfg.metric,
        center=cfg.center,
        # the ring at a float wire takes the one-pass rule; every other
        # form runs the program it always ran
        onepass_applies=(onepass_applies(cfg)
                         and cfg.ring_transfer_dtype is None),
        c_tile=c_tile, c_pad=c_pad, mesh=mesh, wire=cfg.ring_transfer_dtype,
    )


@dataclasses.dataclass(frozen=True, eq=False)
class RingCorpus(PreparedCorpus):
    """The ring's form: the centred corpus padded and placed on the ring's
    sharding, its id row, and the int8 wire's scales where that wire is
    configured. What a round does to an arriving block (its norms, the
    scan's copy) is the rotation program's, every call."""

    corpus_p: jax.Array  # (c_pad, d) rows over the ring axis (int8: codes)
    corpus_ids: jax.Array  # (c_pad,)
    corpus_scale: jax.Array | None  # (c_pad,) f32, the int8 wire only

    def search(self, queries, query_ids, cfg: KNNConfig):
        mesh, c_pad = self.form["mesh"], self.form["c_pad"]
        q_axis, axis, dp, ring_n = parse_ring_mesh(mesh)
        nq = queries.shape[0]
        q_tile, _, q_pad, _ = ring_tiles(cfg, self.m, nq, dp, ring_n)
        rounds = (bidir_rounds(ring_n)[0] if cfg.ring_schedule == "bidir"
                  else ring_n)
        wire_bytes = ring_wire_bytes_per_batch(cfg, c_pad, self.dim, ring_n)
        if self.mu is not None:
            queries = queries - self.mu  # center_for_l2's own subtraction

        # entry until the last dispatch has returned, inside knn:api.all_knn
        with obs_spans.span(
            "call", cat="ring", devices=dp * ring_n, rounds=rounds,
            rows_per_block=c_pad // ring_n, wire_bytes=wire_bytes,
        ):
            dtype = jnp.dtype(cfg.dtype)
            q_sharding = NamedSharding(mesh, _query_spec(q_axis, axis))
            queries_p = jax.device_put(
                pad_rows_any(queries, q_pad, dtype=dtype), q_sharding)
            qids_p = jax.device_put(
                pad_rows_any(query_ids, q_pad, fill=-1, dtype=jnp.int32),
                q_sharding)
            best_d, best_i, *counts = _ring_knn_sharded(
                queries_p,
                qids_p,
                self.corpus_p,
                self.corpus_ids,
                cfg,
                self.form["backend"] == "ring-overlap",
                mesh,
                axis,
                q_tile,
                self.c_tile,
                q_axis=q_axis,
                corpus_scale=self.corpus_scale,
                onepass=self.onepass if onepass_rule(cfg, q_tile) else None,
            )
            # static per layout, added at dispatch: no device read
            reg = obs_metrics.get_registry()
            reg.counter("ring_calls_total", help="ring searches").inc()
            reg.counter(
                "ring_rounds_total", help="rotation rounds dispatched"
            ).inc(rounds)
            reg.counter(
                "ring_wire_bytes_total",
                help="bytes all devices send over the interconnect "
                "(ring_wire_bytes_per_batch a call)",
            ).inc(wire_bytes)
            return best_d[:nq], best_i[:nq], tile_counts(
                counts, q_pad // q_tile, c_pad // self.c_tile, cfg.metric)


def prepare_ring(corpus, cfg: KNNConfig, form: dict) -> RingCorpus:
    """Every pass a ring call makes over its corpus, once
    (``backends.serial.prepare_serial``'s counterpart): centre, pad, shard
    the corpus over the ring axis (ids as a separate row — no
    augmented-row smuggling, SURVEY.md C6), quantize for the int8 wire.
    ``form`` is :func:`ring_form`'s for the calls to come."""
    mesh, c_pad = form["mesh"], form["c_pad"]
    axis = parse_ring_mesh(mesh)[1]
    m, dim = corpus.shape
    mu = fact = None
    if cfg.center and cfg.metric == "l2":
        corpus, mu, fact = center_corpus(corpus)
    # a device corpus that already lies as the ring wants it passes through
    # all of this untouched: no pad, no cast, and device_put onto the
    # sharding it has returns the array it was given
    corpus_p = pad_rows_any(corpus, c_pad, dtype=jnp.dtype(cfg.dtype))
    del corpus
    corpus_scale = None
    if cfg.ring_transfer_dtype == "int8":
        # quantize ONCE at shard time (the EQuARX recipe): the rotation
        # program receives (codes, scales) as inputs and only ever
        # dequantizes — the quantization reduce never enters the compiled
        # ring, so the overlap schedule's permutes stay compute-independent
        corpus_p, corpus_scale = quantize_ring_block(corpus_p)
    c_sharding = NamedSharding(mesh, P(axis))
    corpus_p = jax.device_put(corpus_p, c_sharding)
    corpus_ids = _global_ids_on(c_sharding, m, c_pad)
    if corpus_scale is not None:
        corpus_scale = jax.device_put(corpus_scale, c_sharding)
    # read last: the shards' placement is queued behind the centring pass
    # that the read waits for (a form without the rule reads nothing)
    onepass = onepass_fact(cfg, fact) if form["onepass_applies"] else None
    return RingCorpus(
        form, m, dim, form["c_tile"], mu, onepass,
        corpus_p, corpus_ids, corpus_scale,
    )
