"""Fused collective-matmul ring rotation (``KNNConfig.ring_fusion="fused"``).

The XLA-level ring (backends/ring.py) issues ``ppermute`` and the distance
compute as separate HLO ops and lets the compiler schedule them
concurrently — lint rule R1 certifies that the schedule *can* overlap, and
obs/attribution measures whether it *did*. This module moves the rotation
inside the Pallas distance kernel, the TPU-KNN/collective-matmul form: the
resident corpus block is on the MXU computing its distance tiles while an
async remote copy (``pltpu.make_async_remote_copy`` with send/recv DMA
semaphores) streams the SAME block over ICI into the next device's landing
buffer — the latency is hidden by construction, not by scheduler goodwill.

Execution forms, chosen by the driver (backends/ring.py):

- **TPU, round mode** — one fused kernel per ring round
  (:func:`fused_round_dma`). Grid is (query_tiles, block_tiles); the first
  grid cell opens a neighbor barrier (``pltpu.get_barrier_semaphore``) and
  starts the remote copies of the whole resident block — codes, scales and
  ids travel exactly as the wire format holds them (int8 codes are NOT
  dequantized before send; the dequant happens in-kernel into each round's
  compress/exact dot) — and the last grid cell waits both DMA semaphore
  sides. The landing buffers are kernel outputs in ``ANY`` (HBM) space:
  they and the resident block are the two slots of the double buffer,
  alternated by the round scan's carry threading.
- **TPU, grid mode** (``ring_fused_rotation="grid"``, behind a flag,
  :func:`fused_rotation_grid`) — the whole P-round rotation as ONE kernel
  launch with rounds on the major grid axis and the block double-buffered
  between two explicit HBM scratch slots, slot reuse gated by a
  receiver→sender capacity handshake; uni/exact, float wire only.
- **CPU interpret** (:func:`fused_block_merge`) — the same kernel body
  computes (interpret mode inlines it into the surrounding XLA program),
  transport stays a driver-level ``ppermute`` moving the identical wire
  bytes. This is the form the tier-1 parity matrix certifies: fused
  results are asserted BIT-IDENTICAL to the XLA-level ring across
  P × schedule × policy × wire dtype (tests/test_ring_fused.py).

Bit-identity is by construction, not luck: the in-kernel tile distances
use the exact expression structure of ops.distance.pairwise_sq_l2 +
ops.topk.mask_tile (same dot shape, precision, accumulation, mask
thresholds), the in-kernel carry merge is ``_k_smallest_sweep`` — bitwise
equal to ``smallest_k``'s ``lax.top_k`` (ascending order, leftmost-column
ties) — and the mixed policy's in-kernel compress pass emits preselect
POSITIONS bitwise equal to ``ops.topk.preselect_smallest`` (the
taken-mask sweep reproduces top_k's index-order hand-out on exhausted
+inf slots), so the shared XLA-side ``rerank_exact_topk`` consumes
identical survivor rows in identical order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_knn_tpu.ops.pallas_knn import _I32_MAX, _ZERO_RTOL, _k_smallest_sweep
from mpi_knn_tpu.ops.quant import dequantize_rows
from mpi_knn_tpu.ops.rerank import (
    mixed_applies,
    overfetch_width,
    rerank_exact_topk,
)
from mpi_knn_tpu.ops.topk import smallest_k


def _k_smallest_positions(d, v):
    """v-pass min extraction emitting COLUMN POSITIONS, bitwise equal to
    ``ops.topk.preselect_smallest`` (= positions of ``lax.top_k(-d, v)``):
    ascending by value, ties to the leftmost column — including the
    exhausted case, where top_k hands out the remaining +inf columns in
    index order. A plain knock-out-with-inf sweep gets that last case
    wrong (it would re-pick column 0 forever), so extraction state is an
    explicit ``taken`` mask instead of overwriting the values."""
    q, c = d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (q, c), 1)
    taken = jnp.zeros((q, c), dtype=jnp.bool_)
    out = []
    for _ in range(v):
        avail = jnp.where(taken, jnp.inf, d)
        row_min = jnp.min(avail, axis=1, keepdims=True)
        # when row_min is +inf every un-taken column compares equal to it,
        # so first_col degrades exactly to "first un-taken column" — the
        # top_k exhausted-slot order
        is_min = jnp.logical_and(~taken, avail == row_min)
        first_col = jnp.min(
            jnp.where(is_min, col, _I32_MAX), axis=1, keepdims=True
        )
        out.append(first_col[:, 0])
        taken = jnp.logical_or(taken, col == first_col)
    return jnp.stack(out, axis=1)


def _load_wire_tile(blk, scl, wire_dtype: str | None, dim: int):
    """The in-kernel arrival of one resident-block tile: exactly the cast
    the XLA ring applies once per round (backends/ring.py compute()) —
    int8 codes·scale dequant, bf16 upcast, f32 passthrough — so the rows
    every dot consumes are bitwise the XLA path's."""
    if wire_dtype == "int8":
        return dequantize_rows(blk, scl[:, 0], "int8", dim)
    return blk.astype(jnp.float32)


def _masked_ring_tile(
    q, blk, q_ids, blk_ids, *, exclude_self, exclude_zero, zero_eps,
    precision, compress,
):
    """(q_tile, c_tile) masked squared-L2 tile of a ring block — the
    kernel-side mirror of backends.serial.masked_dist_tile (exact) and
    ops.rerank.compress_tile + its id-only mask (compress). Candidate ids
    are OPERANDS (the rotated block's global ids), not grid-affine — a
    ring block's ids are arbitrary after rotation and carry the padding
    sentinel (−1) the masks key on. ``q_ids``/``blk_ids`` arrive as
    (rows, 1) columns (TPU block shapes are 2-D)."""
    q_sq = jnp.sum(q * q, axis=-1, keepdims=True)  # (q_tile, 1)
    c_sq = jnp.sum(blk * blk, axis=-1, keepdims=True).T  # (1, c_tile)
    xy = jax.lax.dot_general(
        q.astype(jnp.bfloat16) if compress else q,
        blk.astype(jnp.bfloat16) if compress else blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT if compress else precision,
    )
    raw = q_sq - 2.0 * xy + c_sq
    # compress keys are never clamped (ops.rerank.compress_tile returns
    # the raw expression — a clamp would reorder near-zero preselect ties
    # vs the XLA path); the exact tile clamps like pairwise_sq_l2
    d = raw if compress else jnp.maximum(raw, 0.0)
    ids_row = blk_ids[:, 0][None, :]  # (1, c_tile)
    invalid = ids_row < 0  # divisibility-padding sentinel rows
    if exclude_zero and not compress:
        # same semantics as ops.topk.mask_tile: explicit absolute eps
        # wins, else relative to the pair magnitude q_sq + c_sq
        thresh = zero_eps if zero_eps > 0.0 else _ZERO_RTOL * (q_sq + c_sq)
        invalid = invalid | (d <= thresh)
    if exclude_self:
        invalid = invalid | (ids_row == q_ids[:, 0][:, None])
    return jnp.where(invalid, jnp.inf, d)


def _exact_merge_body(
    q_ref, qid_ref, blk_ref, scl_ref, bid_ref, cind_ref, cini_ref,
    outd_ref, outi_ref, cd_ref, ci_ref,
    *, k, dim, wire_dtype, exclude_self, exclude_zero, zero_eps, precision,
):
    """One ring round's exact-policy block merge: for a fixed query tile
    the block-tile sweep (minor grid axis, sequential on TPU) threads the
    running top-k through VMEM scratch, merging each masked tile with the
    stream semantics — concat(carry ‖ full tile), k-sweep — which is
    bitwise ``smallest_k(concat(carry, d), ..., method="exact")``."""
    ci = pl.program_id(1)
    n_c = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        cd_ref[:] = cind_ref[:]
        ci_ref[:] = cini_ref[:]

    blk = _load_wire_tile(
        blk_ref[:], scl_ref[:] if scl_ref is not None else None,
        wire_dtype, dim,
    )
    d = _masked_ring_tile(
        q_ref[:], blk, qid_ref[:], bid_ref[:],
        exclude_self=exclude_self, exclude_zero=exclude_zero,
        zero_eps=zero_eps, precision=precision, compress=False,
    )
    all_d = jnp.concatenate([cd_ref[:], d], axis=1)
    all_i = jnp.concatenate(
        [ci_ref[:], jnp.broadcast_to(bid_ref[:][:, 0][None, :], d.shape)],
        axis=1,
    )
    md, mi = _k_smallest_sweep(all_d, all_i, k)
    cd_ref[:] = md
    ci_ref[:] = mi

    @pl.when(ci == n_c - 1)
    def _emit():
        outd_ref[:] = cd_ref[:]
        outi_ref[:] = ci_ref[:]


def _make_exact_kernel(quantized, **kw):
    """Positional-signature adapters: pallas passes refs positionally, so
    the quantized form has a scale ref slot and the float form must not."""
    if quantized:
        def kern(q, qid, blk, scl, bid, cind, cini, outd, outi, cd, ci_):
            _exact_merge_body(
                q, qid, blk, scl, bid, cind, cini, outd, outi, cd, ci_,
                **kw,
            )
    else:
        def kern(q, qid, blk, bid, cind, cini, outd, outi, cd, ci_):
            _exact_merge_body(
                q, qid, blk, None, bid, cind, cini, outd, outi, cd, ci_,
                **kw,
            )
    return kern


def _compress_body(
    q_ref, qid_ref, blk_ref, scl_ref, bid_ref, pos_ref,
    *, ov, dim, wire_dtype, exclude_self,
):
    """Mixed policy pass 1, in-kernel: the bf16 DEFAULT compress dot over
    the (dequantized) block tile, id-only masking, and the top-ov
    preselect POSITIONS out — bitwise ``preselect_smallest`` of
    ops.rerank.compress_rerank_tile. The survivors' exact rerank and the
    carry merge stay in the shared XLA code (fused_block_merge below), so
    the carry algebra cannot drift from the XLA ring's."""
    blk = _load_wire_tile(
        blk_ref[:], scl_ref[:] if scl_ref is not None else None,
        wire_dtype, dim,
    )
    d_lo = _masked_ring_tile(
        q_ref[:], blk, qid_ref[:], bid_ref[:],
        exclude_self=exclude_self, exclude_zero=False, zero_eps=0.0,
        precision=None, compress=True,
    )
    pos_ref[0] = _k_smallest_positions(d_lo, ov)


def _make_compress_kernel(quantized, **kw):
    if quantized:
        def kern(q, qid, blk, scl, bid, pos):
            _compress_body(q, qid, blk, scl, bid, pos, **kw)
    else:
        def kern(q, qid, blk, bid, pos):
            _compress_body(q, qid, blk, None, bid, pos, **kw)
    return kern


def _exact_precision(cfg):
    """The exact-policy dot precision, resolved the way ops.distance does
    for f32 inputs (fused requires dtype='float32'): HIGHEST unless
    explicitly overridden."""
    if cfg.matmul_precision is None:
        return jax.lax.Precision.HIGHEST
    return {
        "default": jax.lax.Precision.DEFAULT,
        "high": jax.lax.Precision.HIGH,
        "highest": jax.lax.Precision.HIGHEST,
    }[cfg.matmul_precision]


def _wire_operands(queries, query_ids, block, block_ids, block_scale,
                   quantized):
    q_local = queries.shape[0]
    b = block.shape[0]
    qid2 = query_ids.astype(jnp.int32).reshape(q_local, 1)
    bid2 = block_ids.astype(jnp.int32).reshape(b, 1)
    operands = [queries.astype(jnp.float32), qid2, block]
    if quantized:
        operands.append(block_scale.astype(jnp.float32).reshape(b, 1))
    operands.append(bid2)
    return operands


def _wire_in_specs(q_tile, c_tile, dim, pd, quantized):
    """Input BlockSpecs shared by the round kernels: queries pinned per
    query tile, the block swept on the minor grid axis."""
    specs = [
        pl.BlockSpec((q_tile, dim), lambda qi, ci: (qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((q_tile, 1), lambda qi, ci: (qi, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((c_tile, pd), lambda qi, ci: (ci, 0),
                     memory_space=pltpu.VMEM),
    ]
    if quantized:
        specs.append(
            pl.BlockSpec((c_tile, 1), lambda qi, ci: (ci, 0),
                         memory_space=pltpu.VMEM)
        )
    specs.append(
        pl.BlockSpec((c_tile, 1), lambda qi, ci: (ci, 0),
                     memory_space=pltpu.VMEM)
    )
    return specs


def fused_block_merge(
    queries: jax.Array,  # (q_local, d) f32
    query_ids: jax.Array,  # (q_local,)
    block: jax.Array,  # (b, d) at the wire dtype (int8: (b, pd) codes)
    block_ids: jax.Array,  # (b,)
    block_scale: jax.Array | None,  # (b,) f32, int8 wire only
    carry_d: jax.Array,  # (q_local, k) f32
    carry_i: jax.Array,  # (q_local, k) i32
    *,
    cfg,
    q_tile: int,
    c_tile: int,
    interpret: bool | None = None,
):
    """Merge one resident ring block into the carry through the fused
    kernel — the ``ring_fusion="fused"`` replacement for the XLA ring's
    per-round compute() (backends/ring.py). Compute-only: transport is
    the caller's (driver-level ppermute under interpret; on TPU the
    driver uses :func:`fused_round_dma`, whose kernel owns transport and
    shares this body's merge).

    Returns the merged ((q_local, k) dists, ids)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q_local, dim = queries.shape
    b, pd = block.shape
    if q_local % q_tile or b % c_tile:
        raise ValueError("caller must pad to tile multiples")
    n_q, n_c = q_local // q_tile, b // c_tile
    quantized = cfg.ring_transfer_dtype == "int8"
    wire_dtype = "int8" if quantized else None
    operands = _wire_operands(
        queries, query_ids, block, block_ids, block_scale, quantized
    )
    in_specs = _wire_in_specs(q_tile, c_tile, dim, pd, quantized)
    carry_spec = pl.BlockSpec(
        (q_tile, cfg.k), lambda qi, ci: (qi, 0), memory_space=pltpu.VMEM
    )

    mixed = cfg.precision_policy == "mixed" and mixed_applies(cfg.k, c_tile)
    if not mixed:
        # exact policy — and the mixed DEGENERATE case (overfetch >= tile
        # width: the compress pass could not drop a single candidate, so
        # the XLA pipeline falls back to one exact HIGHEST pass; mirror it)
        kernel = _make_exact_kernel(
            quantized,
            k=cfg.k,
            dim=dim,
            wire_dtype=wire_dtype,
            exclude_self=cfg.exclude_self,
            exclude_zero=cfg.exclude_zero,
            zero_eps=cfg.zero_eps,
            precision=_exact_precision(cfg),
        )
        out_d, out_i = pl.pallas_call(
            kernel,
            grid=(n_q, n_c),
            in_specs=in_specs + [carry_spec, carry_spec],
            out_specs=[carry_spec, carry_spec],
            out_shape=[
                jax.ShapeDtypeStruct((q_local, cfg.k), jnp.float32),
                jax.ShapeDtypeStruct((q_local, cfg.k), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((q_tile, cfg.k), jnp.float32),
                pltpu.VMEM((q_tile, cfg.k), jnp.int32),
            ],
            interpret=interpret,
        )(*operands, carry_d.astype(jnp.float32), carry_i)
        return out_d, out_i

    # mixed policy: in-kernel compress preselect, shared-XLA exact finish
    ov = overfetch_width(cfg.k, c_tile)
    kernel = _make_compress_kernel(
        quantized,
        ov=ov,
        dim=dim,
        wire_dtype=wire_dtype,
        exclude_self=cfg.exclude_self,
    )
    pos = pl.pallas_call(
        kernel,
        grid=(n_q, n_c),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, q_tile, ov), lambda qi, ci: (ci, qi, 0),
                         memory_space=pltpu.VMEM)
        ],
        out_shape=[jax.ShapeDtypeStruct((n_c, q_local, ov), jnp.int32)],
        interpret=interpret,
    )(*operands)[0]

    queries = queries.astype(jnp.float32)
    q_sq = jnp.sum(queries * queries, axis=-1)

    def merge_tile(carry, xs):
        cd, ci_ = carry
        t, tile_pos = xs  # (), (q_local, ov) tile-local positions
        pos_g = t * c_tile + tile_pos
        # gather survivors at the WIRE level and dequantize/upcast only
        # them — dequant is row-wise elementwise, so the rows are bitwise
        # the ones the XLA path gathers from its once-per-round
        # dequantized block
        if quantized:
            rows = dequantize_rows(
                jnp.take(block, pos_g, axis=0),
                jnp.take(block_scale, pos_g, axis=0),
                "int8",
                dim,
            )
        else:
            rows = jnp.take(block, pos_g, axis=0).astype(jnp.float32)
        ids_sel = jnp.take(block_ids, pos_g, axis=0)
        ld, li = rerank_exact_topk(
            queries,
            query_ids,
            q_sq,
            rows,
            ids_sel,
            None,
            cfg.k,
            metric=cfg.metric,
            exclude_self=cfg.exclude_self,
            exclude_zero=cfg.exclude_zero,
            zero_eps=cfg.zero_eps,
        )
        md, mi = smallest_k(
            jnp.concatenate([cd, ld.astype(cd.dtype)], axis=1),
            jnp.concatenate([ci_, li], axis=1),
            cfg.k,
            method="exact",
        )
        return (md, mi), None

    (out_d, out_i), _ = jax.lax.scan(
        merge_tile,
        (carry_d.astype(jnp.float32), carry_i),
        (jnp.arange(n_c), pos),
    )
    return out_d, out_i


# ---------------------------------------------------------------------------
# TPU-only transport-owning forms. These issue real remote DMAs and cannot
# run under interpret mode (a copy between devices cannot be emulated
# inside one single-device kernel evaluation) — the CPU tier certifies the
# shared compute body + identical-bytes ppermute transport instead, and
# these forms ride the next TPU bench round.
# ---------------------------------------------------------------------------

# semaphore slots of the per-round DMA kernel: one (send, recv) pair per
# traveling array — block, ids, and (int8 wire) the scale vector
_SEM_BLOCK, _SEM_IDS, _SEM_SCALE = 0, 1, 2


def _dma_round_kernel(
    q_ref, qid_ref, blk_hbm_ref, scl_hbm_ref, bid_hbm_ref,
    blk_ref, scl_ref, bid_ref, cind_ref, cini_ref,
    outd_ref, outi_ref, land_blk_ref, land_scl_ref, land_bid_ref,
    cd_ref, ci_ref, send_sem, recv_sem,
    *,
    k, dim, wire_dtype, exclude_self, exclude_zero, zero_eps, precision,
    axis_name, quantized,
):
    """Round-mode fused kernel WITH transport: grid cell (0, 0) opens a
    neighbor barrier and starts the async remote copies of the whole
    resident block (at the wire format, straight from HBM) to the ring
    successor's landing buffers; every cell runs the same exact merge as
    the interpret path; the LAST cell waits both semaphore sides — the
    ICI stream is hidden under the full (q_tiles × block_tiles) MXU
    sweep, which is the entire point of the fused form."""
    qi, ci = pl.program_id(0), pl.program_id(1)
    n_q, n_c = pl.num_programs(0), pl.num_programs(1)
    num_dev = jax.lax.axis_size(axis_name)
    my_id = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my_id + 1, num_dev)
    left = jax.lax.rem(my_id + num_dev - 1, num_dev)

    def remote_copies():
        copies = [
            pltpu.make_async_remote_copy(
                blk_hbm_ref, land_blk_ref,
                send_sem.at[_SEM_BLOCK], recv_sem.at[_SEM_BLOCK],
                device_id={axis_name: right},
            ),
            pltpu.make_async_remote_copy(
                bid_hbm_ref, land_bid_ref,
                send_sem.at[_SEM_IDS], recv_sem.at[_SEM_IDS],
                device_id={axis_name: right},
            ),
        ]
        if quantized:
            copies.append(
                pltpu.make_async_remote_copy(
                    scl_hbm_ref, land_scl_ref,
                    send_sem.at[_SEM_SCALE], recv_sem.at[_SEM_SCALE],
                    device_id={axis_name: right},
                )
            )
        return copies

    @pl.when(jnp.logical_and(qi == 0, ci == 0))
    def _start():
        # neighbor barrier: the remote write must not land before the
        # receiver has entered the kernel (its landing buffer is a kernel
        # output — live only inside the launch)
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: left})
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: right})
        pltpu.semaphore_wait(barrier, 2)
        for copy in remote_copies():
            copy.start()

    _exact_merge_body(
        q_ref, qid_ref, blk_ref, scl_ref if quantized else None, bid_ref,
        cind_ref, cini_ref, outd_ref, outi_ref, cd_ref, ci_ref,
        k=k, dim=dim, wire_dtype=wire_dtype, exclude_self=exclude_self,
        exclude_zero=exclude_zero, zero_eps=zero_eps, precision=precision,
    )

    @pl.when(jnp.logical_and(qi == n_q - 1, ci == n_c - 1))
    def _wait():
        for copy in remote_copies():
            copy.wait()


def fused_round_dma(
    queries, query_ids, block, block_ids, block_scale, carry_d, carry_i,
    *, cfg, q_tile, c_tile, axis_name, collective_id=0,
):
    """TPU round-mode fused rotation step: returns
    ``(landed_block, landed_scale, landed_ids, carry_d, carry_i)`` — the
    landing buffers hold the predecessor's resident block, i.e. exactly
    what the XLA ring's ppermutes would have delivered, but streamed
    during the MXU sweep instead of scheduled beside it. Exact policy
    (the mixed compress round keeps transport at the driver until its
    DMA form is banked on hardware)."""
    q_local, dim = queries.shape
    b, pd = block.shape
    n_q, n_c = q_local // q_tile, b // c_tile
    quantized = cfg.ring_transfer_dtype == "int8"
    wire_dtype = "int8" if quantized else None

    qid2 = query_ids.astype(jnp.int32).reshape(q_local, 1)
    bid2 = block_ids.astype(jnp.int32).reshape(b, 1)
    scl2 = (
        block_scale.astype(jnp.float32).reshape(b, 1)
        if quantized
        else jnp.zeros((b, 1), jnp.float32)
    )
    kernel = functools.partial(
        _dma_round_kernel,
        k=cfg.k,
        dim=dim,
        wire_dtype=wire_dtype,
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps,
        precision=_exact_precision(cfg),
        axis_name=axis_name,
        quantized=quantized,
    )
    carry_spec = pl.BlockSpec(
        (q_tile, cfg.k), lambda qi, ci: (qi, 0), memory_space=pltpu.VMEM
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    n_sems = 3 if quantized else 2
    out = pl.pallas_call(
        kernel,
        grid=(n_q, n_c),
        in_specs=[
            pl.BlockSpec((q_tile, dim), lambda qi, ci: (qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, 1), lambda qi, ci: (qi, 0),
                         memory_space=pltpu.VMEM),
            any_spec,  # whole-block DMA sources (stay in HBM)
            any_spec,
            any_spec,
            pl.BlockSpec((c_tile, pd), lambda qi, ci: (ci, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c_tile, 1), lambda qi, ci: (ci, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c_tile, 1), lambda qi, ci: (ci, 0),
                         memory_space=pltpu.VMEM),
            carry_spec,
            carry_spec,
        ],
        out_specs=[carry_spec, carry_spec, any_spec, any_spec, any_spec],
        out_shape=[
            jax.ShapeDtypeStruct((q_local, cfg.k), jnp.float32),
            jax.ShapeDtypeStruct((q_local, cfg.k), jnp.int32),
            jax.ShapeDtypeStruct(block.shape, block.dtype),
            jax.ShapeDtypeStruct(scl2.shape, scl2.dtype),
            jax.ShapeDtypeStruct(bid2.shape, bid2.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_tile, cfg.k), jnp.float32),
            pltpu.VMEM((q_tile, cfg.k), jnp.int32),
            pltpu.SemaphoreType.DMA((n_sems,)),
            pltpu.SemaphoreType.DMA((n_sems,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=False,
    )(
        queries.astype(jnp.float32), qid2,
        block, scl2, bid2,  # DMA sources
        block, scl2, bid2,  # compute views (same arrays, blocked to VMEM)
        carry_d.astype(jnp.float32), carry_i,
    )
    out_d, out_i, land_blk, land_scl, land_bid = out
    return (
        land_blk,
        land_scl[:, 0] if quantized else None,
        land_bid[:, 0],
        out_d,
        out_i,
    )


def _grid_rotation_kernel(
    q_ref, qid_ref, blk0_ref, bid0_ref, cind_ref, cini_ref,
    outd_ref, outi_ref,
    slot_blk, slot_bid, tile_blk, tile_bid, cd_ref, ci_ref,
    stage_sem, send_sem, recv_sem, free_sem,
    *,
    k, dim, exclude_self, exclude_zero, zero_eps, precision,
    axis_name, q_tile, c_tile,
):
    """Whole-rotation variant: rounds ride the MAJOR grid axis, the block
    double-buffers between two HBM scratch slots (compute reads slot r%2
    while the remote DMA fills the successor's slot (r+1)%2) — one launch
    for the whole ring. Uni schedule, exact policy, float wire — f32 or
    bf16, upcast at the dot; config refuses int8 transfer for this form
    and the driver re-asserts it (raw codes cast without dequantization
    would be silently wrong distances).

    The running top-k carry lives in ONE (q_local, k) VMEM scratch pair
    sliced per query tile (``q_local·k·8`` bytes resident): the grid
    sweeps (r, qi, ci) with ci minor, so every query tile's carry must
    survive the other tiles' cells between its own visits — a (q_tile, k)
    scratch would be clobbered at every qi switch. Init fires per qi at
    round 0, emit per qi at the last round's last ci.

    Cross-device sync is the initial neighbor barrier plus a
    receiver→sender capacity handshake on ``free_sem``: a device's
    round-r stream overwrites its RIGHT neighbor's slot (r+1)%2, which
    that neighbor is still staging compute tiles from (its round r-1)
    until its last cell — so each device releases a slot to its LEFT
    neighbor once every round-r read of it has retired (the final
    staging copy AND its own send DMA, hence after the DMA waits), and
    the sender consumes one release before every stream after the
    first. Without it, device skew
    lets a fast sender corrupt an in-use buffer (the recv-semaphore chain
    alone only orders arrivals, not slot reuse)."""
    r, qi, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_r, n_q, n_c = (
        pl.num_programs(0), pl.num_programs(1), pl.num_programs(2)
    )
    num_dev = jax.lax.axis_size(axis_name)
    my_id = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my_id + 1, num_dev)
    left = jax.lax.rem(my_id + num_dev - 1, num_dev)
    slot = jax.lax.rem(r, 2)
    nxt = jax.lax.rem(r + 1, 2)
    first_cell = jnp.logical_and(qi == 0, ci == 0)
    last_cell = jnp.logical_and(qi == n_q - 1, ci == n_c - 1)
    rows = pl.ds(qi * q_tile, q_tile)  # this query tile's carry slice

    @pl.when(jnp.logical_and(r == 0, first_cell))
    def _boot():
        # stage the resident block into slot 0 (local HBM→HBM copy), then
        # one whole-rotation neighbor barrier
        for src, dst in ((blk0_ref, slot_blk), (bid0_ref, slot_bid)):
            copy = pltpu.make_async_copy(src, dst.at[0], stage_sem)
            copy.start()
            copy.wait()
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: left})
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: right})
        pltpu.semaphore_wait(barrier, 2)

    def remote_copies():
        return [
            pltpu.make_async_remote_copy(
                slot_blk.at[slot], slot_blk.at[nxt],
                send_sem.at[_SEM_BLOCK], recv_sem.at[_SEM_BLOCK],
                device_id={axis_name: right},
            ),
            pltpu.make_async_remote_copy(
                slot_bid.at[slot], slot_bid.at[nxt],
                send_sem.at[_SEM_IDS], recv_sem.at[_SEM_IDS],
                device_id={axis_name: right},
            ),
        ]

    @pl.when(
        jnp.logical_and(r > 0, jnp.logical_and(r < n_r - 1, first_cell))
    )
    def _backpressure():
        # the slot this round's stream lands in (right neighbor's
        # (r+1)%2) was being staged from during its round r-1 — consume
        # one capacity release before overwriting it. The wait at round r
        # consumes the r-th release, so it proves the neighbor finished
        # ALL reads through its round r-1 (counting order, device skew
        # notwithstanding). Round 0 streams into a never-read slot.
        pltpu.semaphore_wait(free_sem, 1)

    @pl.when(jnp.logical_and(r < n_r - 1, first_cell))
    def _stream():
        for copy in remote_copies():
            copy.start()

    @pl.when(jnp.logical_and(r == 0, ci == 0))
    def _init():
        cd_ref[rows] = cind_ref[:]
        ci_ref[rows] = cini_ref[:]

    # stage this cell's (c_tile) compute tile out of the resident HBM slot
    # (slots live outside BlockSpec's automatic staging)
    for src, dst in (
        (slot_blk.at[slot, pl.ds(ci * c_tile, c_tile)], tile_blk),
        (slot_bid.at[slot, pl.ds(ci * c_tile, c_tile)], tile_bid),
    ):
        copy = pltpu.make_async_copy(src, dst, stage_sem)
        copy.start()
        copy.wait()

    d = _masked_ring_tile(
        q_ref[:], tile_blk[:].astype(jnp.float32), qid_ref[:], tile_bid[:],
        exclude_self=exclude_self, exclude_zero=exclude_zero,
        zero_eps=zero_eps, precision=precision, compress=False,
    )
    all_d = jnp.concatenate([cd_ref[rows], d], axis=1)
    all_i = jnp.concatenate(
        [ci_ref[rows], jnp.broadcast_to(tile_bid[:][:, 0][None, :], d.shape)],
        axis=1,
    )
    md, mi = _k_smallest_sweep(all_d, all_i, k)
    cd_ref[rows] = md
    ci_ref[rows] = mi

    @pl.when(jnp.logical_and(r < n_r - 1, last_cell))
    def _wait():
        for copy in remote_copies():
            copy.wait()

    @pl.when(jnp.logical_and(r < n_r - 2, last_cell))
    def _release():
        # ALL of round r's reads of slot r%2 are now retired — the last
        # staging copy above and (order matters: this sits AFTER _wait's
        # send-semaphore wait) the round's own send DMA out of the slot —
        # so release it to the left neighbor, whose round-(r+1) stream
        # overwrites it. No release for the final two rounds: r = n_r-2
        # feeds the last stream that waits (round n_r-2's wait consumes
        # round n_r-3's release); a later release would leave the
        # semaphore nonzero at kernel exit.
        pltpu.semaphore_signal(
            free_sem, inc=1, device_id={axis_name: left}
        )

    @pl.when(jnp.logical_and(r == n_r - 1, ci == n_c - 1))
    def _emit():
        # per QUERY TILE, not per launch: outd/outi blocks are keyed by
        # qi, and each block's final HBM flush is its last-round visit
        outd_ref[:] = cd_ref[rows]
        outi_ref[:] = ci_ref[rows]


def fused_rotation_grid(
    queries, query_ids, block, block_ids, carry_d, carry_i,
    *, cfg, q_tile, c_tile, axis_name, num_dev, collective_id=0,
):
    """Whole-rotation single-launch form (``ring_fused_rotation="grid"``):
    TPU-only — the between-round remote DMA cannot be emulated inside one
    interpret-mode evaluation, so off-TPU callers must use the per-round
    form (the one the CPU parity matrix certifies). Config already pins
    this variant to uni/exact and a float wire."""
    if not jnp.issubdtype(block.dtype, jnp.floating):
        # config refuses int8 transfer for the grid form; re-assert at
        # the kernel boundary so a relaxed config could never stream raw
        # quantized codes into a plain float cast (silently wrong
        # distances — the scale plumbing belongs to the round form)
        raise ValueError(
            "ring_fused_rotation='grid' supports float wire formats only "
            "(f32/bf16): the grid kernel casts slot bytes straight into "
            f"the distance dot, got block dtype {block.dtype}"
        )
    if jax.default_backend() != "tpu":
        raise ValueError(
            "ring_fused_rotation='grid' runs the whole rotation as one "
            "TPU kernel launch with real inter-device DMAs and cannot be "
            "emulated in interpret mode — use ring_fused_rotation="
            "'round' off-TPU"
        )
    q_local, dim = queries.shape
    b, pd = block.shape
    n_q, n_c = q_local // q_tile, b // c_tile
    qid2 = query_ids.astype(jnp.int32).reshape(q_local, 1)
    bid2 = block_ids.astype(jnp.int32).reshape(b, 1)
    kernel = functools.partial(
        _grid_rotation_kernel,
        k=cfg.k,
        dim=dim,
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps,
        precision=_exact_precision(cfg),
        axis_name=axis_name,
        q_tile=q_tile,
        c_tile=c_tile,
    )
    carry_spec = pl.BlockSpec(
        (q_tile, cfg.k), lambda r, qi, ci: (qi, 0), memory_space=pltpu.VMEM
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=(num_dev, n_q, n_c),
        in_specs=[
            pl.BlockSpec((q_tile, dim), lambda r, qi, ci: (qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q_tile, 1), lambda r, qi, ci: (qi, 0),
                         memory_space=pltpu.VMEM),
            any_spec,
            any_spec,
            carry_spec,
            carry_spec,
        ],
        out_specs=[carry_spec, carry_spec],
        out_shape=[
            jax.ShapeDtypeStruct((q_local, cfg.k), jnp.float32),
            jax.ShapeDtypeStruct((q_local, cfg.k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.HBM((2,) + block.shape, block.dtype),  # double buffer
            pltpu.HBM((2,) + bid2.shape, bid2.dtype),
            pltpu.VMEM((c_tile, pd), block.dtype),  # staged compute tile
            pltpu.VMEM((c_tile, 1), bid2.dtype),
            # per-query-tile carries, FULL q_local rows: the (r, qi, ci)
            # sweep leaves each qi's carry parked across every other
            # tile's cells, so the whole (q_local, k) pair stays resident
            pltpu.VMEM((q_local, cfg.k), jnp.float32),
            pltpu.VMEM((q_local, cfg.k), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,  # slot-free capacity handshake
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id
        ),
        interpret=False,
    )(
        queries.astype(jnp.float32), qid2, block, bid2,
        carry_d.astype(jnp.float32), carry_i,
    )
    return out_d, out_i
