"""Mixed-precision compress-and-rerank distance pipeline
(``KNNConfig.precision_policy="mixed"``).

The TPU-KNN paper's peak-FLOPs structure (PAPERS.md, arxiv 2206.14286),
applied to the *distance* side the way ``smallest_k``'s "approx-rerank"
already applies it to the key side:

- **compress** — the (q_tile × c_tile) distance tile is computed with the
  −2·X·Yᵀ dot at ``Precision.DEFAULT`` on bf16-rounded operands (single-pass
  MXU, f32 accumulation), and an overfetched candidate set of ``4k`` columns
  per query survives an exact top-4k over the compressed keys. The operands
  are rounded to bf16 *explicitly* (not just via the precision flag) so the
  CPU tier-1 recall gate measures the same rounding the TPU MXU applies —
  a DEFAULT-precision f32 dot is exact on CPU and would make the gate
  vacuous.
- **rerank** — only the survivors' corpus rows are gathered and their
  distances recomputed exactly (f32 ``HIGHEST``), with ``mask_tile``'s
  padding/self/zero semantics re-applied on the exact values, before the
  final exact top-k.

So the O(q·c·d) FLOPs run at full single-pass MXU rate and only O(q·4k·d)
runs multi-pass. A true top-k member is lost only if bf16 rounding pushes
it out of the top-4k of its tile — the recall gate (≥ 0.999 recall@10 vs
the f64 oracle, tests/test_mixed_precision.py) measures exactly that loss,
on CPU, because the rounding is explicit.

Masking split (deliberate): the compress pass masks *padding and self by
id* (exact under any precision) but NOT zero-by-value — a genuine
near-duplicate neighbor must not be dropped on the evidence of a rounded
distance it would survive exactly. Zero-exclusion happens once, in the
rerank, on exact values; compressed near-zero survivors merely occupy
overfetch slots (≤ a few of the 4k).

The carry stays exact everywhere: each tile's contribution enters the
cross-tile/cross-round merges as (k exact-f32 distances, ids), so ring
checkpoint layouts and the merge algebra are unchanged
(backends/ring_resumable.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mpi_knn_tpu.ops.distance import (
    _NORM_EPS,
    _l2_normalize,
    pairwise_dist,
    sq_norms,
)
from mpi_knn_tpu.ops.topk import mask_tile, preselect_smallest, smallest_k

# Overfetch factor: the compress pass keeps 4k candidates per query — the
# TPU-KNN paper's operating point, shared with smallest_k's "bf16" /
# "approx-rerank" preselects so all three recipes make the same recall
# trade.
OVERFETCH_FACTOR = 4


def overfetch_width(k: int, c: int) -> int:
    """Candidates the compress pass keeps per query from a c-wide tile."""
    return min(OVERFETCH_FACTOR * k, c)


def mixed_applies(k: int, c: int) -> bool:
    """Whether the two-pass pipeline buys anything on a c-wide tile: with
    4k >= c the compress pass could not drop a single candidate, so the
    policy degenerates to one exact pass (the caller falls back)."""
    return overfetch_width(k, c) < c


def compress_tile(
    q_x: jax.Array,  # (q, d)
    blk: jax.Array,  # (c, d)
    q_sq: jax.Array | None,
    blk_sq: jax.Array | None,
    metric: str = "l2",
) -> jax.Array:
    """Pass-1 (q, c) distances: bf16-rounded operands, single-pass DEFAULT
    dot, f32 accumulation. Order-faithful up to bf16 rounding; never used
    as an output value — only as preselect keys."""
    acc = jnp.float32
    if metric == "l2":
        if q_sq is None:
            q_sq = sq_norms(q_x)
        if blk_sq is None:
            blk_sq = sq_norms(blk)
        xy = jax.lax.dot_general(
            q_x.astype(jnp.bfloat16),
            blk.astype(jnp.bfloat16),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=acc,
            precision=jax.lax.Precision.DEFAULT,
        )
        return q_sq[:, None] - 2.0 * xy + blk_sq[None, :]
    sim = jax.lax.dot_general(
        _l2_normalize(q_x).astype(jnp.bfloat16),
        _l2_normalize(blk).astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc,
        precision=jax.lax.Precision.DEFAULT,
    )
    return 1.0 - sim


def _batched_dot(q_x, cand_rows, acc):
    """(q, d) x (q, v, d) -> (q, v): every row against its own candidates,
    at HIGHEST."""
    return jax.lax.dot_general(
        q_x,
        cand_rows,
        dimension_numbers=(((1,), (2,)), ((0,), (0,))),
        preferred_element_type=acc,
        precision=jax.lax.Precision.HIGHEST,
    )


@jax.named_scope("knn.rerank")
def rerank_exact_topk(
    q_x: jax.Array,  # (q, d)
    q_ids: jax.Array | None,  # (q,) or None (no self-exclusion)
    q_sq: jax.Array | None,  # (q,) exact squared norms (l2)
    cand_rows: jax.Array,  # (q, v, d) gathered candidate corpus rows
    cand_ids: jax.Array,  # (q, v) global ids (<0 = invalid slot)
    cand_sq: jax.Array | None,  # (q, v) exact squared norms (l2)
    k: int,
    metric: str = "l2",
    exclude_self: bool = True,
    exclude_zero: bool = True,
    zero_eps: float = 0.0,
):
    """Pass-2 exact finish: recompute the survivors' distances at HIGHEST,
    re-apply the full mask_tile semantics on the exact values, exact top-k.

    Returns ((q, k) dists ascending, (q, k) ids) — same contract as
    ``smallest_k`` over an exactly-computed masked tile, which is what
    makes the pipeline drop-in for every backend's tile loop.

    Cosine with ``cand_sq`` given is the PREPARED form of
    ``ops.distance.pairwise_cosine``: ``cand_sq`` holds the candidates'
    inverse norms (a cosine stack's norm plane) and ``q_x`` unit rows, and
    the value is ``max(1 - (q . c) * inv, 0)`` — a tile step's own
    arithmetic on the same numbers (the certified screen's finish,
    ``backends/serial.py``). Without it both sides are normalised here.
    ``metric="ip"``: the negated dot, nothing clamped, no scale for a zero
    test (``ops.distance.pairwise_neg_ip``).
    """
    acc = jnp.float32
    if metric == "l2":
        if q_sq is None:
            q_sq = sq_norms(q_x)
        if cand_sq is None:
            cand_sq = jnp.sum(
                cand_rows.astype(acc) * cand_rows.astype(acc), axis=-1
            )
        xy = _batched_dot(q_x, cand_rows, acc)
        d = jnp.maximum(q_sq[:, None] - 2.0 * xy + cand_sq, 0.0)
        pair_scale = q_sq[:, None] + cand_sq
    elif metric == "cosine" and cand_sq is not None:
        sim = _batched_dot(q_x, cand_rows, acc) * cand_sq.astype(acc)
        d = jnp.maximum(1.0 - sim, 0.0)
        pair_scale = jnp.asarray(2.0, dtype=d.dtype)
    elif metric == "ip":
        d = -_batched_dot(q_x, cand_rows, acc)
        pair_scale = None
    elif metric == "cosine":
        qn = _l2_normalize(q_x)
        n = jnp.sqrt(
            jnp.maximum(
                jnp.sum(cand_rows.astype(acc) * cand_rows.astype(acc), -1),
                _NORM_EPS,
            )
        )
        rn = cand_rows.astype(acc) / n[..., None]
        d = jnp.maximum(1.0 - _batched_dot(qn, rn, acc), 0.0)
        pair_scale = jnp.asarray(2.0, dtype=d.dtype)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    d = mask_tile(
        d,
        cand_ids,
        query_ids=q_ids if exclude_self else None,
        exclude_self=exclude_self,
        exclude_zero=exclude_zero,
        zero_eps=zero_eps,
        scale=pair_scale,
    )
    return smallest_k(d, cand_ids, k, method="exact")


def compress_rerank_tile(
    q_x: jax.Array,  # (q, d)
    q_ids: jax.Array,  # (q,)
    q_sq: jax.Array | None,
    blk: jax.Array,  # (c, d)
    blk_ids: jax.Array,  # (c,)
    blk_sq: jax.Array | None,
    cfg,
):
    """The full two-pass tile reduction (q, c) → (q, k): the mixed-policy
    replacement for ``masked_dist_tile`` + ``smallest_k`` in every XLA tile
    loop (serial scan, ring per-round block merge). Falls back to one exact
    pass when the tile is too narrow for the overfetch to drop anything."""
    c = blk.shape[0]
    k = cfg.k
    if not mixed_applies(k, c):
        # narrow tile: compress could not discard a single candidate — run
        # the one exact pass the policy degenerates to (HIGHEST dot, full
        # mask semantics; same shape as the "exact" policy's tile step)
        d = pairwise_dist(
            q_x,
            blk,
            metric=cfg.metric,
            x_sq=q_sq,
            y_sq=blk_sq,
            precision=jax.lax.Precision.HIGHEST,
        )
        if cfg.metric == "l2" and q_sq is not None and blk_sq is not None:
            pair_scale = q_sq[:, None] + blk_sq[None, :]
        else:
            pair_scale = jnp.asarray(2.0, dtype=d.dtype)
        d = mask_tile(
            d,
            blk_ids,
            query_ids=q_ids if cfg.exclude_self else None,
            exclude_self=cfg.exclude_self,
            exclude_zero=cfg.exclude_zero,
            zero_eps=cfg.zero_eps,
            scale=pair_scale,
        )
        return smallest_k(d, blk_ids, k, method="exact")
    d_lo = compress_tile(q_x, blk, q_sq, blk_sq, metric=cfg.metric)
    # padding/self masks are id-based — exact under any precision — but
    # zero-by-value is deliberately NOT applied to compressed keys (see
    # module docstring); the rerank applies it on exact values
    d_lo = mask_tile(
        d_lo,
        blk_ids,
        query_ids=q_ids if cfg.exclude_self else None,
        exclude_self=cfg.exclude_self,
        exclude_zero=False,
    )
    pos = preselect_smallest(d_lo, overfetch_width(k, c))  # (q, 4k)
    rows = jnp.take(blk, pos, axis=0)  # (q, 4k, d)
    ids_sel = jnp.take(blk_ids, pos, axis=0)
    sq_sel = (
        jnp.take(blk_sq, pos, axis=0)
        if blk_sq is not None and cfg.metric == "l2"
        else None
    )
    return rerank_exact_topk(
        q_x,
        q_ids,
        q_sq,
        rows,
        ids_sel,
        sq_sel,
        k,
        metric=cfg.metric,
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps,
    )
