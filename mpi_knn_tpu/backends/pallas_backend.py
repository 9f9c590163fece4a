"""Pallas-kernel backend: fused distance+top-k tiles + one XLA merge.

Same observable semantics as the serial backend (same masks, same exclusion
rules); differs only in where the (q × c) distance block lives (VMEM, never
HBM). Selected with ``backend="pallas"``.

Two kernel shapes (``cfg.pallas_variant``):

- ``"tiles"``: per-(q,c)-tile local top-k, candidates written to HBM, one
  XLA cross-tile merge (honors ``topk_method``/``recall_target`` there);
- ``"sweep"``: the corpus-tile loop rides the minor grid axis (TPU grid
  cells run sequentially) with the running (q_tile, k) top-k carried in
  VMEM scratch; only the final (Q, k) leaves the kernel and the in-kernel
  merge is always EXACT — ``topk_method="approx"`` has no effect here.

Status on the chip (TPU v5e, jax 0.9.0, PR 22 ``chip_smoke.py``): both
variants are compiled by Mosaic — never interpreted on a TPU — and agree
with the serial backend on 4 096 query rows against the 60 000 x 784
corpus. Two things Mosaic refused on the way, and what answers them:
``Precision.HIGH`` dots ("Unsupported dot precision: HIGH"; the config now
refuses ``matmul_precision="high"`` for this backend), and 512 x 2048
tiles at d = 784 ("Scoped allocation with size 26.00M and limit 16.00M";
:func:`kernel_tiles` shrinks the tiles from ``dim``). Speed: not measured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.distance import _NORM_EPS, _l2_normalize, sq_norms
from mpi_knn_tpu.ops.pallas_knn import _ZERO_RTOL, fused_knn_sweep, fused_knn_tiles
from mpi_knn_tpu.ops.rerank import (
    OVERFETCH_FACTOR,
    mixed_applies,
    overfetch_width,
    rerank_exact_topk,
)
from mpi_knn_tpu.ops.topk import smallest_k
from mpi_knn_tpu.parallel.partition import (
    make_global_ids,
    pad_rows_any,
    pad_to_multiple,
)


# Mosaic's default scoped-VMEM limit is 16 MiB on every TPU generation (it
# is all of a v4's VMEM); the kernels ask for no more, so one tile rule
# serves every chip. 2 MiB stay spare for what the estimate cannot see.
_VMEM_BUDGET = 14 * 2**20


def kernel_tiles(query_tile: int, corpus_tile: int, nq: int, m: int,
                 dim: int, k: int) -> tuple[int, int]:
    """(q_tile, c_tile) for the fused kernels: MXU/VPU-aligned, no larger
    than the request, the (aligned) problem or 512 × 2048, and shrunk until
    what one grid cell keeps in VMEM fits the budget. Both input blocks
    span the whole (lane-padded) feature axis, so the width of the data
    decides how many rows fit; ``k`` is the extraction width (4k under the
    mixed policy). The estimate was fitted to Mosaic's own allocation
    figures from v5e compiles across dim 64–7168, both variants."""
    q_tile = min(max(8, pad_to_multiple(query_tile, 8)), 512,
                 pad_to_multiple(nq, 8))
    c_tile = min(max(128, pad_to_multiple(corpus_tile, 128)), 2048,
                 pad_to_multiple(m, 128))
    dim_p = pad_to_multiple(dim, 128)

    def vmem_bytes(q, c):
        return (
            8 * (q + c) * dim_p  # f32 input blocks, double-buffered
            + 12 * q * dim_p  # the multi-pass dot's split of the query block
            + 2048 * k * q  # k-pass staging: 2k lane-padded (q,) columns,
            # twice in the sweep (tile extract + carry merge)
            + 8 * q * c  # the distance block and one same-shape temporary
        )

    # corpus rows first (every cell re-reads its corpus block anyway, so
    # they buy no reuse) down to 512, then query rows down to 128, then
    # the floor of each
    while vmem_bytes(q_tile, c_tile) > _VMEM_BUDGET:
        if c_tile > 512 or (q_tile <= 128 and c_tile > 128):
            c_tile = pad_to_multiple(c_tile // 2, 128)
        elif q_tile > 8:
            q_tile = pad_to_multiple(q_tile // 2, 8)
        else:
            break  # the floor: Mosaic says what does not fit
    return q_tile, c_tile


def _mixed_exact_finish(queries, corpus, cand_i, cfg, q_tile, all_pairs):
    """Pass-2 of the mixed policy for the fused path: the kernel's
    overfetched candidates (compressed-key survivors, global ids) are
    reranked exactly in XLA — gather the survivors' corpus rows, recompute
    at HIGHEST, re-apply the mask semantics on exact values, final top-k.
    Runs per query tile under ``lax.map`` so the (q_tile, V, d) gather —
    not a (Q, V, d) one — is the peak intermediate. Cosine rides through
    as L2 on the pre-normalized rows, same as the kernel itself."""
    Q = queries.shape[0]
    csq = sq_norms(corpus)  # exact norms, hoisted out of the tile map
    q_ids = (
        jnp.arange(Q, dtype=jnp.int32)
        if all_pairs
        else jnp.full(Q, -1, jnp.int32)
    )
    qt = Q // q_tile
    V = cand_i.shape[1]

    def per_tile(args):
        q_x, q_id, ci = args
        idx = jnp.maximum(ci, 0)  # INVALID_ID slots: clamp, re-mask below
        rows = jnp.take(corpus, idx, axis=0)  # (q_tile, V, d)
        return rerank_exact_topk(
            q_x,
            q_id,
            sq_norms(q_x),
            rows,
            ci,
            jnp.take(csq, idx, axis=0),
            cfg.k,
            metric="l2",
            exclude_self=cfg.exclude_self and all_pairs,
            exclude_zero=cfg.exclude_zero,
            zero_eps=cfg.zero_eps,
        )

    d, i = jax.lax.map(
        per_tile,
        (
            queries.reshape(qt, q_tile, -1),
            q_ids.reshape(qt, q_tile),
            cand_i.reshape(qt, q_tile, V),
        ),
    )
    return d.reshape(Q, cfg.k), i.reshape(Q, cfg.k)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "q_tile", "c_tile", "m_corpus", "all_pairs", "variant"
    ),
)
def _pallas_all_knn(
    queries, corpus, cfg, q_tile, c_tile, m_corpus, all_pairs, variant
):
    if cfg.precision_policy == "mixed" and mixed_applies(cfg.k, c_tile):
        # pass 1 IN-KERNEL: the compress dot (bf16 DEFAULT) plus the
        # overfetch selection run in VMEM; each tile emits 4k compressed-
        # key survivors instead of k. Pass 2 (exact HIGHEST rerank of the
        # gathered survivors) is XLA-side, shared with the serial/ring
        # pipeline's rerank helper.
        ov = overfetch_width(cfg.k, c_tile)
        common = dict(
            m_corpus=m_corpus,
            k=ov,
            q_tile=q_tile,
            c_tile=c_tile,
            exclude_self=cfg.exclude_self,
            exclude_zero=cfg.exclude_zero,
            all_pairs=all_pairs,
            zero_eps=cfg.zero_eps,
            compress=True,
        )
        if variant == "sweep":
            _, cand_i = fused_knn_sweep(queries, corpus, **common)
        else:
            cand_d, cand_i = fused_knn_tiles(queries, corpus, **common)
            # the tiles kernel emits 4k survivors PER corpus tile
            # (n_c·4k per query); preselect the global 4k by the same
            # compressed keys before the gather, or the pass-2 cost —
            # the (q_tile, V, d) gather and the HIGHEST rerank dot —
            # would scale with the tile count instead of the promised
            # O(q·4k·d). Compressed keys are comparable across tiles
            # (one rounding rule), so this is the paper's global
            # overfetch; invalid (+inf, -1) slots sort to the end.
            if cand_i.shape[1] > ov:
                _, cand_i = smallest_k(cand_d, cand_i, ov, method="exact")
        return _mixed_exact_finish(
            queries, corpus, cand_i, cfg, q_tile, all_pairs
        )
    if variant == "sweep":
        # the sweep kernel merges in VMEM scratch; its output IS the final
        # top-k (exact merge — cfg.topk_method does not apply here). The
        # caller guarantees k <= c_tile (see all_knn_pallas).
        return fused_knn_sweep(
            queries,
            corpus,
            m_corpus=m_corpus,
            k=cfg.k,
            q_tile=q_tile,
            c_tile=c_tile,
            exclude_self=cfg.exclude_self,
            exclude_zero=cfg.exclude_zero,
            all_pairs=all_pairs,
            zero_eps=cfg.zero_eps,
            precision=cfg.matmul_precision,
        )
    outd, outi = fused_knn_tiles(
        queries,
        corpus,
        m_corpus=m_corpus,
        k=min(cfg.k, c_tile),
        q_tile=q_tile,
        c_tile=c_tile,
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero,
        all_pairs=all_pairs,
        zero_eps=cfg.zero_eps,
        precision=cfg.matmul_precision,
    )
    # cross-tile merge: k survivors per corpus tile -> final k
    return smallest_k(
        outd, outi, cfg.k, method=cfg.topk_method,
        recall_target=cfg.recall_target, block=cfg.topk_block,
    )


def all_knn_pallas(
    corpus: np.ndarray,
    queries: np.ndarray,
    query_ids: np.ndarray,
    cfg: KNNConfig,
):
    if cfg.dtype != "float32":
        raise ValueError(
            f"pallas backend computes in float32; dtype={cfg.dtype!r} is not "
            "supported (use the serial/ring backends for bf16/f64)"
        )
    m, dim = corpus.shape
    nq = queries.shape[0]

    # Cosine rides the L2 kernels: on unit vectors the kernel's squared-L2
    # output is exactly 2·(1 − cos sim) — monotonic with cosine distance
    # (same top-k), converted back to the serial backend's cosine-distance
    # space (ops.distance.pairwise_cosine) by halving on the way out. The
    # zero-exclusion epsilon maps the same way: serial's threshold in
    # cosine space (absolute cfg.zero_eps, else _ZERO_RTOL·scale with
    # scale = 2.0 — backends/serial.py) doubles into kernel d² space.
    cosine = cfg.metric == "cosine"
    if cosine:
        # The d² = 2·d_cos identity requires UNIT rows; a zero row
        # normalizes to the zero vector (serial: distance 1.0 to
        # everything) and would come out as 0.5 here. Degenerate input →
        # route the whole call to serial for exact semantics (the check is
        # one reduced scalar off-device, not a data fetch).
        all_pairs_same = queries is corpus
        corpus = jnp.asarray(corpus, dtype=jnp.float32)
        queries = corpus if all_pairs_same else jnp.asarray(
            queries, dtype=jnp.float32
        )
        # Guard must match _l2_normalize's clamp: a row with
        # 0 < ||x||² <= _NORM_EPS is NOT normalized to unit length (the
        # clamp wins), so it breaks the d² = 2·d_cos identity just like an
        # exact zero row. Route anything the normalizer would clamp to
        # serial.
        any_zero = (sq_norms(corpus) <= _NORM_EPS).any()
        if not all_pairs_same:
            any_zero = any_zero | (sq_norms(queries) <= _NORM_EPS).any()
        if bool(jax.device_get(any_zero)):
            from mpi_knn_tpu.backends.serial import all_knn_serial

            return all_knn_serial(corpus, queries, query_ids, cfg)[:2]
        # normalize on device (jnp), once when queries IS corpus (the
        # all-pairs reference workload) — no host round-trip of the corpus
        corpus = _l2_normalize(corpus)
        queries = corpus if all_pairs_same else _l2_normalize(queries)
        zero_eps = 2.0 * (
            cfg.zero_eps if cfg.zero_eps > 0 else _ZERO_RTOL * 2.0
        )
        cfg = cfg.replace(zero_eps=zero_eps)
    # the kernel derives candidate/query ids from grid position, which covers
    # two cases: query i IS corpus row i (all-pairs, or the first nq corpus
    # rows as queries) and query mode (no corpus identity). Any other
    # identity cannot be honored and is refused, not dropped.
    query_ids = np.asarray(query_ids)
    all_pairs = bool(
        nq <= m and np.array_equal(query_ids, np.arange(nq, dtype=np.int32))
    )
    if not all_pairs and (query_ids >= 0).any():
        raise ValueError(
            "the pallas backend takes query identities from grid position: "
            "query_ids must be arange(len(queries)) (the queries are the "
            "first corpus rows) or all -1; use backend='serial' for an "
            "arbitrary sample of corpus rows"
        )

    q_tile, c_tile = kernel_tiles(
        cfg.query_tile, cfg.corpus_tile, nq, m, dim,
        k=cfg.k * (OVERFETCH_FACTOR if cfg.precision_policy == "mixed" else 1),
    )

    c_pad = pad_to_multiple(m, c_tile)
    q_pad = pad_to_multiple(nq, q_tile)

    corpus_p = pad_rows_any(corpus, c_pad, dtype=jnp.float32)
    queries_p = pad_rows_any(queries, q_pad, dtype=jnp.float32)

    # k > c_tile is a corner both kernels COULD handle without truncation
    # (a tile yields at most c_tile real candidates; extra extraction passes
    # produce inf/-1 padding that later merges fill in) — but the kernels
    # unroll k min-extraction passes at trace time, and the sweep pays that
    # unroll TWICE per tile (tile extract + carry merge). Route the corner
    # to the tiles variant, whose per-tile unroll is bounded by c_tile and
    # whose XLA merge tops up across tiles.
    variant = cfg.pallas_variant
    if variant == "sweep" and cfg.k > c_tile:
        variant = "tiles"

    best_d, best_i = _pallas_all_knn(
        queries_p, corpus_p, cfg, q_tile, c_tile, m, all_pairs, variant
    )
    if cosine:
        # back to cosine-distance space (d² on unit vectors = 2·d_cos);
        # inf sentinels for invalid slots survive the halving
        best_d = best_d * 0.5
    return best_d[:nq], best_i[:nq]
