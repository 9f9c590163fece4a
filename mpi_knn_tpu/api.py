"""Public functional API.

One entry point replaces the reference's three copy-pasted ``main()``s
(SURVEY.md §1): the backend is a config field, not a separate program.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.topk import start_lane_bin_import
from mpi_knn_tpu.ops.vote import classify_from_labels
from mpi_knn_tpu.types import ClassifyResult, KNNResult


def resolve_backend(cfg: KNNConfig, mesh=None) -> str:
    if cfg.backend != "auto":
        return cfg.backend
    n = cfg.num_devices or (len(mesh.devices.flat) if mesh is not None else len(jax.devices()))
    return "ring-overlap" if n > 1 else "serial"


def all_knn(
    corpus,
    queries=None,
    config: Optional[KNNConfig] = None,
    mesh=None,
    query_ids=None,
    **overrides,
) -> KNNResult:
    """All-kNN search.

    Args:
      corpus: (m, d) point matrix.
      queries: (q, d) query matrix, or None for all-pairs leave-one-out mode —
        the reference's workload: every corpus point queries the whole corpus
        with itself excluded (``/root/reference/knn-serial.c:72-93``).
      config: KNNConfig; individual fields may be overridden by kwargs, e.g.
        ``all_knn(X, k=10, backend="ring")``.
      mesh: optional jax.sharding.Mesh for the ring backends.
      query_ids: optional (q,) int32 corpus identities for explicit
        ``queries`` — when the queries are a subset of the corpus, passing
        their corpus row indices preserves all-pairs self-exclusion for the
        sampled rows (the sampled recall gate's use). Ignored in all-pairs
        mode (identities are implicit); -1 entries mean "no identity".

    Returns:
      KNNResult with (q, k) distances (sortable space, ascending) and 0-based
      global ids.
    """
    cfg = (config or KNNConfig()).replace(**overrides)
    # entry until the last dispatch has returned (the result is not waited for)
    with obs_spans.span(
        "all_knn", cat="api", rows=len(corpus if queries is None else queries)
    ):
        return _all_knn(corpus, queries, cfg, mesh, query_ids)


def _all_knn(corpus, queries, cfg: KNNConfig, mesh, query_ids) -> KNNResult:
    start_lane_bin_import()  # under the corpus passes below
    on_device = isinstance(corpus, jax.Array)
    if not on_device:
        corpus = np.asarray(corpus)
    m = corpus.shape[0]

    if queries is None:
        q_arr = corpus
        q_ids = np.arange(m, dtype=np.int32)
    else:
        q_arr = queries if isinstance(queries, jax.Array) else np.asarray(queries)
        if query_ids is not None:
            q_ids = np.asarray(query_ids, dtype=np.int32)
            if q_ids.shape != (q_arr.shape[0],):
                raise ValueError(
                    f"query_ids shape {q_ids.shape} != ({q_arr.shape[0]},)"
                )
        else:
            # no query has a corpus identity in query mode; -1 never matches
            # a *valid* candidate id, so self-exclusion is a no-op
            q_ids = np.full(q_arr.shape[0], -1, dtype=np.int32)

    fact = steps = None
    if cfg.center and cfg.metric == "l2":
        from mpi_knn_tpu.ops.distance import center_for_l2

        corpus, q_arr, fact, _ = center_for_l2(
            corpus, q_arr, all_pairs=queries is None)

    backend = resolve_backend(cfg, mesh)
    if backend == "serial":
        from mpi_knn_tpu.backends.serial import all_knn_serial

        d, i, steps = all_knn_serial(corpus, q_arr, q_ids, cfg, fact)
    elif backend in ("ring", "ring-overlap"):
        from mpi_knn_tpu.backends.ring import all_knn_ring

        d, i, steps = all_knn_ring(
            corpus, q_arr, q_ids, cfg, mesh=mesh,
            overlap=(backend == "ring-overlap"), fact=fact,
        )
    elif backend == "pallas":
        from mpi_knn_tpu.backends.pallas_backend import all_knn_pallas

        d, i = all_knn_pallas(corpus, q_arr, q_ids, cfg)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return KNNResult(dists=d, ids=i, dist_steps=steps)


def build_index(corpus, config: Optional[KNNConfig] = None, mesh=None,
                **overrides):
    """Build a device-resident corpus index for query serving — all
    corpus-side work (tiling, global ids, squared norms, sharding,
    centering mean) done once, reused by every :func:`query_knn` batch.
    See ``mpi_knn_tpu.serve`` for the full engine."""
    from mpi_knn_tpu.serve import build_index as _build

    return _build(corpus, config=config, mesh=mesh, **overrides)


def query_knn(queries, index, config: Optional[KNNConfig] = None,
              **overrides) -> KNNResult:
    """Queries-vs-resident-corpus top-k over a :func:`build_index` handle.

    The serving counterpart of ``all_knn(corpus, queries=...)``: the corpus
    never moves, query batches are padded to power-of-two row buckets, and
    each (bucket, config) executable is AOT-compiled exactly once — a
    steady-state query stream issues zero recompiles for ANY batch size
    (machine-verified; see ``mpi_knn_tpu.serve``). Results are
    bit-identical to the one-shot API on every backend, returned
    host-resident with padding stripped (``ServeSession`` exposes the
    padded device arrays for callers that chain device work)."""
    from mpi_knn_tpu.serve import query_knn as _query

    return _query(queries, index, config=config, **overrides)


def knn_classify(
    result: KNNResult,
    labels,
    num_classes: int = 10,
    tie_break: str = "nearest",
) -> ClassifyResult:
    """Majority-vote classification over a KNNResult (reference C10)."""
    import jax.numpy as jnp

    return classify_from_labels(
        result.ids, jnp.asarray(labels), num_classes, tie_break=tie_break
    )
