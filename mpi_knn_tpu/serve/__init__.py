"""Query-serving engine: device-resident corpus index, bucketed AOT
executable cache, donated per-batch scratch, double-buffered dispatch.

Public surface::

    from mpi_knn_tpu.serve import build_index, query_knn, ServeSession

    index = build_index(corpus, KNNConfig(k=10, backend="serial"))
    res = query_knn(Q, index)              # one-shot, recompile-free when warm

    session = ServeSession(index)          # streaming, dispatch-ahead
    for batch_result in session.stream(batches):
        use(batch_result.ids)

Design rationale and the machine-checked donation/copy contract (lint
rule R5): ``serve/engine.py`` docstring and DESIGN.md "Serving pipeline".
Cold start — the persistent on-disk executable cache
(``serve/aotcache.py``: ``aotcache.set_cache_dir`` / ``TKNN_AOT_CACHE``,
CLI ``--cache-dir``), fingerprint-deduped parallel ``warm()``, and the
zero-copy index load — is DESIGN.md "Cold start".
"""

from mpi_knn_tpu.serve import aotcache
from mpi_knn_tpu.serve.engine import (
    BatchResult,
    ServeSession,
    bucket_rows,
    get_executable,
    query_knn,
)
from mpi_knn_tpu.serve.index import (
    CorpusIndex,
    build_index,
    build_index_blocks,
)

__all__ = [
    "BatchResult",
    "CorpusIndex",
    "ServeSession",
    "aotcache",
    "bucket_rows",
    "build_index",
    "build_index_blocks",
    "get_executable",
    "query_knn",
]
