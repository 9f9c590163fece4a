"""mpi_knn_tpu — a TPU-native exact k-nearest-neighbor framework.

A ground-up JAX/XLA/Pallas rebuild of the capabilities of ``yiapou13/mpi-knn``
(brute-force all-pairs kNN search + leave-one-out kNN classification, serial
and ring-distributed). Nothing here is a port: the reference's OpenMP distance
loops (``/root/reference/knn-serial.c:72-93``) become MXU matmuls, its
hand-rolled MPI ring (``/root/reference/mpi-knn-parallel_blocking.c:122-214``)
becomes a ``lax.ppermute`` ring inside ``shard_map``, and its qsort-per-insert
top-k (``/root/reference/knn-serial.c:86-91``) becomes on-device ``lax.top_k``
merges.

Public API::

    from mpi_knn_tpu import all_knn, knn_classify, KNNConfig
    result = all_knn(corpus, k=30)                # leave-one-out all-kNN
    result = all_knn(corpus, queries=Q, k=10)     # query mode
    pred   = knn_classify(result, labels, num_classes=10)
"""

import importlib
import typing

# Lazy (PEP 562) exports: the api/models modules import jax at load, but
# the resilience supervisors (bench.py, `mpi-knn doctor`) import
# `mpi_knn_tpu.resilience.*` from processes that must never touch a
# (possibly wedged) device transport — `import mpi_knn_tpu.resilience`
# executes THIS file, so the public API must not drag jax in eagerly.
_EXPORTS = {
    "KNNConfig": "mpi_knn_tpu.config",
    "KNNResult": "mpi_knn_tpu.types",
    "all_knn": "mpi_knn_tpu.api",
    "prepare_corpus": "mpi_knn_tpu.api",
    "PreparedCorpus": "mpi_knn_tpu.api",
    "build_index": "mpi_knn_tpu.api",
    "query_knn": "mpi_knn_tpu.api",
    "knn_classify": "mpi_knn_tpu.api",
    "KNNClassifier": "mpi_knn_tpu.models.classifier",
}

if typing.TYPE_CHECKING:  # static analyzers see the eager imports
    from mpi_knn_tpu.api import (
        PreparedCorpus,
        all_knn,
        build_index,
        knn_classify,
        prepare_corpus,
        query_knn,
    )
    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.models.classifier import KNNClassifier
    from mpi_knn_tpu.types import KNNResult

__version__ = "0.1.0"


def __getattr__(name):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        )
    value = getattr(importlib.import_module(target), name)
    globals()[name] = value  # cache: resolve once per process
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))

__all__ = [
    "KNNConfig",
    "KNNResult",
    "all_knn",
    "prepare_corpus",
    "PreparedCorpus",
    "build_index",
    "query_knn",
    "knn_classify",
    "KNNClassifier",
    "__version__",
]
