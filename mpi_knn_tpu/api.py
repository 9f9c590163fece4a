"""Public functional API.

One entry point replaces the reference's three copy-pasted ``main()``s
(SURVEY.md §1): the backend is a config field, not a separate program.
"""

from __future__ import annotations

import threading
import weakref
from typing import Optional

import jax
import numpy as np

from mpi_knn_tpu.backends.serial import PreparedCorpus
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs import host as obs_host
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.topk import start_lane_bin_import
from mpi_knn_tpu.ops.vote import classify_from_labels
from mpi_knn_tpu.types import ClassifyResult, KNNResult


def resolve_backend(cfg: KNNConfig, mesh=None) -> str:
    if cfg.backend != "auto":
        return cfg.backend
    n = cfg.num_devices or (len(mesh.devices.flat) if mesh is not None else len(jax.devices()))
    if n > 1 and cfg.metric == "ip":
        # what KNNConfig refuses of a named ring, for the ring "auto" means
        raise ValueError(
            f"metric='ip' runs on one device: backend='auto' over {n} "
            "devices is the corpus ring, whose rounds have no "
            "inner-product form yet — pass backend='serial'"
        )
    if n > 1 and cfg.dtype == "uint8":
        raise ValueError(
            f"dtype='uint8' rests on one device: backend='auto' over {n} "
            "devices is the corpus ring, whose rounds carry float blocks — "
            "pass backend='serial'"
        )
    return "ring-overlap" if n > 1 else "serial"


class _LastCorpus:
    """The one corpus :func:`all_knn` remembers: the last concrete device
    array it prepared for a call with ``queries``, held by weak identity —
    a ``jax.Array`` is immutable, so the object IS its contents — with the
    :class:`PreparedCorpus` made from it. The entry, and the device memory
    of its arrays, go when the caller drops the array or another corpus
    (or another form of this one) is prepared."""

    def __init__(self):
        # re-entrant: the weak reference's callback can run inside any
        # allocation, one made under the lock by this thread among them
        self._lock = threading.RLock()
        self._ref = self._prepared = None

    def get(self, corpus, form: dict) -> Optional[PreparedCorpus]:
        with self._lock:
            if (self._ref is not None and self._ref() is corpus
                    and self._prepared.form == form):
                return self._prepared
        return None

    def put(self, corpus, prepared: PreparedCorpus) -> None:
        ref = weakref.ref(corpus, self._drop)
        with self._lock:
            self._ref, self._prepared = ref, prepared

    def _drop(self, ref) -> None:
        with self._lock:
            if self._ref is ref:
                self._ref = self._prepared = None

    def clear(self) -> None:
        with self._lock:
            self._ref = self._prepared = None


_remembered = _LastCorpus()


class _CallWatch:
    """The overrun rule (``obs/host.py``) on consecutive :func:`all_knn`
    calls that HIT one prepared corpus from one thread at one query height:
    the entry-to-entry period against the running median of the periods
    before it. A period is the call's own host span (entry to its last
    dispatch's return) and what lay outside it — the device, its runtime or
    the caller, for the result is not waited for here; the part with the
    larger excess over its own median is where an overrun sat
    (``dispatch`` / ``outside``). A miss, a bypass, another corpus, height
    or thread begins anew."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rule = obs_host.OverrunRule()
        self._report = obs_host.OverrunReport("knn_call", "api")
        # the call before: (prepared's weakref, rows, thread, its entry's
        # sample, its host span's seconds)
        self._last = None
        self._calls = 0

    def note(self, prepared, rows: int, entry, host_s: float) -> None:
        """One finished call: ``prepared`` is the corpus it hit (None: it
        prepared its own), ``entry`` its :func:`~obs.host.host_sample` at
        entry, ``host_s`` its span's seconds."""
        with self._lock:
            last, self._last = self._last, None
            if prepared is None:
                self._rule.reset()
                return
            self._calls += 1
            tid = threading.get_ident()
            self._last = (weakref.ref(prepared), rows, tid, entry, host_s)
            if last is None:
                self._rule.reset()
                return
            ref, last_rows, last_tid, began, dispatch_s = last
            if ref() is not prepared or (last_rows, last_tid) != (rows, tid):
                self._rule.reset()
                return
            parts = {"dispatch": dispatch_s,
                     "outside": max(0.0, entry.at - began.at - dispatch_s)}
            over = self._rule.judge(rows, parts)
            if over is not None:
                self._report(
                    obs_metrics.get_registry(), over.where, over.excess_s,
                    seq=self._calls - 1, rows=rows,
                    median_ms=round(1e3 * over.median_s, 3),
                    parts_ms={n: round(1e3 * v, 3)
                              for n, v in parts.items()},
                    **obs_host.host_delta(began, entry))

    def reset(self) -> None:
        with self._lock:
            self._last = None
            self._rule.reset()


_calls = _CallWatch()


def _count_prepare(result: str) -> None:
    obs_metrics.get_registry().counter(
        "knn_corpus_prepare_total",
        help="all_knn calls by what became of the corpus side: hit (a "
        "prepared corpus was brought or remembered), miss (prepared and "
        "kept), bypass (prepared, used and dropped: a host array, a tracer, "
        "an all-pairs call, a form with nothing to keep)",
        labels={"result": result},
    ).inc()


def _form_and_maker(backend: str, cfg: KNNConfig, m, dim, nq, mesh):
    """``(form, prepare)`` of a backend: every fact that shapes its
    prepared corpus for ``nq``-row calls, and the function that makes
    one."""
    if backend == "serial":
        from mpi_knn_tpu.backends.serial import prepare_serial, serial_form

        return serial_form(cfg, m, dim, nq), prepare_serial
    if backend in ("ring", "ring-overlap"):
        from mpi_knn_tpu.backends.ring import prepare_ring, ring_form

        return ring_form(cfg, m, dim, nq, mesh, backend), prepare_ring
    raise ValueError(f"unknown backend {backend!r}")


def _prepare(corpus, cfg: KNNConfig, form: dict, make) -> PreparedCorpus:
    with obs_spans.span(
        "prepare", cat="api", rows=int(corpus.shape[0]),
        bytes=int(corpus.size) * corpus.dtype.itemsize, metric=cfg.metric,
    ):
        return make(corpus, cfg, form)


def prepare_corpus(
    corpus,
    config: Optional[KNNConfig] = None,
    mesh=None,
    query_rows: Optional[int] = None,
    **overrides,
) -> PreparedCorpus:
    """The corpus side of :func:`all_knn`, made once and handed back:
    ``all_knn(corpus=<the handle>, queries=...)`` then runs only the query
    side and the tile program. For callers who want the handle in their own
    hands; a sliced job over ONE device array gets the same from
    :func:`all_knn` itself, which remembers the last corpus it prepared.

    Args:
      corpus: (m, d) point matrix, host or device. The handle holds its own
        device arrays (the centred tile stack, ids and norms; on a ring the
        centred shards): later changes to a host array do not reach it.
      config, overrides: as :func:`all_knn`. What shapes the prepared form
        — backend, ``dtype``, ``metric``, ``center``, the tile sizes, the
        mesh, the ring's wire — must be the later calls' too; a call whose
        form differs is refused, not answered from the wrong arrays.
      mesh: optional jax.sharding.Mesh for the ring backends.
      query_rows: rows of the calls to come (the corpus tile follows the
        query rows as well: ``max_tile_elems`` caps their product). Default:
        the corpus's own rows.
    """
    cfg = (config or KNNConfig()).replace(**overrides)
    start_lane_bin_import()  # under the corpus passes below
    if not isinstance(corpus, jax.Array):
        corpus = np.asarray(corpus)
    m, dim = corpus.shape
    form, make = _form_and_maker(
        resolve_backend(cfg, mesh), cfg, m, dim,
        m if query_rows is None else int(query_rows), mesh)
    _count_prepare("miss")
    return _prepare(corpus, cfg, form, make)


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
      corpus: (m, d) point matrix, or a :func:`prepare_corpus` handle (then
        ``queries`` is required).
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

    **The corpus is prepared once, not once a call.** What a call derives
    from its corpus (centring, the tile stack, ids, norms, the one-pass
    fact; on a ring the centred shards) is a :class:`PreparedCorpus`, and
    ``all_knn`` remembers the last one it made: ONE entry, keyed by the
    corpus array's identity (held weakly) and every fact that shapes the
    prepared form, and only for a concrete device array (``jax.Array``: it
    is immutable) in a call with ``queries``. So a job that walks one
    device array slice by slice — ``all_knn(X, queries=X[lo:lo + q],
    query_ids=...)`` — pays the corpus passes in its first call and runs
    the query side and the tile program in every later one, bit for bit
    the same answers. The entry holds the prepared arrays on the device
    (about the corpus's own size) until the caller drops the array,
    another corpus or another form is prepared; a host array (mutable), a
    traced corpus and an all-pairs call are prepared, used and dropped, as
    ever. No option turns this on or off: counter
    ``knn_corpus_prepare_total{result="hit"|"miss"|"bypass"}`` and span
    ``knn:api.prepare`` say what a call did.
    """
    cfg = (config or KNNConfig()).replace(**overrides)
    if queries is None and isinstance(corpus, PreparedCorpus):
        raise ValueError(
            "a prepared corpus answers queries: pass queries=... (the "
            "all-pairs job needs the rows themselves)")
    obs_host.install_gc_hook()
    entry = obs_host.host_sample()
    rows = len(corpus if queries is None else queries)
    observe = obs_metrics.get_registry().histogram(
        "knn_call_host_seconds",
        help="per all_knn call: entry to its last dispatch's return (the "
        "result is not waited for)",
    ).observe
    host_s = []

    def sink(seconds: float) -> None:
        observe(seconds)
        host_s.append(seconds)

    # entry until the last dispatch has returned (the result is not waited for)
    try:
        with obs_spans.span("all_knn", cat="api", rows=rows, sink=sink):
            result, hit = _all_knn(corpus, queries, cfg, mesh, query_ids)
    except BaseException:
        _calls.reset()
        raise
    _calls.note(hit, rows, entry, host_s[0])
    return result


def _corpus_side(corpus, cfg: KNNConfig, form: dict, make,
                 sliced: bool) -> tuple[PreparedCorpus, bool]:
    """The corpus side of one call: brought, remembered, or prepared now —
    and then remembered where nothing can change under the entry: a
    concrete device array, in a call that is one slice of a job. With it,
    whether it was a hit (brought or remembered)."""
    if isinstance(corpus, PreparedCorpus):
        if corpus.form != form:
            differ = {k: (corpus.form.get(k), form.get(k))
                      for k in corpus.form.keys() | form.keys()
                      if corpus.form.get(k) != form.get(k)}
            raise ValueError(
                "this call's form is not the prepared corpus's (prepared, "
                f"this call): {differ}; prepare_corpus takes the call's "
                "config, mesh and query_rows")
        _count_prepare("hit")
        return corpus, True
    keep = (sliced and isinstance(corpus, jax.Array)
            and not isinstance(corpus, jax.core.Tracer))
    prepared = _remembered.get(corpus, form) if keep else None
    if prepared is not None:
        _count_prepare("hit")
        return prepared, True
    prepared = _prepare(corpus, cfg, form, make)
    # nothing to keep where the handle holds the caller's own array (it
    # would pin its key) or tracers (prepared under an outer jit)
    keep = keep and not any(
        a is corpus or isinstance(a, jax.core.Tracer)
        for a in vars(prepared).values())
    if keep:
        _remembered.put(corpus, prepared)
    _count_prepare("miss" if keep else "bypass")
    return prepared, False


def _all_knn(corpus, queries, cfg: KNNConfig, mesh, query_ids):
    """``(result, the prepared corpus the call hit or None)``."""
    start_lane_bin_import()  # under the corpus passes below
    if not isinstance(corpus, (PreparedCorpus, jax.Array)):
        corpus = np.asarray(corpus)
    m, dim = corpus.shape

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

    backend = resolve_backend(cfg, mesh)
    form, make = _form_and_maker(backend, cfg, m, dim, q_arr.shape[0], mesh)
    prepared, hit = _corpus_side(
        corpus, cfg, form, make, sliced=queries is not None)
    d, i, counts = prepared.search(q_arr, q_ids, cfg)
    return (KNNResult(dists=d, ids=i, **counts._asdict()),
            prepared if hit else None)


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
