"""Streamed query serving: bucketed AOT executable cache + double-buffered
batch pipeline over a :class:`~mpi_knn_tpu.serve.index.CorpusIndex`.

Why buckets: ``jax.jit`` compiles per shape, so serving raw batch sizes
means one compile per distinct size — a stream of ragged batches never
stops compiling. Here every batch is padded up to the smallest
``query_bucket · 2^j`` rows and each (bucket, config) pair is
``jit(...).lower(...).compile()``d exactly once; a steady-state stream
touches a handful of buckets and issues ZERO recompiles after warm-up
(machine-checked by the compile-counter tests in ``tests/test_serve.py``
via ``jax.monitoring``). Padded rows carry query id −1 and zero data; the
per-row independence of the tile reduction makes ragged batches
bit-identical to their unpadded selves.

Why donation: the per-batch top-k scratch (``carry_d``/``carry_i``) is
passed to the executable with ``donate_argnums``, so XLA aliases it to the
output buffers (``input_output_alias`` in the module header) and
steady-state serving reuses the same carry memory in place. The padded
QUERY buffer is deliberately NOT donated: there is no query-shaped output
to alias it to (XLA would ignore the donation and warn), so the engine
owns that buffer and drops its reference after dispatch instead. Lint
rule R5 (``analysis/rules.py``) reads the alias map and a copy census
back from the lowered batch program, so "donation happened" and "the
resident corpus is not copied per batch" are compiled-program facts, not
intent.

Why dispatch-ahead: ``dispatch_depth`` bounds how many batches may be in
flight; at depth ≥ 2 batch t+1's H2D transfer and dispatch overlap batch
t's device compute (double buffering). Timing is honest per the
BASELINE.md methodology — a batch is only timed when ``device_sync`` has
forced its result to materialize, never at dispatch.

Why resilience lives here (ISSUE 6): a serving stack is only
production-shaped when hangs, transient faults, and overload degrade
gracefully. ``ServeSession`` optionally takes a
:class:`~mpi_knn_tpu.resilience.ladder.ResiliencePolicy`: per-batch
deadline, bounded-backoff retry of transient dispatch failures, a
NaN/all-inf sentinel on every retired top-k, and an explicit degradation
ladder (smaller ``nprobe`` → ``precision_policy="mixed"`` → smaller
bucket) walked on repeated deadline breach — every rung an ordinary
(bucket, config) cell of this cache, every degradation stamped into the
per-batch records. The fault-injection hooks
(``mpi_knn_tpu/resilience/faults.py``) make all of it testable on CPU.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import functools
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig, RangeCapError
from mpi_knn_tpu.obs import host as obs_host
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.parallel.partition import pad_rows_any
from mpi_knn_tpu.resilience.faults import fault_point, poison_topk
from mpi_knn_tpu.resilience.heartbeat import maybe_beat
from mpi_knn_tpu.resilience.ladder import (
    FULL_RUNG,
    PoisonedResultError,
    ResiliencePolicy,
    build_ladder,
)
from mpi_knn_tpu.resilience.retry import retry_with_backoff
from mpi_knn_tpu.serve.index import CorpusIndex
from mpi_knn_tpu.types import KNNResult
from mpi_knn_tpu.utils.pjrt import pjrt_memory_stats
from mpi_knn_tpu.utils.timing import device_sync


# a usual ``wait`` shorter than this cannot tell a late device from a late
# thread by the next batch's wait (ServeSession._close_overrun)
WAIT_TELLS_S = 0.001


def bucket_rows(n: int, base: int) -> int:
    """Smallest ``base · 2^j`` (j ≥ 0) that holds ``n`` rows — power-of-two
    row buckets over a configurable base, so a stream of arbitrary batch
    sizes quantizes to O(log(max/base)) executables instead of one per
    size."""
    if n < 1:
        raise ValueError(f"batch must have >= 1 row, got {n}")
    b = base
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Executable cache. What a kind's batch program is — the function to jit,
# its statics and donation, its argument shapes, its scratch — is its
# layout's to say (``serve.index.BatchLayout``, carried by the index);
# everything below reads ``index.layout`` and names no kind.


@dataclasses.dataclass
class _BucketExec:
    """One AOT-compiled (bucket, config) cell plus everything a dispatch
    needs: padded row count, query tiling, and the run adapter state."""

    compiled: object  # jax.stages.Compiled
    bucket: int
    q_pad: int
    q_tile: int
    cfg: KNNConfig
    q_sharding: object | None  # NamedSharding of the query side, if any
    # the all −1 query-id vector is identical for every batch of this
    # executable (serving queries carry no corpus identity) and is NOT
    # donated — built once here instead of re-uploaded per submit
    qids: jax.Array
    # the layout's maker of one batch's donated scratch (fresh buffers
    # per call: the executable consumes them)
    make_carry: object
    # the (static) bytes a sharded batch's all-to-alls move — stamped
    # into the exchange-bytes counter without reading the device
    exchange_bytes: int | None = None
    # how this cell's executable came to exist: "compiled" (a real XLA
    # compile in this process) or "cache-hit" (revived from the
    # persistent AOT cache, zero XLA work) — warm() reports tally it
    source: str = "compiled"
    # static peak HBM of this executable (args + outputs − aliased +
    # temps, from PJRT's own memory_analysis at build time — zero
    # device reads, ISSUE 15): the serve_peak_hbm_bytes gauge, the
    # --report summary and /healthz index facts all read this figure;
    # 0 when the runtime could not answer (absent, never fake)
    peak_hbm_bytes: int = 0


def bucket_shapes(index, cfg: KNNConfig, bucket: int):
    """``(q_pad, q_tile)`` of one (bucket, config) cell — pure shape
    math, shared by the lowering below and the persistent-cache hit path
    (which must build a dispatchable :class:`_BucketExec` WITHOUT tracing
    or lowering anything)."""
    return index.layout.bucket_shapes(index, cfg, bucket)


def _batch_args(index, cfg: KNNConfig, bucket: int):
    """One cell's ``(q_pad, q_tile, array arguments in call order)``: the
    layout's query side, then its resident arrays."""
    lay = index.layout
    q_pad, q_tile = lay.bucket_shapes(index, cfg, bucket)
    return q_pad, q_tile, (
        *lay.query_side(index, cfg, q_pad, q_tile), *lay.resident(index)
    )


def expected_args(index, cfg: KNNConfig, bucket: int) -> list:
    """The flattened ``(shape, dtype)`` input signature the cell's
    executable must carry: the very arguments :func:`lower_bucket` lowers
    with. The persistent AOT cache checks a loaded executable's
    ``args_info`` against this, so even a fingerprint collision cannot
    put a mismatched program on the dispatch path."""
    return _signature(_batch_args(index, cfg, bucket)[2])


def _signature(args) -> list:
    return [
        (tuple(int(s) for s in a.shape), str(jnp.dtype(a.dtype)))
        for a in args
        if a is not None
    ]


def lower_bucket(index, cfg: KNNConfig, bucket: int):
    """The per-batch program for one (bucket, config) cell as a
    ``jax.stages.Lowered`` — the exact object the executable cache
    compiles, exposed so the lint engine (``analysis.lowering``) inspects
    production lowerings rather than a parallel reimplementation. Returns
    ``(lowered, q_pad, q_tile)``."""
    q_pad, q_tile, args = _batch_args(index, cfg, bucket)
    lay = index.layout
    lowered = lay.jit(cfg.donate).lower(
        *args, **lay.statics(index, cfg, bucket)
    )
    return lowered, q_pad, q_tile


def _fingerprint_cfg(cfg: KNNConfig) -> KNNConfig:
    """The cache fingerprint: the full config MINUS the host-only knobs
    that never reach ``lower_bucket`` (dispatch_depth paces the session;
    query_bucket only selects the bucket, which is a separate key
    component). Without this, changing the dispatch depth would recompile
    a bit-identical executable for every warm bucket. The live-mutation
    pacing knobs are host-only the same way: ``mutation_bucket`` only
    selects a mutation cell's bucket, the compact thresholds pace the
    background compactor, and ``bucket_headroom`` is a BUILD-time shape
    input whose effect the index facts already carry (``bucket_cap``) —
    none of them reach ``lower_bucket``."""
    return cfg.replace(
        dispatch_depth=1, query_bucket=1, mutation_bucket=1,
        bucket_headroom=0.0, compact_fill_threshold=1.0,
        compact_tombstone_fraction=1.0,
    )


# per-(index, cell) compile locks so a parallel warm pool (and a live
# dispatch racing it) compiles each distinct cell exactly once; the lock
# map lives on the index instance (``__dict__``-attached, like ``_cache``
# a per-index mutable) and the tiny module mutex only guards map access
_KEYLOCK_MUTEX = threading.Lock()


def _key_lock(index, key) -> threading.Lock:
    with _KEYLOCK_MUTEX:
        locks = index.__dict__.setdefault("_cache_key_locks", {})
        lk = locks.get(key)
        if lk is None:
            lk = locks[key] = threading.Lock()
        return lk


def mutation_lock(index) -> threading.Lock:
    """The per-index mutation lock (ISSUE 14): every live mutation
    (upsert/delete scatter, compact swap — ``serve.mutate``) and every
    batch dispatch (``_run``) serialize on it, so a query batch always
    runs against a CONSISTENT store — wholly before or wholly after any
    mutation, never an in-between (the donated in-place scatters would
    otherwise race the dispatch reading ``_resident_args``). Held only
    for the O(chunk) dispatch / O(1) swap, never across device waits.
    The lookup is lock-free after first creation (this sits on EVERY
    batch dispatch — funneling all sessions through the global mutex
    per batch would add a cross-index serialization point); the dict
    read is atomic under the GIL and the mutex only arbitrates the
    one-time creation."""
    return _index_lock(index, "_mutation_lock")


def _index_lock(index, name: str) -> threading.Lock:
    lk = index.__dict__.get(name)
    if lk is None:
        with _KEYLOCK_MUTEX:
            lk = index.__dict__.setdefault(name, threading.Lock())
    return lk


def writer_lock(index) -> threading.Lock:
    """The per-index WRITERS' lock: one mutation at a time plans against
    the freelist, copies its chunk to the device and then takes
    :func:`mutation_lock` for its scatter's dispatch and the mirror's
    commit. Batch dispatch never takes it, so a batch waits for a
    write's dispatch at most, not for its plan or its copy. Order:
    writer lock, then mutation lock."""
    return _index_lock(index, "_writer_lock")


@contextlib.contextmanager
def held(lock: threading.Lock, side: str):
    """Hold ``lock``; what the caller waited for it goes to
    ``mutation_lock_wait_seconds_total{side=...}`` (and one to its
    ``mutation_lock_waits_total``) after the lock is released: ``side="batch"`` is a batch
    dispatch, ``"mutation"`` a write."""
    t = time.perf_counter()
    lock.acquire()
    waited = time.perf_counter() - t
    try:
        yield
    finally:
        lock.release()
        reg = obs_metrics.get_registry()
        reg.counter(
            "mutation_lock_wait_seconds_total",
            help="seconds spent waiting for an index's mutation lock, by "
            "who waited: a batch dispatch or a write",
            labels={"side": side},
        ).inc(waited)
        reg.counter(
            "mutation_lock_waits_total",
            help="acquisitions behind mutation_lock_wait_seconds_total",
            labels={"side": side},
        ).inc()


def get_executable(
    index: CorpusIndex, cfg: KNNConfig, bucket: int
) -> _BucketExec:
    """The (bucket, config) executable, built at most once per index —
    revived from the persistent AOT cache when one is active
    (``serve.aotcache``; a hit skips trace, lowering AND the XLA compile),
    compiled otherwise. The frozen config is the fingerprint (host-only
    pacing knobs canonicalized out) — two configs differing in any field
    that reaches the lowering (k, topk method, precision policy,
    donation, …) occupy distinct cells and can never serve each other's
    programs; the on-disk key extends the same fingerprint with the index
    facts, platform topology, and jax version (``aotcache.fingerprint``).
    Thread-safe per cell: concurrent callers of the same cell serialize
    on a per-key lock (one compile), distinct cells build in parallel
    (the warm pool's whole point)."""
    return _get_or_build(index, cfg, bucket, SERVE_KIND)


SERVE_KIND = "serve"  # the k-NN batch programs: ``aotcache``'s default kind
RANGE_KIND = "range"  # the range programs (``backends/range_scan.py``)


def _get_or_build(index, cfg: KNNConfig, bucket: int, kind: str):
    """The cell's executable from the index's cache, built under the
    cell's lock where it is not there. The k-NN family keys its cells
    ``(bucket, config)`` as ever, another family ``(kind, bucket,
    config)``."""
    key = (bucket, _fingerprint_cfg(cfg))
    if kind != SERVE_KIND:
        key = (kind, *key)
    exec_ = index._cache.get(key)
    if exec_ is not None:
        return exec_
    with _key_lock(index, key):
        exec_ = index._cache.get(key)
        if exec_ is None:
            exec_ = _build_executable(index, cfg, bucket, kind)
            index._cache[key] = exec_
    return exec_


def _build_executable(
    index: CorpusIndex, cfg: KNNConfig, bucket: int, kind: str = SERVE_KIND
) -> _BucketExec:
    from mpi_knn_tpu.serve import aotcache

    # a family is its lowering and the signature that lowering carries
    lower, signature = (
        (lower_range, range_expected_args) if kind == RANGE_KIND
        else (lower_bucket, expected_args))

    # the central compile capture must be live BEFORE the compile it
    # is supposed to count (idempotent; jax is already imported here)
    obs_metrics.install_jax_compile_listener()
    disk = aotcache.active_cache()
    cache_mode = "off"
    sid = obs_spans.begin_span(
        "compile", cat="compile", bucket=bucket, backend=index.backend,
        policy=cfg.precision_policy,
    )
    try:
        compiled = None
        fp = None
        if disk is not None:
            # the signature check rebuilds the cell's argspec from pure
            # shape math — a hit never lowers anything
            fp = aotcache.fingerprint(index, cfg, bucket, kind)
            compiled = disk.load(
                fp, expect_args=signature(index, cfg, bucket)
            )
            cache_mode = "hit" if compiled is not None else "miss"
        if compiled is not None:
            q_pad, q_tile = bucket_shapes(index, cfg, bucket)
        else:
            lowered, q_pad, q_tile = lower(index, cfg, bucket)
            compiled = lowered.compile()
            if disk is not None:
                # best-effort (a full disk must not fail serving); meta
                # carries the readable fingerprint for doctor/forensics
                disk.store(
                    fp, compiled,
                    meta=aotcache.fingerprint_facts(
                        index, cfg, bucket, kind),
                )
        exec_ = _finish_executable(
            index, cfg, bucket, compiled, q_pad, q_tile,
            source="cache-hit" if cache_mode == "hit" else "compiled",
        )
    except Exception as e:
        # a raised lowering/compile failure is survivable by the
        # caller — close the span with the error; an OPEN compile
        # span must stay what the contract says: a kill diagnosis
        obs_spans.end_span(sid, error=type(e).__name__)
        raise
    obs_spans.end_span(sid, cache=cache_mode)
    reg = obs_metrics.get_registry()
    if exec_.source == "cache-hit":
        reg.counter(
            "serve_executables_loaded_total",
            help="(bucket, config) cells revived from the persistent AOT "
            "cache (zero XLA compiles)",
        ).inc()
    else:
        reg.counter(
            "serve_executables_compiled_total",
            help="(bucket, config) cells compiled by the serve cache",
        ).inc()
    # compression-ladder gauges, stamped at LOWER time (pure shape
    # math, no device reads — the sharded exchange-bytes precedent):
    # the 2×/4×/8× byte cuts of bf16/int8 transfer and bf16/int8/int4
    # at-rest stores are visible in `mpi-knn metrics` / `--report`
    # next to the recall they paid.
    index.layout.stamp_gauges(index, cfg, reg)
    # peak-HBM gauge (ISSUE 15): the max static peak across this
    # index's built cells, from the executables' own buffer assignment
    # — the ledger's figure for the production shapes, stamped at
    # build time with zero device reads (the wire-gauge precedent)
    reg.gauge(
        "serve_peak_hbm_bytes",
        help="static peak live bytes of the largest built serve "
        "executable (args + outputs − aliased + temps, from PJRT "
        "memory_analysis at build time)",
    ).set(max(exec_.peak_hbm_bytes, index_peak_hbm_bytes(index)))
    return exec_


def _finish_executable(
    index, cfg: KNNConfig, bucket: int, compiled, q_pad: int, q_tile: int,
    source: str,
) -> _BucketExec:
    """Wrap a ready executable (freshly compiled OR revived from disk)
    with the dispatch-side state every batch needs — the query sharding,
    the constant query-id vector, the scratch maker, the sharded exchange
    accounting. All of it is the layout's shape math and small device
    constants, none of it needs the lowering."""
    lay = index.layout
    qsh = lay.query_sharding(index)
    # the executable's static peak HBM (ISSUE 15) — PJRT answers from
    # the compiled binary's own buffer assignment, so the figure costs
    # zero device reads and is identical for a fresh compile and an
    # AOT-cache revival of the same program
    stats = pjrt_memory_stats(compiled)
    return _BucketExec(
        compiled, bucket, q_pad, q_tile, cfg,
        q_sharding=qsh,
        # built in numpy and device_put (a transfer, never an XLA
        # program), in the shape a prepared batch has: on a persistent-
        # cache hit the whole cell build must count ZERO backend
        # compiles, and an eager jnp.full here would compile a tiny fill
        qids=jax.device_put(
            np.full(lay.prepared_rows(q_pad, q_tile), -1, np.int32), qsh
        ),
        make_carry=lay.carry_maker(index, cfg, q_pad, q_tile),
        exchange_bytes=lay.exchange_bytes(index, cfg, bucket, q_pad, q_tile),
        source=source,
        peak_hbm_bytes=stats["peak_bytes"] if stats else 0,
    )


def index_peak_hbm_bytes(index) -> int:
    """The serving peak-HBM figure of one index: the max static peak
    across its built executables (any of them may run; the binding one
    is the worst). Zero before the first cell builds — absent, never a
    fake measurement. Reads the cell cache lock-free like the dispatch
    path does (values are immutable once inserted)."""
    return max(
        # mutation cells share the dict as raw Compiled objects
        # (serve.mutate) and carry no batch-peak figure — they read 0
        (getattr(e, "peak_hbm_bytes", 0)
         for e in list(index._cache.values())),
        default=0,
    )


# ---------------------------------------------------------------------------
# Batch preparation and dispatch


def _prep_queries(index, cfg: KNNConfig, exec_: _BucketExec, q):
    """Center + pad one batch to the executable's padded row count and move
    it on device, engine-owned. Host batches are centered/padded in numpy
    (one H2D of a bucket-stable shape — no per-raw-size device programs);
    device batches stay on device (ops cached per raw shape after first
    sight). Returns (queries, qids, rows)."""
    rows = q.shape[0]
    if rows > exec_.q_pad:
        raise ValueError(
            f"batch of {rows} rows exceeds the executable's bucket "
            f"({exec_.q_pad} padded rows)"
        )
    lay = index.layout
    dtype = lay.query_dtype(cfg)
    shape = lay.prepared_rows(exec_.q_pad, exec_.q_tile) + (index.dim,)
    on_device = isinstance(q, jax.Array)
    if cfg.center and cfg.metric == "l2" and index.mu is not None:
        # same op order as all_knn's center_for_l2 on each residency, so
        # serving stays bit-identical to the one-shot API
        q = q - index.mu if (on_device or isinstance(index.mu, jax.Array)) \
            else np.asarray(q) - index.mu
        on_device = isinstance(q, jax.Array)
    if on_device:
        qp = pad_rows_any(q, exec_.q_pad, dtype=dtype)
        if lay.pretiled:
            # a shard-local reshape op, cached per bucket shape
            qp = qp.reshape(shape)
        if exec_.q_sharding is not None:
            qp = jax.device_put(qp, exec_.q_sharding)
    else:
        qh = np.asarray(q)
        if rows < exec_.q_pad:
            qh = np.pad(qh, ((0, exec_.q_pad - rows), (0, 0)))
        if exec_.q_sharding is not None:
            # one transfer, straight onto the query sharding, tiles shaped
            # on host: casting here first avoids the default-device upload
            # that a jnp.asarray → device_put resharding pair would pay
            # twice, and a host reshape is no device program
            qp = jax.device_put(
                qh.astype(dtype).reshape(shape), exec_.q_sharding
            )
        else:
            qp = jnp.asarray(qh, dtype=dtype)
    return qp, exec_.qids, rows


def _run(index, cfg: KNNConfig, exec_: _BucketExec, q, qids, extra=()):
    """Issue one prepared batch on the compiled executable; returns padded
    ((q_pad, k) dists, ids, exchange_stats-or-None, TileCounts)
    device results (async — not synchronized here). The stats slot is the
    per-shard (N_STATS·S,) vector of a layout with ``exchange_stats``;
    the last is the batch's ``backends.serial.TileCounts``, filled for the
    layouts whose batches run ``masked_dist_tile``.
    Dispatch serializes with live mutation on the per-index mutation
    lock — the resident args are read and the batch enqueued as one
    atomic step w.r.t. any in-place store update. ``extra``: the
    batch-owned operands a layout's program takes after the scratch (a
    tagged index's predicate rows, :func:`_prep_tags`)."""
    lay = index.layout
    with held(mutation_lock(index), "batch"):
        scratch = exec_.make_carry()
        if lay.tiled and not lay.pretiled:
            tiles = lay.rows(exec_.q_pad, exec_.q_tile)
            q, qids = q.reshape(*tiles, index.dim), qids.reshape(tiles)
        d, i, *rest = exec_.compiled(
            q, qids, *scratch, *extra, *lay.resident(index)
        )
        if lay.tiled:
            d = d.reshape(exec_.q_pad, cfg.k)
            i = i.reshape(exec_.q_pad, cfg.k)
        return (
            d, i,
            rest[0] if lay.exchange_stats else None,
            lay.batch_counts(index, exec_.q_pad, exec_.q_tile, rest),
        )


def _prep_tags(index, exec_: _BucketExec, scan_tags) -> tuple:
    """The predicate operand of a tagged index's scan dispatch: the rows'
    bitset rows (``serve.tags.Plan.scan_tags``) padded to the executable's
    rows with "no tag" and tiled as the queries are."""
    rows, width = scan_tags.shape
    padded = np.full((exec_.q_pad, width), index.tags.n_bitsets, np.int32)
    padded[:rows] = scan_tags
    return (jnp.asarray(padded.reshape(
        *index.layout.rows(exec_.q_pad, exec_.q_tile), width)),)


GATHER_KIND = "filter-gather"


class _GatherCell:
    """What ``ServeSession.warm`` reports a gather program as: compiled
    here (the persistent AOT cache keeps batch programs only)."""

    source = "compiled"


def lower_gather(index, cfg: KNNConfig, rows: int, slots: int):
    """A tagged index's gather program for dispatches of ``rows`` segments
    of ``slots`` candidates (``serve.tags.gather_finish``) as a
    ``jax.stages.Lowered``."""
    from mpi_knn_tpu.serve.tags import gather_finish

    tags = index.tags
    src = tags.src if tags.src is not None else index.tiles
    sds = jax.ShapeDtypeStruct
    return jax.jit(
        gather_finish, static_argnames=("cfg", "dim", "pack")
    ).lower(
        sds((rows, index.dim), index.layout.query_dtype(cfg)),
        sds((rows, slots), jnp.int32), sds(src.shape, src.dtype),
        cfg=cfg, dim=index.dim, pack=tags.pack,
    )


def get_gather_executable(index, cfg: KNNConfig, rows: int, slots: int):
    """The compiled gather program of one dispatch shape, built at most
    once an index: a cell of the same cache as the batch programs, counted
    with them (``serve_executables_compiled_total``)."""
    key = (GATHER_KIND, (rows, slots), _fingerprint_cfg(cfg))
    compiled = index._cache.get(key)
    if compiled is not None:
        return compiled
    with _key_lock(index, key):
        compiled = index._cache.get(key)
        if compiled is None:
            obs_metrics.install_jax_compile_listener()
            with obs_spans.span("compile", cat="compile", bucket=rows,
                                backend=index.backend, kind=GATHER_KIND):
                compiled = lower_gather(index, cfg, rows, slots).compile()
            obs_metrics.get_registry().counter(
                "serve_executables_compiled_total",
                help="(bucket, config) cells compiled by the serve cache",
            ).inc()
            index._cache[key] = compiled
    return compiled


def _run_gather(index, cfg: KNNConfig, queries, cand):
    """Issue one gather dispatch (``serve.tags.GatherPart``): ``queries``
    the part's rows as the caller has them, ``cand`` (R, M) its candidate
    slots. Returns padded ((R, k) dists, ids) device results (async)."""
    compiled = get_gather_executable(index, cfg, *cand.shape)
    q = np.zeros((cand.shape[0], index.dim), np.float32)
    q[:queries.shape[0]] = queries
    if cfg.center and cfg.metric == "l2" and index.mu is not None:
        q = q - np.asarray(index.mu)
    tags = index.tags
    # no mutation lock: an index with tags is frozen, its arrays never move
    return compiled(
        jnp.asarray(q, dtype=index.layout.query_dtype(cfg)),
        jnp.asarray(cand),
        tags.src if tags.src is not None else index.tiles)


def _range_args(index, cfg: KNNConfig, bucket: int):
    """``(q_pad, q_tile, arguments in call order)`` of a range cell: the
    query rows, their ids and their bounds (no scratch: the answers are
    no (rows, k) pair), then the layout's resident arrays."""
    lay = index.layout
    q_pad, q_tile = lay.bucket_shapes(index, cfg, bucket)
    rows = lay.rows(q_pad, q_tile)
    sds = jax.ShapeDtypeStruct
    return q_pad, q_tile, (
        sds(rows + (index.dim,), lay.query_dtype(cfg)),
        sds(rows, jnp.int32), sds(rows, jnp.float32),
        *lay.resident(index))


@functools.lru_cache(maxsize=None)
def _range_jit():
    from mpi_knn_tpu.backends.range_scan import serve_chunk_range

    return jax.jit(serve_chunk_range, static_argnames=("cfg",))


def lower_range(index, cfg: KNNConfig, bucket: int):
    """The range program of one (bucket, config) cell
    (``backends/range_scan.py serve_chunk_range``) as a
    ``jax.stages.Lowered``: ``(lowered, q_pad, q_tile)``."""
    q_pad, q_tile, args = _range_args(index, cfg, bucket)
    return _range_jit().lower(*args, cfg=cfg), q_pad, q_tile


def require_range(index, cfg: KNNConfig) -> None:
    """An index answers a request that names a radius only if it was
    built to (``range_cap`` > 0: the build's checks — the dense serial
    layout, no tags, whole-number rows, the width — are what make the
    answer exact)."""
    if not cfg.range_cap or not index.cfg.range_cap:
        raise ValueError(
            "this index answers k-NN alone: a request names a radius only "
            "against an index built with range_cap > 0 (mpi-knn serve "
            "--range-cap N; config.py _refuse_under_range lists what it "
            "runs on)")


def range_expected_args(index, cfg: KNNConfig, bucket: int) -> list:
    """:func:`expected_args` of a range cell."""
    return _signature(_range_args(index, cfg, bucket)[2])


def get_range_executable(index, cfg: KNNConfig, bucket: int) -> _BucketExec:
    """The range program of a (bucket, config) cell: a SECOND family of
    executables beside :func:`get_executable`'s, built the same way
    (:func:`_build_executable`), keyed apart in the index's cache
    (``RANGE_KIND``) and on disk (``aotcache.fingerprint``'s ``kind``)."""
    require_range(index, cfg)
    return _get_or_build(index, cfg, bucket, RANGE_KIND)


def _run_range(index, cfg: KNNConfig, exec_: _BucketExec, q, qids, under):
    """Issue one prepared range batch: ``under`` (rows,) float32, the
    rows' bounds (``backends/range_scan.py range_bound``), padded here
    with a bound nothing is under. Returns the program's six device
    outputs (async)."""
    lay = index.layout
    padded = np.full(exec_.q_pad, -1.0, np.float32)
    padded[:under.shape[0]] = under
    tiles = lay.rows(exec_.q_pad, exec_.q_tile)
    with held(mutation_lock(index), "batch"):
        return exec_.compiled(
            q.reshape(*tiles, index.dim), qids.reshape(tiles),
            jnp.asarray(padded.reshape(tiles)), *lay.resident(index))


def query_range(queries, radius, index: CorpusIndex,
                config: KNNConfig | None = None):
    """One-shot range batch against a resident index built with
    ``range_cap`` > 0: every live row at a squared L2 distance strictly
    under ``radius`` (one number, or one a row), as ``(lims (rows + 1,),
    dists, ids)`` — row i's results are ``dists[lims[i]:lims[i + 1]]``,
    ascending, ties by the lower id. A row with more than ``range_cap``
    results raises ``RangeCapError`` naming it: nothing is cut."""
    from mpi_knn_tpu.backends.range_scan import range_bound, require_byte_rows

    cfg = index.compatible_cfg(config or index.cfg)
    require_range(index, cfg)
    queries = np.asarray(queries, dtype=np.float32)
    require_byte_rows(queries)
    nq = queries.shape[0]
    under = range_bound(np.broadcast_to(
        np.asarray(radius, np.float32), (nq,)))
    bucket = bucket_rows(nq, cfg.query_bucket)
    exec_ = get_range_executable(index, cfg, bucket)
    q2d, qids, rows = _prep_queries(index, cfg, exec_, queries)
    res = BatchResult(None, None, rows, bucket, k=cfg.k,
                      range_out=_run_range(
                          index, cfg, exec_, q2d, qids, under),
                      range_cap=cfg.range_cap)
    lims, dists, ids, refused = res.range_answer
    _count_range(obs_metrics.get_registry(), res)
    if refused:
        raise RangeCapError(refused, cfg.range_cap)
    return lims, dists, ids


RANGE_RESULT_BUCKETS = (0, 1, 10, 100, 1000, 8192, 65536)


def _count_range(registry, res) -> None:
    """A fetched range batch on ``/metrics``: rows, results, rows that
    took the second path, rows refused over the cap, the tile steps by
    who walked them, and the results a row."""
    lims, _, _, refused = res.range_answer
    counts = np.asarray(jax.device_get(res.range_out[5])).sum(axis=0)
    registry.counter(
        "knn_range_rows_total",
        help="query rows answered by range search (padding excluded; "
        "refused rows included)").inc(res.rows)
    registry.counter(
        "knn_range_results_total",
        help="(query row, corpus row) pairs answered by range search"
    ).inc(int(lims[-1]))
    registry.counter(
        "knn_range_overflow_rows_total",
        help="range query rows whose results the lane lists could not "
        "hold (or that had none), answered by the second, complete path "
        "(scope knn.range_overflow)").inc(int(counts[2]))
    registry.counter(
        "knn_range_overflow_tiles_total",
        help="corpus tiles the second path fetched for those rows"
    ).inc(int(counts[4]))
    registry.counter(
        "knn_range_refused_rows_total",
        help="range query rows with more results than range_cap: refused "
        "by name, never cut").inc(len(refused))
    for path, steps in (("range", counts[0]), ("range_counted", counts[1])):
        registry.counter(
            obs_metrics.DIST_STEPS,
            help="distance tile steps by the path of their dot",
            labels={"path": path}).inc(int(steps))
    hist = registry.histogram(
        "knn_range_results_per_row",
        help="results of one range query row", buckets=RANGE_RESULT_BUCKETS)
    for results in np.diff(lims).tolist():
        hist.observe(results)


def check_filters(index, filters, rows: int):
    """A batch's predicates as the engine takes them: (rows,
    max_query_tags) int32 tag ids, -1 for none — or None for an index
    without tags. A predicate the index cannot honour is an error, never
    ignored: filters against an index without tags, more tags a row than
    the index was built for, an id under -1. (An id the index has never
    seen is not one: it matches nothing.)"""
    if getattr(index, "tags", None) is None:  # a clustered index has no field
        if filters is not None:
            raise ValueError(
                "this index was built without tags and cannot honour a "
                "filter (build it with tags= / --tags)")
        return None
    width = index.tags.width
    if filters is None:
        return np.full((rows, width), -1, np.int32)
    f = np.asarray(filters)
    if f.ndim != 2 or f.shape[0] != rows or not np.issubdtype(
            f.dtype, np.integer):
        raise ValueError(
            f"filters must be whole numbers, (rows, tags a row) = ({rows}, "
            f"<= {width}); got {f.dtype} {f.shape}")
    if f.shape[1] > width:
        raise ValueError(
            f"{f.shape[1]} tags a query row, but the index was built for "
            f"max_query_tags={width}")
    if f.size and f.min() < -1:
        raise ValueError("a tag id is >= 0 (-1: no tag)")
    out = np.full((rows, width), -1, np.int32)
    out[:, :f.shape[1]] = f
    return out


def _dispatch_planned(index, cfg: KNNConfig, queries, plan, phase):
    """Dispatch a planned batch of a tagged index (``serve.tags.Plan``):
    its scan part on the bucket's executable, then each gather part.
    ``phase(name)`` is the caller's span maker around the host's prep and
    the enqueues. Returns ``(bucket, TileCounts, parts)``, ``parts`` as
    ``BatchResult.parts`` takes them; the batch is counted here
    (:func:`_count_plan`)."""
    from mpi_knn_tpu.backends.serial import TileCounts

    parts, bucket, counts, slots = [], 0, TileCounts(), 0
    n_scan = plan.scan_rows.size
    if n_scan:
        q = queries if n_scan == queries.shape[0] else queries[plan.scan_rows]
        bucket = bucket_rows(n_scan, cfg.query_bucket)
        exec_ = get_executable(index, cfg, bucket)
        with phase("prep"):
            q2d, qids, _ = _prep_queries(index, cfg, exec_, q)
            extra = _prep_tags(index, exec_, plan.scan_tags)
        with phase("enqueue"):
            d, i, _, counts = _run(index, cfg, exec_, q2d, qids, extra)
        parts.append((d, i, plan.scan_rows))
    for part in plan.parts():
        with phase("enqueue"):
            d, i = _run_gather(index, cfg, queries[part.rows], part.cand)
        parts.append((d, i, part.rows))
        slots += part.cand.size
    _count_plan(obs_metrics.get_registry(), plan, int(n_scan > 0),
                len(parts) - int(n_scan > 0), slots)
    return bucket, counts, tuple(parts)


def plan_filters(index, filters, **attrs):
    """The ``serve.tags.Plan`` of some rows' filters (``check_filters``'s),
    made inside the span ``knn:filter.plan`` on the calling thread — a
    request's at its admission (``Frontend.submit``, the handler's thread),
    a batch's where a caller hands the session raw filters — its seconds
    into ``filter_plan_seconds_total``."""
    sink = obs_metrics.get_registry().counter(
        "filter_plan_seconds_total",
        help="host seconds planning query rows' predicates (look-ups, "
        "intersections, the split by regime, padding), on whichever "
        "thread planned",
    ).inc
    with obs_spans.span("plan", cat="filter", sink=sink,
                        rows=int(filters.shape[0]), **attrs):
        return index.tags.plan(filters)


def _count_plan(registry, plan, scans: int, gathers: int, slots: int) -> None:
    """A dispatched batch's rows by regime, its candidates and the
    dispatches it took, into ``registry``."""
    from mpi_knn_tpu.serve.tags import REGIMES

    by_regime = np.bincount(plan.regime, minlength=len(REGIMES))
    for name, n in zip(REGIMES, by_regime):
        registry.counter(
            "filter_rows_total",
            help="query rows of a tagged index by what became of them: "
            "none (no tag: scanned unmasked), scan (frequent tags: the "
            "masked scan), gather (a rare tag: candidates gathered and "
            "finished), empty (nothing matches: answered on the host)",
            labels={"regime": name},
        ).inc(int(n))
    registry.counter(
        "filter_candidates_total",
        help="candidate slots of the gather regime's rows, before padding",
    ).inc(plan.candidates)
    registry.counter(
        "filter_gather_slots_total",
        help="candidate slots the gather programs read, padding included "
        "(over filter_candidates_total: the regime's padding)",
    ).inc(slots)
    for name, n in (("scan", scans), ("gather", gathers)):
        registry.counter(
            "filter_dispatches_total",
            help="device dispatches of a tagged index's batches by regime "
            "(filter_rows_total over this: the mean rows of one)",
            labels={"regime": name},
        ).inc(n)


@dataclasses.dataclass
class BatchResult:
    """One served batch: padded device results plus the real row count.
    ``dists``/``ids`` strip the padding on host (no per-raw-size device
    program in the steady-state path), fetching the device buffer once —
    repeated attribute access must not re-pay the padded D2H transfer.

    The resilience fields are the per-batch record the degradation
    machinery stamps (``None``/zero when the session has no policy):
    ``degraded`` names the ladder rung the batch was DISPATCHED under
    (``None`` = the configured full rung — the PR 4 ``"degraded"`` marker
    convention), ``retries``/``backoffs`` the transient-failure retry
    story, and ``deadline_breached`` whether this batch's measured
    latency overran the policy's per-batch deadline."""

    dists_padded: jax.Array
    ids_padded: jax.Array
    rows: int
    bucket: int
    latency_s: float | None = None  # filled by the session at sync time
    seq: int = 0  # 0-indexed session-order batch number (provenance —
    # the same number the serve CLI prints on the batch's latency line)
    degraded: str | None = None  # ladder rung label, None = full
    retries: int = 0
    backoffs: tuple = ()
    deadline_breached: bool = False
    # multi-tenant composition of a coalesced batch (the serving front
    # end, mpi_knn_tpu.frontend): ((tenant, rows), ...) in row order,
    # summing to ``rows``; None = an unattributed legacy batch. The
    # session's per-tenant accumulators and the per-tenant registry
    # counters are fed from this at retire.
    tenants: tuple | None = None
    # sharded-clustered batches only: the device (N_STATS·S,) exchange
    # stats vector (routed/dropped/served per shard) + the executable's
    # static per-batch exchange bytes
    stats_padded: jax.Array | None = None
    exchange_bytes: int | None = None
    # the batch's dispatch→retire span (obs.spans handle, None when
    # nothing records): the phases of the batch name it as their parent
    span: object = None
    # serial / ring batches: the tile steps by the path of their distance
    # dot (backends.serial.dist_steps) and, from a program whose scans
    # carry the lane-bin lists, the query-tile merges by what became of
    # the carried selection (backends.serial.select_tiles) and the chunks
    # of its tiles that *bins* inserted or skipped under the row bound
    # (backends.serial.TileCounts.bins_chunks), counted at retire
    dist_steps: object = None
    select_tiles: object = None
    bins_chunks: object = None
    # from a program whose scans screen: the query rows by the screen's
    # certificate (backends.serial.TileCounts.screen_rows), counted with
    # them
    screen_rows: object = None
    # a clustered index's batch: what it probed (backends.serial
    # .TileCounts.ivf_probe), counted at retire with the rest
    ivf_probe: object = None
    # a tagged index's batch: ``((dists_padded, ids_padded, positions),
    # ...)``, one entry a device dispatch (the scan part, then the gather
    # parts), ``positions`` the batch rows its leading rows answer. Rows
    # that no entry names matched nothing. ``dists_padded``/``ids_padded``
    # then name the LAST dispatch (the device runs them in order: its end
    # is the batch's), None where every row was answered on the host.
    parts: tuple | None = None
    k: int = 0  # the answers' width, for rows no dispatch answered
    # a RANGE batch (``submit(..., radii=)``): the range program's device
    # outputs (``backends/range_scan.py serve_chunk_range``); its answers
    # are no (rows, k) pair but ``range_answer``. ``dists_padded`` /
    # ``ids_padded`` then name the flat answers' first piece (what a
    # retire waits for).
    range_out: tuple | None = None
    range_cap: int = 0

    @property
    def padded_rows(self) -> int:
        """Rows of the padded dispatches behind this batch."""
        if self.range_out is not None:
            return int(np.prod(self.range_out[0].shape))
        if self.parts is None:
            return self.dists_padded.shape[0]
        return sum(d.shape[0] for d, _, _ in self.parts)

    @functools.cached_property
    def _assembled(self) -> tuple:
        """A tagged index's answers in batch order, from its parts. A row
        that several entries answer (its candidates went out in several
        segments) gets the k smallest of them all, ties by the lower id."""
        k = self.k
        dists = np.full((self.rows, k), np.inf, np.float32)
        ids = np.full((self.rows, k), -1, np.int32)
        if self.parts:
            got = [(np.asarray(d)[: len(pos)], np.asarray(i)[: len(pos)], pos)
                   for (d, i), pos in zip(
                       jax.device_get([p[:2] for p in self.parts]),
                       (p[2] for p in self.parts))]
            d, i, pos = (np.concatenate(x) for x in zip(*got))
            if len(np.unique(pos)) < len(pos):
                flat = np.repeat(pos, k)
                order = np.lexsort((i.ravel(), d.ravel(), flat))
                flat = flat[order]
                rank = np.arange(flat.size) - np.searchsorted(flat, flat)
                keep = order[rank < k]
                dists[flat[rank < k], rank[rank < k]] = d.ravel()[keep]
                ids[flat[rank < k], rank[rank < k]] = i.ravel()[keep]
            else:
                dists[pos], ids[pos] = d, i
        # a slot past a row's matches is empty: it names no row
        ids[np.isinf(dists)] = -1
        return dists, ids

    @functools.cached_property
    def range_answer(self) -> tuple:
        """``(lims (rows + 1,), dists, ids, refused)`` of a range batch:
        row i's results are ``dists[lims[i]:lims[i + 1]]``, ascending,
        ties by the lower id; ``refused`` is ``[(row, true count), ...]``
        of the rows over the cap, which have none. One D2H of the counts
        and the flat answers' first piece; the second is fetched only
        where a query tile's results pass the first."""
        from mpi_knn_tpu.backends.range_scan import assemble

        n, head_d, head_i, rest_d, rest_i, _ = self.range_out
        n, head_d, head_i = jax.device_get((n, head_d, head_i))
        return assemble(
            np.asarray(n), np.asarray(head_d), np.asarray(head_i),
            lambda: tuple(map(np.asarray, jax.device_get((rest_d, rest_i)))),
            self.rows, self.range_cap)

    @functools.cached_property
    def dists(self) -> np.ndarray:
        if self.range_out is not None:
            return self.range_answer[1]
        if self.parts is not None:
            return self._assembled[0]
        return np.asarray(jax.device_get(self.dists_padded))[: self.rows]

    @functools.cached_property
    def ids(self) -> np.ndarray:
        if self.range_out is not None:
            return self.range_answer[2]
        if self.parts is not None:
            return self._assembled[1]
        return np.asarray(jax.device_get(self.ids_padded))[: self.rows]

    @functools.cached_property
    def exchange(self) -> np.ndarray | None:
        """Per-shard (S, N_STATS) [routed, dropped, served] exchange
        stats of a sharded-clustered batch (None elsewhere). Counts are
        over the PADDED batch — bucket padding rows route like real
        rows, deterministically."""
        if self.stats_padded is None:
            return None
        from mpi_knn_tpu.ivf.sharded import N_STATS

        return np.asarray(
            jax.device_get(self.stats_padded)
        ).reshape(-1, N_STATS)


def query_knn(
    queries,
    index: CorpusIndex,
    config: KNNConfig | None = None,
    filters=None,
    **overrides,
) -> KNNResult:
    """One-shot query batch against a resident index (the serving analogue
    of ``all_knn(corpus, queries=...)``): bucket, fetch-or-compile the
    executable, dispatch, and return (q, k) results with padding stripped.

    Results are fetched to HOST: stripping a ragged batch's padding on
    device (``d[:rows]``) would trace a fresh slice program per distinct
    raw batch size — exactly the per-shape compile churn the bucket cache
    exists to eliminate — so the strip happens in numpy, like
    ``BatchResult``. Steady-state calls at a warm bucket therefore
    compile nothing for ANY batch size; callers that want padded
    device-resident results use :class:`ServeSession`.
    """
    cfg = index.compatible_cfg(
        (config or index.cfg).replace(**overrides)
    )
    nq = queries.shape[0]
    filters = check_filters(index, filters, nq)
    if filters is not None:
        # a tagged index (``filters``: (nq, max_query_tags) tag ids, -1
        # none): planned and dispatched by regime, as a session's batch is
        plan = plan_filters(index, filters)
        bucket, counts, parts = _dispatch_planned(
            index, cfg, queries, plan, lambda name: contextlib.nullcontext())
        res = BatchResult(None, None, nq, bucket, parts=parts, k=cfg.k)
        counts = jax.device_get(counts)
        _count_tiles(obs_metrics.get_registry(), counts)
        return KNNResult(dists=res.dists, ids=res.ids, **counts._asdict())
    bucket = bucket_rows(nq, cfg.query_bucket)
    exec_ = get_executable(index, cfg, bucket)
    q2d, qids, rows = _prep_queries(index, cfg, exec_, queries)
    d, i, stats, counts = _run(index, cfg, exec_, q2d, qids)
    if stats is not None:
        _count_exchange(stats, exec_.exchange_bytes)
    d, i, counts = jax.device_get((d, i, counts))
    _count_tiles(obs_metrics.get_registry(), counts)
    return KNNResult(
        dists=np.asarray(d)[:rows],
        ids=np.asarray(i)[:rows],
        **counts._asdict(),
    )


def _count_tiles(registry, counts) -> None:
    """Add a fetched batch's ``backends.serial.TileCounts`` (or a
    ``BatchResult``'s fields of the same names) to ``registry``."""
    if counts.dist_steps is not None:
        registry.count_dist_steps(counts.dist_steps)
    if counts.select_tiles is not None:
        registry.count_select_tiles(counts.select_tiles)
    if counts.bins_chunks is not None:
        registry.count_bins_chunks(counts.bins_chunks)
    if counts.ivf_probe is not None:
        registry.count_ivf_probe(counts.ivf_probe)
    if counts.screen_rows is not None:
        registry.count_screen_rows(counts.screen_rows)


def _count_exchange(stats, exchange_bytes: int | None,
                    registry=None) -> np.ndarray:
    """Stamp one sharded batch's candidate-exchange story into the
    metrics registry: routed candidate rows (counter),
    probe-cap overflow drops (counter — a nonzero here is recall being
    spent on routing skew), and the static exchange bytes. Returns the
    per-shard (S, N_STATS) array for callers that also want it."""
    from mpi_knn_tpu.ivf.sharded import N_STATS

    reg = registry or obs_metrics.get_registry()
    per_shard = np.asarray(jax.device_get(stats)).reshape(-1, N_STATS)
    routed = int(per_shard[:, 0].sum())
    dropped = int(per_shard[:, 1].sum())
    reg.counter(
        "serve_exchange_routed_total",
        help="probe routes exchanged between shards (padded batches)",
    ).inc(routed)
    reg.counter(
        "serve_exchange_overflow_dropped_total",
        help="probes dropped at the static per-shard route cap "
        "(counted recall loss, never wrong answers)",
    ).inc(dropped)
    if exchange_bytes:
        reg.counter(
            "serve_exchange_bytes_total",
            help="bytes moved by the candidate-exchange all-to-alls "
            "(static per executable)",
        ).inc(exchange_bytes)
    return per_shard


class ServeSession:
    """Bounded dispatch-ahead serving over one index.

    ``submit`` dispatches a batch and returns any batches whose results it
    had to retire to respect ``dispatch_depth``; ``drain`` retires the
    rest. With depth ≥ 2 the next batch's preparation/H2D overlaps the
    previous batch's device compute (double buffering). Latency per batch
    is dispatch→``device_sync`` — the honest number under async dispatch.

    Sessions are REUSABLE across streams: ``stream``/``submit``+``drain``
    may be called any number of times over one session, the executable
    cache stays warm across streams (zero recompiles on the second
    stream), and ``seq`` keeps counting monotonically so batch provenance
    never aliases between streams. ``latencies``/``queries_served``/
    ``tenant_stats``/``exchange`` accumulate until ``reset_stats()``: a
    long-lived server should reset per reporting window (one float per
    batch adds up over millions of batches). See ``reset_stats`` for the
    exact window semantics (in-flight batches land in the NEW window).

    Multi-tenant attribution (the serving front end's contract): a
    coalesced batch submitted with ``tenants=((tenant, rows), ...)``
    stamps its composition on the batch span and, at retire, feeds
    ``tenant_stats`` — per tenant: served query rows, batches touched,
    latency sum/max, and (sharded-clustered sessions) a rows-proportional
    share of the routed candidate exchange — plus the labeled
    ``serve_tenant_queries_total{tenant=...}`` registry counters, so
    per-tenant reporting is first-class state, never reconstructed from
    deltas of the global accumulators.

    With a :class:`~mpi_knn_tpu.resilience.ladder.ResiliencePolicy` the
    session additionally enforces a per-batch deadline (measured at
    retire — the same dispatch→sync latency it already reports), retries
    transiently-failing dispatches with bounded exponential backoff,
    trips a NaN/all-inf sentinel on every retired batch's top-k (loudly:
    :class:`PoisonedResultError` with full batch provenance), and on
    ``degrade_after`` CONSECUTIVE deadline breaches sheds load one rung
    down the explicit degradation ladder (smaller ``nprobe`` →
    ``precision_policy="mixed"`` → smaller bucket — see
    ``resilience/ladder.py`` for why each rung is recall-safe). Every
    rung is an ordinary (bucket, config) cell of the executable cache;
    every degradation is stamped into the batch records
    (``BatchResult.degraded``) and the ``degradations`` event list.
    ``resilience=None`` (default) is the zero-overhead legacy behavior.
    """

    def __init__(
        self,
        index: CorpusIndex,
        config: KNNConfig | None = None,
        resilience: ResiliencePolicy | None = None,
        **overrides,
    ):
        self.index = index
        self.cfg = index.compatible_cfg(
            (config or index.cfg).replace(**overrides)
        )
        # observability: every session feeds the shared registry (the
        # compile capture must be live before warm()'s first compile)
        obs_metrics.install_jax_compile_listener()
        obs_host.install_gc_hook()
        self._metrics = obs_metrics.get_registry()
        self.policy = resilience
        if resilience is not None:
            self.ladder = build_ladder(index, self.cfg, resilience)
        else:
            self.ladder = [(FULL_RUNG, self.cfg)]
        self._rung = 0
        self._consecutive_breaches = 0
        self._seq = 0
        # seconds of the driving thread that its phases have covered since
        # phase_remainder last took them
        self._phase_s = 0.0
        # the overrun record (obs/host.py): the phases' seconds of the
        # cycle that the next retire ends, the driving thread's sample at
        # the retire that began it, the cycles of each bucket height so
        # far, and a ``wait`` overrun that the batch behind it has yet to
        # tell apart (_judge closes it one retire later)
        self._cycle: dict[str, float] = {}
        self._cycle_from: obs_host.Sample | None = None
        self._overruns = obs_host.OverrunRule()
        self._overrun_open: tuple | None = None
        self._report_overrun = obs_host.OverrunReport("serve_batch", "serve")
        # cold-start readiness (ISSUE 12): warm() publishes per-cell
        # progress here — /healthz's warming block and the front end's
        # per-bucket admission read it (possibly from other threads)
        self._warm_lock = threading.Lock()
        self.warm_state: dict = {"total": 0, "ready": 0, "done": True}
        self.warm_report: dict | None = None
        # window accumulators + current rung are read cross-thread (the
        # front end's /healthz handler threads via stats_snapshot, the
        # scheduler's shed/restore callbacks) while the dispatch pump
        # mutates them at retire — one lock guards them all (host-lint
        # H1 guard map: serve.engine.ServeSession). The in-flight deque
        # and seq counter stay pump-confined: the session has exactly
        # one dispatching caller by contract.
        self._stats_lock = threading.Lock()
        self._inflight: collections.deque = collections.deque()
        self.latencies: list[float] = []
        self.queries_served = 0
        self.degradations: list[dict] = []  # rung-shed events, in order
        self.restorations: list[dict] = []  # rung-restore events, in order
        self.retries_total = 0
        self.deadline_breaches = 0
        # per-tenant window accumulators (fed by batches submitted with a
        # ``tenants`` composition): tenant -> {queries, batches,
        # latency_sum_s, latency_max_s[, routed]}
        self.tenant_stats: dict[str, dict] = {}
        # live-mutation window accumulators (ISSUE 14): rows upserted/
        # tombstoned through this session + compaction passes — guarded
        # by _stats_lock like every other window stat; the index-level
        # occupancy truth lives on the freelist (serve.mutate)
        self.mutation_stats: dict[str, int] = {
            "upserts": 0, "deletes": 0, "calls": 0, "compactions": 0,
        }
        self._compactor = None
        # sharded-clustered sessions accumulate the candidate-exchange
        # story (routed/dropped totals, static exchange bytes, per-shard
        # served-request load) for the CLI report; None elsewhere
        self.exchange: dict | None = None
        if index.layout.exchange_stats:
            self.exchange = {
                "shards": index.shards,
                "routed_total": 0,
                "dropped_total": 0,
                "exchange_bytes_total": 0,
                "served_per_shard": [0] * index.shards,
            }

    @property
    def rung(self) -> str:
        """The ladder rung new submissions dispatch under."""
        with self._stats_lock:
            return self.ladder[self._rung][0]

    @property
    def next_seq(self) -> int:
        """The ``seq`` the next submitted batch will carry (for the
        dispatching thread, which alone submits)."""
        return self._seq

    def _phase_counter(self, phase: str):
        return self._metrics.counter(
            "serve_batch_phase_seconds_total",
            help="seconds of the dispatching thread by phase: idle, hold, "
            "coalesce, plan, prep, enqueue, wait, d2h, reply, other",
            labels={"phase": phase},
        )

    def phase_sink(self, phase: str):
        """``sink=`` of a span of the thread that drives this session (the
        front end's pump): its seconds go to
        ``serve_batch_phase_seconds_total{phase=...}``, so the phases of a
        window say where that thread's time went."""
        inc = self._phase_counter(phase).inc

        def sink(seconds: float) -> None:
            inc(seconds)
            self._phase_s += seconds
            self._cycle[phase] = self._cycle.get(phase, 0.0) + seconds

        return sink

    def phase_remainder(self, wall_s: float) -> None:
        """Once a turn of the driving thread's loop: what of the turn's
        ``wall_s`` (on the spans' clock) no phase covered — lock waits,
        bookkeeping, time off the CPU — goes to phase ``other``, so the
        phases partition that thread's time by construction."""
        covered, self._phase_s = self._phase_s, 0.0
        self._phase_counter("other").inc(max(0.0, wall_s - covered))

    def phase(self, name: str, cat: str = "batch", **attrs):
        """The span of one phase of the dispatching thread around a
        block, feeding :meth:`phase_sink` of the same name."""
        return obs_spans.span(
            name, cat=cat, sink=self.phase_sink(name), **attrs
        )

    def warm_snapshot(self) -> dict:
        """A consistent copy of ``warm_state`` for cross-thread readers
        (the /healthz handler, the front end's warming admission) —
        ``dict(session.warm_state)`` outside the warm lock raced the
        pool threads' per-cell updates (a dict being replaced AND
        mutated while iterated)."""
        with self._warm_lock:
            return dict(self.warm_state)

    def stats_snapshot(self) -> dict:
        """The serving-posture counters read from other threads (the
        front end's /healthz), in ONE critical section — reading the
        raw attributes while the dispatch pump retires a batch tears
        (e.g. ``sorted(tenant_stats)`` raises mid-rehash, queries_served
        disagrees with batches_retired)."""
        with self._stats_lock:
            return {
                "batches_retired": len(self.latencies),
                "queries_served": self.queries_served,
                "retries_total": self.retries_total,
                "deadline_breaches": self.deadline_breaches,
                "rung": self.ladder[self._rung][0],
                "tenants": sorted(self.tenant_stats),
                "mutation": dict(self.mutation_stats),
                # static peak HBM of the largest built cell (ISSUE 15;
                # a lock-free cache read — no new lock edge from here)
                "peak_hbm_bytes": index_peak_hbm_bytes(self.index),
            }

    def warm(self, sizes, parallel: int | None = None,
             progress=None) -> dict:
        """Pre-build the executables for the given batch sizes — at
        EVERY ladder rung, not just the configured one: the first batch
        after a degradation lands at the moment of overload, and a cold
        compile there would itself breach the deadline and cascade the
        session further down the ladder on compile latency, not load.

        Cold-start machinery (ISSUE 12):

        - cells are DEDUPED by executable fingerprint before anything
          lowers — rungs whose frozen config resolves to an identical
          program at the same bucket (e.g. the ``bucket/2`` rung when a
          size pads to the same row count) occupy one cell, so the
          dedupe saves compiles even with the persistent cache disabled;
        - distinct cells build across a thread pool (XLA releases the
          GIL during compilation; ``parallel=None`` sizes the pool to
          min(cells, cpu count), ``parallel=1`` forces the old
          sequential walk). Per-cell "compile" spans carry a ``cache``
          attr (hit/miss/off) and the aot hit/miss counters land in the
          registry, so a warm's cache story is machine-readable;
        - per-cell progress feeds ``warm_state`` (ready / total — the
          ``/healthz`` warming block) and the optional
          ``progress(ready, total, bucket)`` callback, called from pool
          threads as each executable lands.

        Returns a report: ``{cells, raw_cells, deduped, compiled,
        loaded, reused, wall_s}`` where ``loaded`` counts cells revived
        from the persistent AOT cache and ``reused`` cells that were
        already in memory before this warm."""
        t0 = time.perf_counter()
        raw: list = []
        for n in sizes:
            for _, cfg in self.ladder:
                raw.append((bucket_rows(n, cfg.query_bucket), cfg))
        distinct: dict = {}
        for bucket, cfg in raw:
            distinct.setdefault((bucket, _fingerprint_cfg(cfg)),
                                (bucket, cfg))
        cells = list(distinct.values())
        if getattr(self.index, "tags", None) is not None:
            # a tagged index's gather programs, one a candidate bucket
            # (and rung): beside the batch programs, in the same pool
            seen = {}
            for _, cfg in self.ladder:
                seen.setdefault(_fingerprint_cfg(cfg), cfg)
            cells += [(GATHER_KIND, shape, cfg) for cfg in seen.values()
                      for shape in self.index.tags.gather_shapes()]
            raw = raw + cells[len(distinct):]
        if self.cfg.range_cap:
            # the range family, at the session's own configuration (a
            # range batch knows no other rung): one cell a bucket, and its
            # first dispatch, all padding
            extra = [(RANGE_KIND, bucket, self.cfg) for bucket in sorted(
                {bucket_rows(n, self.cfg.query_bucket) for n in sizes})]
            cells += extra
            raw = raw + extra
        total = len(cells)
        with self._warm_lock:
            self.warm_state = {"total": total, "ready": 0, "done": False}
        workers = (
            max(1, min(total, os.cpu_count() or 1))
            if parallel is None else max(1, parallel)
        )

        def _one(cell):
            *kind, bucket, cfg = cell
            key = (*kind, bucket, _fingerprint_cfg(cfg))
            existed = key in self.index._cache
            if kind == [RANGE_KIND]:
                exec_ = get_range_executable(self.index, cfg, bucket)
                if not existed:
                    device_sync(_run_range(
                        self.index, cfg, exec_, *_prep_queries(
                            self.index, cfg, exec_,
                            np.zeros((1, self.index.dim), np.float32))[:2],
                        np.zeros(0, np.float32))[0])
            elif kind:
                # its first dispatch too (all padding): a gather program
                # is first met in the middle of a planned batch
                device_sync(_run_gather(
                    self.index, cfg, np.zeros((1, self.index.dim)),
                    np.full(bucket, -1, np.int32)))
                exec_ = _GatherCell()
            else:
                exec_ = get_executable(self.index, cfg, bucket)
            with self._warm_lock:
                self.warm_state["ready"] += 1
                ready = self.warm_state["ready"]
            if progress is not None:
                progress(ready, total, bucket)
            return existed, exec_

        with obs_spans.span("warm", cat="serve", sizes=list(sizes),
                            rungs=len(self.ladder), cells=total,
                            deduped=len(raw) - total, workers=workers):
            if workers <= 1 or total <= 1:
                built = [_one(c) for c in cells]
            else:
                with concurrent.futures.ThreadPoolExecutor(
                    max_workers=workers,
                    thread_name_prefix="tknn-warm",
                ) as pool:
                    built = list(pool.map(_one, cells))
        with self._warm_lock:
            self.warm_state["done"] = True
        report = {
            "cells": total,
            "raw_cells": len(raw),
            "deduped": len(raw) - total,
            "reused": sum(1 for existed, _ in built if existed),
            "loaded": sum(
                1 for existed, e in built
                if not existed and e.source == "cache-hit"
            ),
            "compiled": sum(
                1 for existed, e in built
                if not existed and e.source == "compiled"
            ),
            "wall_s": round(time.perf_counter() - t0, 4),
        }
        self.warm_report = report
        return report

    def warm_async(self, sizes, parallel: int | None = None,
                   progress=None) -> threading.Thread:
        """Run :meth:`warm` on a background thread (the serve CLI's
        bind-the-port-first startup): returns the started daemon thread;
        ``warm_state``/``bucket_ready`` expose progress to ``/healthz``
        and the front end's per-bucket admission while it runs."""
        t = threading.Thread(
            target=self.warm, args=(sizes, parallel, progress),
            name="tknn-warm-async", daemon=True,
        )
        t.start()
        return t

    def bucket_ready(self, rows: int) -> bool:
        """Whether a batch of exactly ``rows`` rows would dispatch on an
        already-built executable at the CURRENT ladder rung."""
        _, cfg = self._current_rung()
        key = (bucket_rows(max(1, rows), cfg.query_bucket),
               _fingerprint_cfg(cfg))
        return key in self.index._cache

    def coalesced_ready(self, rows: int, max_rows: int) -> bool:
        """The front end's per-bucket admission signal while warming: a
        request of ``rows`` rows admitted into a coalescer that fills up
        to ``max_rows`` can land in ANY power-of-two bucket between its
        own and the fill target's — gating on the request's own bucket
        alone would let admitted requests coalesce into a larger, still-
        cold bucket and compile inline on the dispatch pump (exactly the
        stall the 503 "warming" refusal exists to prevent). True iff
        every bucket in that span is built at the current rung."""
        _, cfg = self._current_rung()
        fp = _fingerprint_cfg(cfg)
        b = bucket_rows(max(1, rows), cfg.query_bucket)
        top = bucket_rows(max(1, max(rows, max_rows)), cfg.query_bucket)
        while True:
            if (b, fp) not in self.index._cache:
                return False
            if b >= top:
                return True
            b *= 2

    def reset_stats(self) -> None:
        """Start a fresh measurement window. The exact contract (tested
        in ``tests/test_serve.py`` — the front end's per-tenant reporting
        leans on it):

        - resets the WINDOW accumulators: ``latencies``,
          ``queries_served``, ``retries_total``, ``deadline_breaches``,
          ``tenant_stats``, and the sharded ``exchange`` totals;
        - does NOT reset serving identity or position: ``seq`` keeps
          counting (batch provenance stays unique across windows), the
          executable cache stays warm (a reset never costs a recompile),
          and the ladder keeps its current rung with its
          ``degradations``/``restorations`` history (shedding is a
          serving condition, not a statistic of one window);
        - in-flight batches keep their dispatch timestamps and land in
          the NEW window at retire — a window boundary never drops or
          double-counts a batch, it only decides which window's
          percentile the batch feeds.
        """
        with self._stats_lock:
            self.latencies = []
            self.queries_served = 0
            self.retries_total = 0
            self.deadline_breaches = 0
            self.tenant_stats = {}
            self.mutation_stats = {
                "upserts": 0, "deletes": 0, "calls": 0, "compactions": 0,
            }
            if self.exchange is not None:
                # the candidate-exchange story is part of the window:
                # totals spanning a warm-up batch would overstate routed
                # volume
                self.exchange.update(
                    routed_total=0,
                    dropped_total=0,
                    exchange_bytes_total=0,
                    served_per_shard=[0] * self.exchange["shards"],
                )

    def _current_rung(self) -> tuple:
        """(label, cfg) of the rung new work dispatches under — one
        locked read of ``_rung`` (mutated by shed/restore, possibly from
        the scheduler's overload callback while a handler thread asks
        ``bucket_ready``)."""
        with self._stats_lock:
            return self.ladder[self._rung]

    def _check_sentinel(self, res: BatchResult) -> None:
        """NaN/all-inf sentinel on a retired batch's REAL rows. NaN in a
        top-k distance has exactly one source — a poisoned distance tile
        (fp distances are sums of squares; the masks use +inf) — and an
        all-inf row means every candidate was masked away. Neither may be
        returned as an answer or dropped silently: trip loudly, with the
        provenance an operator needs to find the batch."""
        with self.phase("d2h", seq=res.seq, parent=res.span):
            d = res.dists  # strips padding; cached: the one D2H of dists
        # a range batch's reading: its distances are the flat results, every
        # one under a finite radius, so NaN OR +inf among them is a poisoned
        # tile; a row with NO result is an answer and trips nothing
        ranged = res.range_out is not None
        bad_nan = bool(np.isnan(d).any()) or (
            ranged and bool(np.isinf(d).any()))
        # a tagged index answers a row that nothing matches with k empty
        # slots: all-inf is an answer there, NaN still is not
        bad_inf = (res.parts is None and not ranged and bool(d.size)
                   and bool(np.isinf(d).all(axis=1).any()))
        if bad_inf and not bad_nan and res.exchange is not None \
                and res.exchange[:, 1].sum() > 0:
            # sharded batch under probe-cap overflow: a query whose every
            # probe was dropped legitimately retires all-inf — that is
            # the DOCUMENTED graceful recall loss (counted per shard in
            # the exchange stats and the overflow-drop counter), not a
            # poisoned tile. NaN still trips unconditionally.
            bad_inf = False
        if bad_nan or bad_inf:
            kind = "NaN" if bad_nan else "all-inf row"
            obs_spans.event(
                "poisoned-result", cat="serve", seq=res.seq,
                kind=kind, bucket=res.bucket,
            )
            self._metrics.counter(
                "serve_poisoned_results_total",
                help="batches whose top-k tripped the NaN/all-inf sentinel",
            ).inc()
            raise PoisonedResultError(
                f"poisoned top-k ({kind}) in served batch seq={res.seq} "
                f"bucket={res.bucket} rows={res.rows} "
                f"rung={res.degraded or FULL_RUNG}",
                batch_seq=res.seq,
                bucket=res.bucket,
                rung=res.degraded or FULL_RUNG,
                rows=res.rows,
            )

    def _judge(self, res: BatchResult) -> None:
        """The one judge of a retired batch, at the end of its ``wait``:
        the policy's deadline and the overrun rule read its numbers once.

        **The deadline** (with a policy that has one): count CONSECUTIVE
        breaches and shed one ladder rung when the policy's patience runs
        out. A single slow batch (compile, GC pause) never degrades; a
        breach streak does, and the event is recorded. Retry backoff sleeps
        are EXCLUDED from the comparison (``latency_s`` itself stays the
        honest dispatch→sync total): backoff is self-inflicted waiting on
        a transient fault, not load — counting it would let two transport
        blips walk the one-way ladder and spend recall on a problem the
        ladder's smaller programs cannot fix.

        **The overrun rule** (always; ``obs/host.py``): the driving thread's
        busy seconds of the CYCLE this retire ends — from the same point of
        the retire before, less what ``idle`` and ``hold`` took — against
        the running median of the cycles of this bucket height. One that
        overruns names the phase with the largest excess over its own
        median; ``wait`` with a batch in flight behind it is told apart one
        retire later by that batch's wait (:meth:`_close_overrun`). It
        changes nothing the session does: counters, an event, a log line.
        A RANGE batch is judged by both: the deadline as any batch (a
        breach streak sheds a rung for the k-NN batches; range batches
        keep the session's own configuration), the overrun rule against
        the cycles of the range batches of its height alone."""
        now = obs_host.host_sample()
        pol = self.policy
        if pol is not None and pol.batch_deadline_s is not None:
            if res.latency_s - sum(res.backoffs) <= pol.batch_deadline_s:
                self._consecutive_breaches = 0
            else:
                res.deadline_breached = True
                with self._stats_lock:
                    self.deadline_breaches += 1
                self._consecutive_breaches += 1
                self._metrics.counter(
                    "serve_deadline_breaches_total",
                    help="batches whose dispatch→sync latency overran the "
                    "deadline",
                ).inc()
                if self._consecutive_breaches >= pol.degrade_after:
                    self.shed_rung(
                        reason="deadline-breach", after_batch=res.seq)
        began, self._cycle_from = self._cycle_from, now
        phases, self._cycle = self._cycle, {}
        if began is None:
            return  # the first retire: a cycle begins here
        self._metrics.counter(
            "serve_pump_cpu_seconds_total",
            help="CPU seconds of the thread that drives the session (the "
            "front end's pump), from one retire to the next",
        ).inc(max(0.0, now.cpu_s - began.cpu_s))
        away = phases.pop("idle", 0.0) + phases.pop("hold", 0.0)
        # the phases partition the thread's time: what no span covered
        phases["other"] = max(
            0.0, now.at - began.at - away - sum(phases.values()))
        if self._overrun_open is not None:
            self._close_overrun(phases.get("wait", 0.0))
        # a range batch's cycle is its own kind: its time goes with the
        # results it found, and a k-NN batch of the same height is no
        # measure of it
        over = self._overruns.judge(
            res.bucket if getattr(res, "range_out", None) is None
            else (RANGE_KIND, res.bucket), phases)
        if over is None:
            return
        self._overrun_open = (over, {
            "seq": res.seq, "bucket": res.bucket,
            "median_ms": round(1e3 * over.median_s, 3),
            "phases_ms": {n: round(1e3 * v, 3) for n, v in phases.items()},
            "inflight": len(self._inflight),
            **obs_host.host_delta(began, now),
        })
        if over.where != "wait" or not self._inflight:
            self._close_overrun(None)

    def _close_overrun(self, next_wait_s: float | None) -> None:
        """Keep the open overrun. With a second batch in flight behind a
        long ``wait``, that batch's own wait says who was late: near zero,
        the device had finished both long before the thread came back
        (``wait-host``: the interpreter lock, the scheduler, a stopped
        process, or the runtime's completion reaching the thread late — the
        host's side of ``device_sync``, not the device's compute); near the
        usual wait, the device delivered late
        (``wait-device``: the device, its runtime, the transfer). A host
        stall that overruns took at least half a cycle, so it leaves the
        next wait under half of its median; a usual wait too short to halve
        tells nothing and the record says plain ``wait``."""
        (over, fields), self._overrun_open = self._overrun_open, None
        where = over.where
        if next_wait_s is not None and over.where_median_s >= WAIT_TELLS_S:
            late_host = next_wait_s < 0.5 * over.where_median_s
            where = "wait-host" if late_host else "wait-device"
        self._report_overrun(
            self._metrics, where, over.excess_s,
            next_wait_ms=(None if next_wait_s is None
                          else round(1e3 * next_wait_s, 3)),
            **fields)

    def shed_rung(self, *, reason: str = "deadline-breach",
                  after_batch: int | None = None) -> str | None:
        """Walk ONE rung down the degradation ladder, explicitly.

        Two callers: the session's own deadline machinery
        (``_judge``, on a breach streak) and the serving front
        end's SLO scheduler (``mpi_knn_tpu.frontend.scheduler``, on
        sustained queue growth — overload is visible upstream of the
        per-batch latency there). Either way the event is recorded the
        same: a ``degradations`` entry with the triggering ``reason``, a
        ``degrade`` flight event, and the registry counter + rung gauge —
        a rung walk is never invisible. Returns the new rung's label, or
        None when already at the ladder floor (nothing shed)."""
        with self._stats_lock:
            if self._rung >= len(self.ladder) - 1:
                return None
            self._rung += 1
            self._consecutive_breaches = 0
            label = self.ladder[self._rung][0]
            rung_idx = self._rung
            breaches = self.deadline_breaches
            ev = {
                "after_batch": after_batch if after_batch is not None
                else max(0, self._seq - 1),
                "rung": label,
                "breaches": breaches,
                "reason": reason,
            }
            self.degradations.append(ev)
        obs_spans.event(
            "degrade", cat="serve", after_batch=ev["after_batch"],
            rung=label, breaches=breaches, reason=reason,
        )
        self._metrics.counter(
            "serve_degradations_total",
            help="ladder rungs shed (deadline breach or queue overload)",
        ).inc()
        self._metrics.gauge(
            "serve_ladder_rung",
            help="current degradation-ladder rung index (0 = full)",
        ).set(rung_idx)
        return label

    def restore_rung(self, *, reason: str = "recovered") -> str | None:
        """Walk ONE rung back UP the ladder after the overload that shed
        it has passed (the front end's recovery path; the deadline
        machinery never restores — a breach-driven shed has no
        symmetrical 'deadlines are comfortably met' signal, queue depth
        does). Every rung on the way up is already compiled (``warm``
        pre-compiles the whole ladder), so a restore can never cold-
        compile into recovering traffic. Returns the restored rung's
        label, or None when already serving the full rung."""
        with self._stats_lock:
            if self._rung == 0:
                return None
            self._rung -= 1
            self._consecutive_breaches = 0
            label = self.ladder[self._rung][0]
            rung_idx = self._rung
            self.restorations.append({"rung": label, "reason": reason})
        obs_spans.event("restore", cat="serve", rung=label, reason=reason)
        self._metrics.counter(
            "serve_restorations_total",
            help="ladder rungs restored after overload recovery",
        ).inc()
        self._metrics.gauge(
            "serve_ladder_rung",
            help="current degradation-ladder rung index (0 = full)",
        ).set(rung_idx)
        return label

    # -- live mutation (ISSUE 14) -----------------------------------------
    # Thin session-facing wrappers over serve.mutate: the index mutates
    # under the per-index mutation lock (serialized with this session's
    # dispatch), the session's window accumulators take the tenant-
    # attributed story under _stats_lock. Mutations interleave freely
    # with submit()/stream() from other threads — that is the point.

    def upsert(self, ids, rows, tenant: str | None = None) -> dict:
        """Upsert rows into the live index (static shapes, donated
        in-place scatter — zero compiles at a warm mutation bucket).
        Returns the mutation stats. A clustered index that overflows its
        headroom compacts synchronously ONCE and retries (the background
        compactor normally fires on the fill threshold first, so this is
        the backstop for a burst that outruns it); the serial layout has
        no re-cluster pass, so its overflow propagates. Raises
        :class:`~mpi_knn_tpu.ivf.mutate.BucketOverflowError` when even a
        compacted store cannot absorb the rows."""
        from mpi_knn_tpu.ivf.mutate import BucketOverflowError
        from mpi_knn_tpu.serve import mutate as serve_mutate

        try:
            stats = serve_mutate.upsert_rows(self.index, ids, rows, self.cfg)
        except BucketOverflowError:
            if self.index.backend == "serial":
                raise
            self.compact(reason="overflow")
            try:
                stats = serve_mutate.upsert_rows(
                    self.index, ids, rows, self.cfg
                )
            except BucketOverflowError:
                # a burst aimed at one cluster can outsize any balanced
                # cap — grow it so the chunk is GUARANTEED to fit (the
                # documented recompile path), rather than failing an
                # admitted write
                serve_mutate.compact_index(
                    self.index, self.cfg, reason="overflow-grow",
                    min_cap=self.index.bucket_cap + int(
                        np.shape(rows)[0]
                    ),
                )
                stats = serve_mutate.upsert_rows(
                    self.index, ids, rows, self.cfg
                )
        self._note_mutation("upserts", stats.get("upserted", 0), tenant)
        return stats

    def delete(self, ids, tenant: str | None = None) -> dict:
        """Tombstone ids in the live index (they are never returned
        again; slots reclaim via the freelist). Idempotent for unknown
        ids. Returns the mutation stats."""
        from mpi_knn_tpu.serve import mutate as serve_mutate

        stats = serve_mutate.delete_rows(self.index, ids, self.cfg)
        self._note_mutation("deletes", stats.get("deleted", 0), tenant)
        return stats

    def compact(self, reason: str = "manual", retrain: bool = True) -> dict:
        """Re-cluster/compact the live index now (the background
        ``Compactor`` calls this on trigger): store rebuilt by one
        donated scatter and swapped between batches under the mutation
        lock."""
        from mpi_knn_tpu.serve import mutate as serve_mutate

        stats = serve_mutate.compact_index(
            self.index, self.cfg, retrain=retrain, reason=reason
        )
        with self._stats_lock:
            self.mutation_stats["compactions"] += 1
        return stats

    def start_compactor(self, interval_s: float = 0.25,
                        retrain: bool = True):
        """Start (and return) the background compaction worker for this
        session — trigger-driven, heartbeat/flight-recorded, deferred
        while the session is shedding load."""
        from mpi_knn_tpu.serve.mutate import Compactor

        compactor = Compactor(self, interval_s=interval_s, retrain=retrain)
        with self._stats_lock:
            self._compactor = compactor
        return compactor.start()

    def _note_mutation(self, kind: str, n: int,
                       tenant: str | None) -> None:
        with self._stats_lock:
            self.mutation_stats[kind] += n
            self.mutation_stats["calls"] += 1
        if tenant is not None:
            self._metrics.counter(
                f"serve_tenant_{kind}_total",
                help=f"rows {kind[:-1]}ed per tenant",
                labels={"tenant": str(tenant)},
            ).inc(n)

    def _retire(self) -> BatchResult:
        res, t0 = self._inflight.popleft()
        sid = res.span
        with self.phase("wait", seq=res.seq, parent=sid):
            device_sync(res.dists_padded, res.ids_padded)
        res.latency_s = time.perf_counter() - t0
        with self._stats_lock:
            self.latencies.append(res.latency_s)
            self.queries_served += res.rows
        self._judge(res)
        if self.policy is not None and self.policy.nan_sentinel:
            try:
                self._check_sentinel(res)
            except PoisonedResultError:
                # the process survives a caught sentinel trip — close the
                # span with the error so an OPEN span stays what the
                # contract says it is: a kill diagnosis, never a raise
                obs_spans.end_span(
                    sid, latency_s=res.latency_s, retries=res.retries,
                    error="poisoned-result",
                )
                raise
        tenant_rows: dict[str, int] = {}
        if res.tenants:
            # aggregate the per-PART composition first: one tenant with
            # several coalesced requests in this batch is still ONE batch
            # (and one latency observation) for that tenant — iterating
            # raw parts would inflate batches and latency_sum per request
            for t, n in res.tenants:
                tenant_rows[t] = tenant_rows.get(t, 0) + n
            with self._stats_lock:
                for t, n in tenant_rows.items():
                    st = self.tenant_stats.setdefault(t, {
                        "queries": 0, "batches": 0,
                        "latency_sum_s": 0.0, "latency_max_s": 0.0,
                    })
                    st["queries"] += n
                    st["batches"] += 1
                    st["latency_sum_s"] += res.latency_s
                    st["latency_max_s"] = max(
                        st["latency_max_s"], res.latency_s
                    )
            for t, n in tenant_rows.items():
                self._metrics.counter(
                    "serve_tenant_queries_total",
                    help="query rows served per tenant (padding excluded)",
                    labels={"tenant": t},
                ).inc(n)
        extra = {}
        if res.stats_padded is not None:
            # the candidate-exchange story, stamped at retire (the batch
            # is already synchronized — reading the tiny stats vector
            # costs one small D2H, never a mid-pipeline sync)
            per_shard = _count_exchange(
                res.stats_padded, res.exchange_bytes,
                registry=self._metrics,
            )
            routed = int(per_shard[:, 0].sum())
            dropped = int(per_shard[:, 1].sum())
            with self._stats_lock:
                if self.exchange is not None:
                    self.exchange["routed_total"] += routed
                    self.exchange["dropped_total"] += dropped
                    self.exchange["exchange_bytes_total"] += (
                        res.exchange_bytes or 0
                    )
                    for s, n in enumerate(per_shard[:, 2].tolist()):
                        self.exchange["served_per_shard"][s] += int(n)
                if tenant_rows and res.rows:
                    # tenant-attributable exchange: the routed volume is
                    # a batch-level fact (routes are per query TILE,
                    # tiles mix tenants), so the per-tenant share is
                    # rows-proportional — documented as an attribution,
                    # not a count
                    for t, n in tenant_rows.items():
                        self.tenant_stats[t]["routed"] = (
                            self.tenant_stats[t].get("routed", 0.0)
                            + routed * n / res.rows
                        )
            extra = {"routed": routed, "dropped": dropped}
            # the per-shard load event is the hang-attribution record: a
            # flight reader pairing an OPEN batch span with the LAST
            # exchange event before it sees which shard was carrying the
            # requests when serving stopped
            obs_spans.event(
                "exchange", cat="serve", seq=res.seq,
                served_per_shard=per_shard[:, 2].tolist(),
                routed=routed, dropped=dropped,
            )
        # the dispatch→retire span closes with the same honest latency
        # the session reports; a beat per retire lets a supervisor see
        # serving progress (a wedged dispatch stops both immediately)
        obs_spans.end_span(
            sid, latency_s=res.latency_s, retries=res.retries,
            deadline_breached=res.deadline_breached, **extra,
        )
        maybe_beat(f"serve-batch-{res.seq}")
        # the batch is synchronized: its counts are on hand, a few bytes
        # after the answers' own D2H, never a wait of their own
        _count_tiles(self._metrics, res)
        if res.range_out is not None:
            with self.phase("d2h", seq=res.seq, parent=sid):
                res.range_answer  # noqa: B018 — fetched once, cached
            _count_range(self._metrics, res)
        self._metrics.counter(
            "serve_batches_total", help="batches retired"
        ).inc()
        self._metrics.counter(
            "serve_queries_total", help="query rows served (padding excluded)"
        ).inc(res.rows)
        self._metrics.counter(
            "serve_padded_rows_total",
            help="rows of the padded batches retired (bucket height); "
            "serve_queries_total over this is the fill ratio",
        ).inc(res.padded_rows)
        self._metrics.histogram(
            "serve_batch_latency_seconds",
            help="per-batch dispatch→device_sync latency",
        ).observe(res.latency_s)
        return res

    def _dispatch_range(self, queries, radii, span=None):
        """One dispatch attempt of a RANGE batch: always the session's
        own configuration — the ladder's rungs trade recall or bucket
        height for time, and a range answer is complete or refused —
        through the range family of executables
        (:func:`get_range_executable`)."""
        from mpi_knn_tpu.backends.range_scan import range_bound

        fault_point("serve-batch")
        cfg = self.cfg
        bucket = bucket_rows(queries.shape[0], cfg.query_bucket)
        exec_ = get_range_executable(self.index, cfg, bucket)
        with self.phase("prep", seq=self._seq, parent=span):
            q2d, qids, rows = _prep_queries(self.index, cfg, exec_, queries)
        with self.phase("enqueue", seq=self._seq, parent=span):
            out = _run_range(self.index, cfg, exec_, q2d, qids,
                             range_bound(radii))
        return bucket, rows, out

    def _dispatch(self, queries, cfg: KNNConfig, span=None, filters=None):
        """One dispatch attempt under ``cfg`` (a ladder rung's config).
        The fault site models a transient transport failure; the poison
        hook injects a NaN into the returned tile for sentinel tests.
        ``span`` is the batch's span, parent of the two phases here.
        ``filters`` (a tagged index): the batch's ``serve.tags.Plan``, or
        ``check_filters``'s array, planned here in a third phase ahead of
        the two, ``plan`` (around ``knn:filter.plan``); the batch is
        dispatched by regime and the last element returned is
        ``BatchResult.parts``."""
        fault_point("serve-batch")
        if filters is not None:
            plan = filters
            if not hasattr(plan, "regime"):  # raw filters: planned here
                with self.phase("plan", seq=self._seq, parent=span):
                    plan = plan_filters(self.index, filters, seq=self._seq)
            bucket, counts, parts = _dispatch_planned(
                self.index, cfg, queries, plan,
                lambda name: self.phase(name, seq=self._seq, parent=span))
            d, i = parts[-1][:2] if parts else (None, None)
            if parts:
                parts = ((poison_topk(parts[0][0]), *parts[0][1:]),
                         *parts[1:])
            return (bucket, queries.shape[0], d, i, None, None, counts,
                    parts)
        bucket = bucket_rows(queries.shape[0], cfg.query_bucket)
        exec_ = get_executable(self.index, cfg, bucket)
        with self.phase("prep", seq=self._seq, parent=span):
            q2d, qids, rows = _prep_queries(self.index, cfg, exec_, queries)
        with self.phase("enqueue", seq=self._seq, parent=span):
            d, i, stats, counts = _run(self.index, cfg, exec_, q2d, qids)
        return (bucket, rows, poison_topk(d), i, stats,
                exec_.exchange_bytes, counts, None)

    def submit(self, queries, tenants=None, filters=None, radii=None
               ) -> list[BatchResult]:
        """Dispatch one batch; ``tenants`` is an optional
        ``((tenant, rows), ...)`` composition in row order (a coalesced
        multi-tenant batch from the serving front end) — it must sum to
        the batch's row count, or the per-tenant accounting would
        silently mis-attribute. ``filters``: a predicate a query row for
        an index built with tags — (rows, tags) tag ids (``check_filters``;
        None there: no row has one), or the batch's ``serve.tags.Plan``
        where the caller planned already (the front end plans a request at
        its admission and joins the plans) — refused for an index without.
        ``radii``: (rows,) squared radii, one a query row — the batch is a
        RANGE batch (every row one; an index built with ``range_cap``),
        its result's answers are ``BatchResult.range_answer``."""
        t0 = time.perf_counter()
        if radii is not None:
            from mpi_knn_tpu.backends.range_scan import require_byte_rows

            require_range(self.index, self.cfg)
            radii = np.asarray(radii, dtype=np.float32).reshape(-1)
            if radii.shape[0] != int(queries.shape[0]):
                raise ValueError(
                    f"{radii.shape[0]} radii for {int(queries.shape[0])} "
                    "query rows: a range batch names one a row")
            if filters is not None:
                raise ValueError("range search takes no predicate yet")
            require_byte_rows(queries)
        if not hasattr(filters, "regime"):  # not a Plan made at admission
            filters = check_filters(
                self.index, filters, int(queries.shape[0]))
        elif len(filters.regime) != queries.shape[0]:
            raise ValueError("the plan is not this batch's")
        if tenants is not None:
            tenants = tuple((str(t), int(n)) for t, n in tenants)
            for t, _ in tenants:
                if not t or any(c in t for c in ('"', "\\", "\n", "\r")):
                    # tenant ids become metrics LABELS at retire; a value
                    # the exposition cannot carry must fail HERE at
                    # submit (loud, at the caller) — not at retire inside
                    # a dispatch pump that serves every other tenant
                    raise ValueError(
                        f"tenant id {t!r} must be non-empty with no "
                        "quotes, backslashes, or newlines (it becomes a "
                        "metrics label)"
                    )
            total = sum(n for _, n in tenants)
            if total != int(queries.shape[0]):
                raise ValueError(
                    f"tenant composition sums to {total} rows but the "
                    f"batch has {int(queries.shape[0])}: refusing to "
                    "mis-attribute per-tenant stats"
                )
        label, cfg = self._current_rung()
        # the batch span opens BEFORE the dispatch attempt: a hang inside
        # the dispatch leaves an OPEN "batch" record in the flight file —
        # the kill diagnosis a supervisor banks (ISSUE 7). Sessions with
        # an exchange stamp the shard topology on the span: an
        # open span plus the last retired batch's per-shard exchange
        # event is how a flight reader attributes a hang to a shard.
        span_attrs = {}
        if self.index.layout.exchange_stats:
            span_attrs["shards"] = self.index.shards
        if tenants is not None:
            # the batch span carries the tenant composition: a hang's
            # open-span diagnosis (or a slow batch in the flight record)
            # names WHOSE rows were on board, not just how many
            comp: dict[str, int] = {}
            for t, n in tenants:
                comp[t] = comp.get(t, 0) + n
            span_attrs["tenants"] = comp
        sid = obs_spans.begin_span(
            "batch", cat="serve", seq=self._seq,
            rows=int(queries.shape[0]), rung=label, **span_attrs,
        )
        pol = self.policy
        if radii is not None:
            from mpi_knn_tpu.backends.serial import TileCounts

            label = FULL_RUNG  # (a range batch knows no other rung)

            def dispatch():
                bucket, rows, out = self._dispatch_range(queries, radii, sid)
                return (bucket, rows, poison_topk(out[1]), out[2], None,
                        None, TileCounts(), None, out)
        else:
            def dispatch():
                return (*self._dispatch(queries, cfg, sid, filters), None)
        try:
            if pol is not None and pol.max_retries > 0:
                out = retry_with_backoff(
                    dispatch,
                    retries=pol.max_retries,
                    base_s=pol.backoff_base_s,
                    max_s=pol.backoff_max_s,
                    retryable=pol.retryable,
                )
                (bucket, rows, d, i, stats, xbytes, counts, parts,
                 ranged) = out.value
                retries, backoffs = out.attempts - 1, out.backoffs
                with self._stats_lock:
                    self.retries_total += retries
                if retries:
                    obs_spans.event(
                        "retry", cat="retry", seq=self._seq,
                        retries=retries, backoffs=list(backoffs),
                    )
                    self._metrics.counter(
                        "serve_retries_total",
                        help="transient dispatch failures retried",
                    ).inc(retries)
            else:
                (bucket, rows, d, i, stats, xbytes, counts, parts,
                 ranged) = dispatch()
                retries, backoffs = 0, ()
        except Exception as e:
            # a RAISED dispatch failure (retries exhausted, non-retryable
            # fault) is survivable by the caller — close the span with
            # the error; only a hang/kill leaves it open
            obs_spans.end_span(sid, error=type(e).__name__)
            raise
        res = BatchResult(
            d, i, rows, bucket,
            seq=self._seq,  # 0-indexed, matching the CLI's printed lines
            degraded=None if label == FULL_RUNG else label,
            retries=retries,
            backoffs=backoffs,
            tenants=tenants,
            stats_padded=stats,
            exchange_bytes=xbytes,
            span=sid,
            parts=parts,
            k=cfg.k,
            range_out=None if ranged is None else (
                ranged[0], d, *ranged[2:]),
            range_cap=self.cfg.range_cap,
            **counts._asdict(),
        )
        self._seq += 1
        self._inflight.append((res, t0))
        done = []
        # bound the dispatch-ahead window: at depth d, batch t+d-1 may be
        # prepared/dispatched while batch t is still in flight; depth 1
        # retires (syncs) every batch before submit returns
        while len(self._inflight) >= max(1, self.cfg.dispatch_depth):
            done.append(self._retire())
        return done

    def drain(self) -> list[BatchResult]:
        out = []
        while self._inflight:
            out.append(self._retire())
        return out

    def stream(self, batches, tenant: str | None = None,
               radius: float | None = None):
        """Serve an iterable of batches, yielding results in order.
        ``tenant`` tags every batch as one tenant's stream (single-tenant
        attribution — the ``mpi-knn query --tenant`` path); coalesced
        multi-tenant batches use ``submit(..., tenants=...)`` directly.
        ``radius``: every batch is a RANGE batch at that squared radius
        (``mpi-knn query --radius``)."""
        for q in batches:
            yield from self.submit(
                q,
                tenants=(
                    None if tenant is None
                    else ((tenant, int(q.shape[0])),)
                ),
                radii=None if radius is None else np.full(
                    int(q.shape[0]), radius, np.float32),
            )
        yield from self.drain()

    def profile(self, batches, trace_dir: str | None = None) -> dict:
        """Opt-in device-time attribution: serve ``batches`` under
        ``jax.profiler.trace`` and return the per-category device busy
        split (``mpi_knn_tpu.obs.attribution``) — matmul / sort-topk /
        collective / copy / other plus the collective-under-compute
        overlap fraction. Steady state is enforced here: every bucket
        the profile batches need is compiled BEFORE the trace opens —
        a batch size the stream never served would otherwise cold-compile
        inside the trace, the compile events would categorize as "other",
        and the split would measure compilation while claiming serving."""
        import tempfile

        from mpi_knn_tpu.obs.attribution import attribute_trace

        batches = list(batches)
        _, cfg = self._current_rung()
        for rows in sorted({int(q.shape[0]) for q in batches}):
            get_executable(
                self.index, cfg, bucket_rows(rows, cfg.query_bucket)
            )
        tdir = trace_dir or tempfile.mkdtemp(prefix="tknn-profile-")
        n = 0
        with obs_spans.span("profile", cat="profile", trace_dir=tdir):
            with jax.profiler.trace(tdir):
                for q in batches:
                    self.submit(q)
                    n += 1
                self.drain()
        out = attribute_trace(tdir)
        out["batches_profiled"] = n
        out["trace_dir"] = tdir
        return out
