"""Live index mutation through the serving stack: bucketed mutation
executables in the SAME AOT cache as serve, host orchestration
(freelist plan → donated dispatch → commit), and the background
re-cluster/compact worker (ISSUE 14).

The serving discipline, applied to writes:

- **Bucketed executables.** Upsert/delete chunks pad to
  ``mutation_bucket · 2^j`` rows, and each (bucket, config, kind) cell is
  compiled exactly once into the index's executable cache — and
  content-addressed into the persistent on-disk AOT cache
  (``serve.aotcache``, fingerprint extended with the mutation ``kind``),
  so a restarted process against a warm ``--cache-dir`` revives every
  mutation program with ZERO XLA compiles. Sustained churn at ragged
  sizes is compile-free the same way ragged query streams are
  (``jax.monitoring``-counted, ``watch_compiles``-tested).
- **Donation.** The resident store arrays are DONATED to every mutation
  executable and updated in place by scatter: a million-row index
  absorbs an upsert at the cost of the touched bucket rows, never a
  corpus-sized copy. Machine-checked, not promised: lint R5 reads the
  compiled program's ``input_output_alias`` + a copy census, R2-strict
  budgets the touched-chunk working set (``analysis/lowering.py``
  mutation cells).
- **One writer at a time, serialized with dispatch.** A per-index
  mutation lock (``engine.mutation_lock``) serializes every mutation
  (and the compact swap) with the engine's batch dispatch, so a query
  batch always runs against a consistent store: either wholly before or
  wholly after a mutation, never an in-between. The lock is held for
  the O(chunk) scatter dispatch only — mutation latency, not a stop-the-
  world.
- **Compaction in the background, shed first.** ``Compactor`` is a
  supervised daemon thread (heartbeats bracket every phase, spans flight-
  record it — a SIGKILL mid-compact leaves an open ``compact`` span as
  the diagnosis): it watches the freelist triggers
  (``compact_fill_threshold`` / ``compact_tombstone_fraction``) and runs
  the re-cluster rebuild — k-means retrained on a live-row sample OFF
  the lock, then one donated ``compact_scatter`` and an atomic store
  swap between batches. Under overload (the session is off its full
  ladder rung) compaction DEFERS — it is the first load shed, counted in
  ``compact_deferred_total``.

Layout support: the serial ``CorpusIndex`` tile stack (headroom rows,
flat freelist), the clustered ``IVFIndex`` (per-bucket freelists,
centroid-scored placement), and the mesh-sharded ``ShardedIVFIndex``
(the SAME donated scatters over the GSPMD-sharded store — S=1 is
bit-identical to unsharded). The ring's dense layout refuses loudly: its
resident blocks are wire-representation shards.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ivf.mutate import (
    BucketOverflowError,
    assign_jit,
    compact_scatter_jit,
    delete_jit,
    freelist_of,
    make_dst_store,
    plan_compact,
    plan_delete,
    plan_upsert,
    should_compact,
    upsert_jit,
)
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.distance import bf16_exact
from mpi_knn_tpu.parallel.partition import pad_cols
from mpi_knn_tpu.resilience.heartbeat import maybe_beat
from mpi_knn_tpu.serve.index import onepass_holds

__all__ = [
    "BucketOverflowError",
    "Compactor",
    "compact_index",
    "delete_rows",
    "mutation_stats",
    "supports_mutation",
    "upsert_rows",
]

MUTABLE_BACKENDS = ("serial", "ivf", "ivf-sharded")

# mutation program kinds — cache-key and AOT-fingerprint components
KIND_ASSIGN = "assign"
KIND_UPSERT = "upsert"
KIND_DELETE = "delete"
KIND_COMPACT = "compact"

# row-count buckets for the mutation chunk-size histogram (powers of two
# around the mutation_bucket grid — the frontend fill-histogram shape)
CHUNK_ROW_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                     4096)


TAGGED_FROZEN = (
    "an index built with tags is frozen: a row written without a bag would "
    "be a row no filtered query can reach, so upsert, delete and compact "
    "are refused — rebuild the index (build_index(..., tags=)) from the "
    "new rows and their bags"
)


IP_FROZEN = (
    "an index built with metric='ip' is frozen: the write path scatters a "
    "row's norm beside it and an inner-product stack keeps none, and no "
    "reference holds a changing corpus under a score — upsert, delete and "
    "compact are refused; rebuild the index from the new rows"
)


U8_FROZEN = (
    "an index built with dtype='uint8' is frozen: the write path scatters "
    "float rows and their norms, and a byte stack takes a row only after "
    "the build's own check (whole numbers in [0, 255]) against the offset "
    "it was centred by — upsert, delete and compact are refused; rebuild "
    "the index from the new rows"
)


RANGE_FROZEN = (
    "an index that answers range search (range_cap > 0) is frozen: its "
    "range programs rest on the build's checks (whole-number rows whose "
    "sums stay under 2^24) and no reference holds lists of no fixed length "
    "over a changing corpus — upsert, delete and compact are refused; "
    "rebuild the index from the new rows, or build with range_cap=0"
)


def _metric(index) -> str | None:
    return getattr(getattr(index, "cfg", None), "metric", None)


def _rests_bytes(index) -> bool:
    return getattr(getattr(index, "cfg", None), "dtype", None) == "uint8"


def _answers_range(index) -> bool:
    return bool(getattr(getattr(index, "cfg", None), "range_cap", 0))


def supports_mutation(index) -> bool:
    return (getattr(index, "backend", None) in MUTABLE_BACKENDS
            and getattr(index, "tags", None) is None
            and _metric(index) != "ip" and not _rests_bytes(index)
            and not _answers_range(index))


def _require_mutable(index) -> None:
    if getattr(index, "tags", None) is not None:
        raise ValueError(TAGGED_FROZEN)
    if _metric(index) == "ip":
        raise ValueError(IP_FROZEN)
    if _rests_bytes(index):
        raise ValueError(U8_FROZEN)
    if _answers_range(index):
        raise ValueError(RANGE_FROZEN)
    if not supports_mutation(index):
        raise ValueError(
            f"the {getattr(index, 'backend', None)!r} layout cannot honor "
            "live mutation: the ring backends hold wire-representation "
            "corpus shards (a scatter would corrupt quantized blocks) — "
            "serve mutable corpora from the serial, ivf, or ivf-sharded layouts"
        )


# ---------------------------------------------------------------------------
# Serial (dense tile stack) mutation programs — the CorpusIndex half of
# the tentpole; the clustered programs live in ivf/mutate.py


def serial_upsert_chunk(
    rows, new_ids, tpos, spos, clear_t, clear_s,
    tiles, tile_ids, tile_sqs,  # DONATED resident tile stack
    cfg: KNNConfig, by_tile: bool = False,
):
    """Donated in-place upsert into the serial tile stack: headroom rows
    (id −1 padding) absorb new rows at (tile, slot) positions the flat
    freelist allocated; updated ids clear their old slot first. The rows
    are zero-filled to the width the stack rests at (``serve/index.py
    rest_width``) ahead of the at-rest cast and the per-row norms, the
    build's own math
    (``ivf.mutate.store_rows_and_sqs``). ``by_tile``: how the rows reach
    the stack (:func:`scatter_rows_by_tile`), chosen by the layout the
    device keeps the stack in (:func:`stack_rests_row_major`)."""
    from mpi_knn_tpu.ivf.mutate import UPSERT_SCOPE, store_rows_and_sqs

    with jax.named_scope(UPSERT_SCOPE):
        rows = pad_cols(rows, tiles.shape[-1])
        at_rest, _, sqs = store_rows_and_sqs(rows, cfg, rows.shape[-1])
        tile_ids = tile_ids.at[clear_t, clear_s].set(-1, mode="drop")
        tile_ids = tile_ids.at[tpos, spos].set(new_ids, mode="drop")
        if by_tile:
            tiles = scatter_rows_by_tile(tiles, tpos, spos, at_rest)
        else:
            tiles = tiles.at[tpos, spos].set(at_rest, mode="drop")
        tile_sqs = tile_sqs.at[tpos, spos].set(
            sqs.astype(tile_sqs.dtype), mode="drop"
        )
    return tiles, tile_ids, tile_sqs


def scatter_rows_by_tile(tiles, tpos, spos, rows):
    """``tiles.at[tpos, spos].set(rows, mode="drop")``, one touched tile
    at a time: take the tile out of the stack, scatter the chunk's rows
    that belong to it, put it back. For a stack whose rows the device
    does not keep contiguous: a TPU keeps a (T, c, d) float32 stack with
    d off the 128-lane grid rows-minor (d = 100: ``{1,0,2}``, no lane
    padding), and the one-scatter form is then compiled as a copy of the
    whole stack into a row-major buffer, the scatter, and a copy back
    (d = 100 at 1224 tiles: 5.13 GB of temporaries and two passes over
    the stack a chunk, read in the program compiled for the v5e). A
    tile's slice is what the search's own scan takes every step, in the
    layout the stack has; the temporaries here are one tile's."""
    n_tiles, c_tile = tiles.shape[:2]
    # ascending; the drop sentinel (n_tiles) sorts last and fills the rest
    touched = jnp.unique(tpos, size=tpos.shape[0], fill_value=n_tiles)

    def one_tile(i, stack):
        t = touched[i]
        tile = jax.lax.dynamic_index_in_dim(stack, t, keepdims=False)
        tile = tile.at[jnp.where(tpos == t, spos, c_tile)].set(
            rows, mode="drop")
        return jax.lax.dynamic_update_index_in_dim(stack, tile, t, 0)

    return jax.lax.fori_loop(0, jnp.sum(touched < n_tiles), one_tile, tiles)


def stack_rests_row_major(index) -> bool:
    """Whether the device keeps the serial tile stack row-major (a row's
    ``dim`` elements contiguous: the default layout, every backend but
    the TPU at a ``dim`` off its lane grid). Read once from the resident
    array and kept on the index: a later reader may find the buffer
    donated away."""
    known = index.__dict__.get("_stack_row_major")
    if known is None:
        try:
            order = tuple(index.tiles.format.layout.major_to_minor)
        except Exception:  # noqa: BLE001 — no layout to read: the default
            order = None
        known = order is None or order == tuple(range(index.tiles.ndim))
        index.__dict__["_stack_row_major"] = known
    return known


serial_upsert_jit = jax.jit(
    serial_upsert_chunk, static_argnames=("cfg", "by_tile"),
    donate_argnums=(6, 7, 8),
)
SERIAL_UPSERT_DONATED = (6, 7, 8)
# the serial delete is the clustered delete program over (tile, slot) —
# one tombstone scatter on the id plane, shared verbatim
serial_delete_jit = delete_jit


# ---------------------------------------------------------------------------
# The mutation executable cache (same per-index cache dict + persistent
# AOT cache as serve, keys extended with the mutation kind)


def _store_args(index) -> tuple:
    """The donated store arrays of a mutation program, in call order."""
    if index.backend == "serial":
        return (index.tiles, index.tile_ids, index.tile_sqs)
    return (index.buckets, index.bucket_ids, index.bucket_sqs,
            index.bucket_scales)


def _store_sds(index) -> tuple:
    """The store args as ShapeDtypeStructs (shape/dtype/sharding are
    metadata — readable even while a concurrent mutation donates the
    underlying buffers away), so lowering never races a donation: the
    compact pre-build runs OFF the mutation lock by design."""
    sds = jax.ShapeDtypeStruct
    return tuple(
        None if a is None
        else sds(a.shape, a.dtype, sharding=a.sharding)
        for a in _store_args(index)
    )


def _replicated(index):
    from jax.sharding import NamedSharding, PartitionSpec

    if getattr(index, "mesh", None) is None:
        return None
    return NamedSharding(index.mesh, PartitionSpec())


def _chunk_sds(index, shapes_dtypes):
    """ShapeDtypeStructs for the chunk-side args — replicated on the
    mesh for a sharded index (the store is GSPMD-sharded; the chunk and
    its scatter indices are small and go everywhere)."""
    sds = jax.ShapeDtypeStruct
    rep = _replicated(index)
    if rep is None:
        return [sds(s, d) for s, d in shapes_dtypes]
    return [sds(s, d, sharding=rep) for s, d in shapes_dtypes]


def _mutation_chunk_specs(index, cfg: KNNConfig, bucket: int, kind: str):
    """(shape, dtype) of the chunk-side args per kind — pure shape math,
    shared by the lowering, the dispatch path, and the persistent-cache
    signature check (the ``engine.expected_args`` convention)."""
    i32 = jnp.int32
    if kind == KIND_ASSIGN:
        return [((bucket, index.dim), jnp.float32)]
    if kind == KIND_UPSERT:
        return [
            ((bucket, index.dim), jnp.float32),
            ((bucket,), i32),
            ((bucket,), i32), ((bucket,), i32),
            ((bucket,), i32), ((bucket,), i32),
        ]
    if kind == KIND_DELETE:
        return [((bucket,), i32), ((bucket,), i32)]
    if kind == KIND_COMPACT:
        # "bucket" for a compact cell is the NEW bucket_cap; the chunk
        # args are the per-old-flat-slot destination vectors
        n = index.buckets.shape[0] * index.bucket_cap
        return [((n,), i32), ((n,), i32)]
    raise ValueError(f"unknown mutation kind {kind!r}")


def expected_mutation_args(index, cfg: KNNConfig, bucket: int,
                           kind: str) -> list:
    """Flattened (shape, dtype) input signature of one mutation cell —
    what a persistent-cache hit's ``args_info`` must match."""
    args = [
        (tuple(int(x) for x in s), str(jnp.dtype(d)))
        for s, d in _mutation_chunk_specs(index, cfg, bucket, kind)
    ]
    if kind == KIND_ASSIGN:
        resident = (index.centroids, index.centroid_sqs)
    elif kind == KIND_COMPACT:
        resident = _store_args(index) + _compact_dst_shapes(index, bucket)
    elif kind == KIND_DELETE:
        # the tombstone program touches only the id plane
        resident = (
            index.tile_ids if index.backend == "serial"
            else index.bucket_ids,
        )
    else:
        resident = _store_args(index)
    for a in resident:
        if a is None:
            continue
        if isinstance(a, tuple):
            args.append(a)
        else:
            args.append(
                (tuple(int(s) for s in a.shape), str(a.dtype))
            )
    return args


def _compact_dst_shapes(index, new_cap: int) -> tuple:
    P = index.buckets.shape[0]
    out = [
        ((P, new_cap, int(index.buckets.shape[-1])),
         str(index.buckets.dtype)),
        ((P, new_cap), "int32"),
        ((P, new_cap), str(index.bucket_sqs.dtype)),
    ]
    if index.bucket_scales is not None:
        out.append(((P, new_cap), "float32"))
    return tuple(out)


def lower_mutation(index, cfg: KNNConfig, bucket: int, kind: str):
    """The one (bucket, config, kind) mutation program as a
    ``jax.stages.Lowered`` — the exact object the cache compiles, exposed
    so the lint engine lowers production mutation programs
    (``analysis/lowering.py``), like ``engine.lower_bucket`` for serve."""
    _require_mutable(index)
    chunk = _chunk_sds(index, _mutation_chunk_specs(index, cfg, bucket, kind))
    store = _store_sds(index)
    if kind == KIND_ASSIGN:
        if index.backend == "serial":
            raise ValueError("the serial layout has no centroid assignment")
        sds = jax.ShapeDtypeStruct
        return assign_jit.lower(
            chunk[0],
            sds(index.centroids.shape, index.centroids.dtype,
                sharding=index.centroids.sharding),
            sds(index.centroid_sqs.shape, index.centroid_sqs.dtype,
                sharding=index.centroid_sqs.sharding),
        )
    if kind == KIND_UPSERT:
        if index.backend == "serial":
            return serial_upsert_jit.lower(
                *chunk, *store, cfg=index.cfg,
                by_tile=not stack_rests_row_major(index))
        return upsert_jit.lower(*chunk, *store, cfg=index.cfg)
    if kind == KIND_DELETE:
        ids_plane = store[1]  # the id plane (tile_ids / bucket_ids)
        return delete_jit.lower(*chunk, ids_plane)
    if kind == KIND_COMPACT:
        if index.backend == "serial":
            raise ValueError("the serial layout compacts by rebuild only")
        sds = jax.ShapeDtypeStruct
        bsh = _bucket_sharding(index)
        dst = [
            sds(s, jnp.dtype(d)) if bsh is None
            else sds(s, jnp.dtype(d), sharding=bsh)
            for s, d in _compact_dst_shapes(index, bucket)
        ]
        if len(dst) == 3:  # unquantized: dst_scales is the empty pytree
            dst.append(None)
        return compact_scatter_jit.lower(*chunk, *store, *dst)
    raise ValueError(f"unknown mutation kind {kind!r}")


def _bucket_sharding(index):
    from jax.sharding import NamedSharding, PartitionSpec

    if getattr(index, "mesh", None) is None:
        return None
    return NamedSharding(index.mesh, PartitionSpec(index.axis))


def get_mutation_executable(index, cfg: KNNConfig, bucket: int, kind: str):
    """The compiled (bucket, config, kind) mutation cell, built at most
    once per index — revived from the persistent AOT cache when active
    (fingerprint = the serve fingerprint + the mutation kind), compiled
    otherwise. Same per-key locking as the serve cache; the key tuples
    carry the kind so serve and mutation cells share one dict without
    collision."""
    from mpi_knn_tpu.serve import aotcache
    from mpi_knn_tpu.serve.engine import _fingerprint_cfg, _key_lock

    key = (bucket, _fingerprint_cfg(cfg), kind)
    exec_ = index._cache.get(key)
    if exec_ is not None:
        return exec_
    with _key_lock(index, key):
        exec_ = index._cache.get(key)
        if exec_ is not None:
            return exec_
        obs_metrics.install_jax_compile_listener()
        disk = aotcache.active_cache()
        cache_mode = "off"
        reg = obs_metrics.get_registry()
        sid = obs_spans.begin_span(
            "compile", cat="compile", bucket=bucket, backend=index.backend,
            kind=kind,
        )
        try:
            compiled = None
            fp = None
            if disk is not None:
                fp = aotcache.fingerprint(index, cfg, bucket, kind=kind)
                compiled = disk.load(
                    fp,
                    expect_args=expected_mutation_args(
                        index, cfg, bucket, kind
                    ),
                )
                cache_mode = "hit" if compiled is not None else "miss"
            if compiled is None:
                lowered = lower_mutation(index, cfg, bucket, kind)
                compiled = lowered.compile()
                if disk is not None:
                    disk.store(
                        fp, compiled,
                        meta={**aotcache.fingerprint_facts(
                            index, cfg, bucket), "kind": kind},
                    )
        except Exception as e:
            obs_spans.end_span(sid, error=type(e).__name__)
            raise
        obs_spans.end_span(sid, cache=cache_mode)
        reg.counter(
            "mutation_executables_loaded_total"
            if cache_mode == "hit" else "mutation_executables_compiled_total",
            help="mutation (bucket, config, kind) cells revived from the "
            "persistent AOT cache" if cache_mode == "hit"
            else "mutation (bucket, config, kind) cells compiled",
        ).inc()
        index._cache[key] = compiled
    return compiled


def warm_mirror(index) -> None:
    """Make the host mirror of the id plane now, during set-up, so that
    the first write finds it (at 10 M rows its fetch and build are half
    a second that no search should wait behind). ``Frontend.start`` runs
    this on a thread of its own beside the serve programs' warm-up."""
    from mpi_knn_tpu.serve.engine import writer_lock

    with writer_lock(index):
        freelist_of(index)


def warm_mutation(index, cfg: KNNConfig | None = None,
                  sizes=(None,)) -> dict:
    """Pre-build the mutation cells for the given chunk sizes (None =
    one ``mutation_bucket``) — the serve ``warm()`` discipline for the
    write path, so the first live upsert never compiles into traffic."""
    from mpi_knn_tpu.serve.engine import bucket_rows

    cfg = cfg or index.cfg
    built = 0
    warm_mirror(index)
    for n in sizes:
        bucket = bucket_rows(
            n if n is not None else cfg.mutation_bucket, cfg.mutation_bucket
        )
        kinds = [KIND_UPSERT, KIND_DELETE]
        if index.backend != "serial":
            kinds.append(KIND_ASSIGN)
        for kind in kinds:
            get_mutation_executable(index, cfg, bucket, kind)
            built += 1
    if index.backend != "serial":
        # the compact path too: the cap-preserving scatter cell (its
        # "bucket" is bucket_cap) plus one tracing call of the
        # assignment pass, so the first trigger-fired compaction
        # compiles nothing while queries wait on the mutation lock
        get_mutation_executable(
            index, cfg, index.bucket_cap, KIND_COMPACT
        )
        from mpi_knn_tpu.ivf.mutate import compact_assign_jit
        from mpi_knn_tpu.serve.engine import mutation_lock

        with mutation_lock(index):  # the eager trace reads the store —
            # never race a concurrent donation
            compact_assign_jit(
                index.buckets, index.bucket_scales, index.centroids,
                index.centroid_sqs, cfg=index.cfg,
            ).block_until_ready()
        built += 2
    return {"cells": built}


# ---------------------------------------------------------------------------
# Orchestration: plan → dispatch (donated) → swap → commit


def _center_rows(index, rows: np.ndarray) -> np.ndarray:
    """The build's centering, applied to an upsert chunk: rows enter the
    store in the index's centered frame (the frozen build-time mean —
    L2 is translation-invariant, so a drifting mean costs conditioning,
    not correctness; compaction keeps the frame for the same reason)."""
    rows = np.asarray(rows)
    if rows.ndim != 2 or rows.shape[1] != index.dim:
        raise ValueError(
            f"upsert rows must be (n, dim={index.dim}), got {rows.shape}"
        )
    if index.mu is not None:
        rows = rows - np.asarray(index.mu)
    return np.ascontiguousarray(rows, dtype=np.float32)


def _dedupe_last(ids: np.ndarray, rows: np.ndarray | None):
    """Last occurrence wins within one chunk (duplicate scatter indices
    apply in unspecified order — refuse to race)."""
    _, last = np.unique(ids[::-1], return_index=True)
    keep = np.sort(len(ids) - 1 - last)
    if len(keep) == len(ids):
        return ids, rows
    return ids[keep], (rows[keep] if rows is not None else None)


def _pad_chunk(arr: np.ndarray, bucket: int, fill) -> np.ndarray:
    n = arr.shape[0]
    if n == bucket:
        return arr
    pad = np.full((bucket - n,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _put_chunk(index, *arrays):
    """The chunk-side args on the device (replicated over a sharded
    index's mesh), copied BEFORE the mutation lock is taken: handed to
    the executable as host arrays they would be copied inside its call,
    under the lock."""
    # one call for the whole tuple: jax batches a pytree's transfers
    return jax.device_put(arrays, _replicated(index))


def _swap_store(index, buckets, bucket_ids, bucket_sqs, bucket_scales):
    index.buckets = buckets
    index.bucket_ids = bucket_ids
    index.bucket_sqs = bucket_sqs
    if bucket_scales is not None:
        index.bucket_scales = bucket_scales


def mutation_stats(index) -> dict:
    """The freelist's occupancy snapshot (live/tombstones/fill) — what
    the gauges, ``/healthz`` and the doctor verdict report."""
    _require_mutable(index)
    return freelist_of(index).stats()


def _stamp_gauges(reg, stats: dict) -> None:
    reg.gauge(
        "index_live_rows", help="live (non-tombstoned) rows in the index"
    ).set(stats["live"])
    reg.gauge(
        "index_tombstone_fraction",
        help="tombstoned slots as a fraction of live rows (a compaction "
        "trigger)",
    ).set(stats["tombstone_fraction"])
    reg.gauge(
        "index_max_bucket_fill",
        help="largest bucket fill fraction (headroom exhaustion — a "
        "compaction trigger)",
    ).set(stats["max_fill"])


def mutation_phase(name: str, **attrs):
    """A phase of one write: span ``knn:mutate.<name>`` whose seconds go
    to ``mutation_phase_seconds_total{phase=<name>}``. The phases:
    ``parse`` (the body's decoding in the HTTP handler; validation,
    centring and padding here), ``plan`` (slots from the freelist),
    ``h2d`` (the chunk's copy to the device), ``dispatch`` (the donated
    scatter's call and the store's swap) and ``commit`` (the mirror's),
    the last two under the mutation lock."""
    return obs_spans.span(
        name, cat="mutate",
        sink=obs_metrics.get_registry().counter(
            "mutation_phase_seconds_total",
            help="host seconds of live mutation by phase (parse, plan, "
            "h2d, dispatch, commit)",
            labels={"phase": name},
        ).inc,
        **attrs,
    )


def _note_mutation(reg, kind: str, rows: int, chunk: int, t0: float):
    reg.counter(
        f"mutation_{kind}_total",
        help="rows upserted into live indices" if kind == "upserts"
        else "rows tombstoned in live indices",
    ).inc(rows)
    reg.histogram(
        "mutation_chunk_rows",
        help="rows per mutation chunk (upsert+delete)",
        buckets=CHUNK_ROW_BUCKETS,
    ).observe(chunk)
    reg.histogram(
        "mutation_latency_seconds",
        help="wall time of one mutation call (plan + donated dispatch + "
        "commit)",
    ).observe(time.perf_counter() - t0)


def upsert_rows(index, ids, rows, config: KNNConfig | None = None) -> dict:
    """Upsert ``rows`` under global ``ids`` into a resident index —
    static shapes end to end: chunk padded to the mutation bucket,
    placement scored on device (clustered layouts), slots from the
    freelist, ONE donated scatter, store swapped in place. Existing ids
    are updated (old slot tombstoned when the row moves partitions).
    Writers take turns on the writers' lock; the mutation lock, which
    batch dispatch shares, is held for the scatter's dispatch and the
    mirror's commit alone. Returns a stats dict; raises
    :class:`BucketOverflowError` when headroom is exhausted (the
    freelist and store are untouched — compact and retry)."""
    from mpi_knn_tpu.serve.engine import (
        bucket_rows,
        held,
        mutation_lock,
        writer_lock,
    )

    _require_mutable(index)
    cfg = config or index.cfg
    reg = obs_metrics.get_registry()
    t0 = time.perf_counter()
    serial = index.backend == "serial"
    with obs_spans.span("upsert", cat="mutate", rows=int(np.size(ids)),
                        backend=index.backend):
        with mutation_phase("parse"):
            ids = np.asarray(ids, dtype=np.int32).reshape(-1)
            if (ids < 0).any():
                raise ValueError("upsert ids must be >= 0 (id -1 is the "
                                 "padding/tombstone sentinel)")
            rows = _center_rows(index, rows)
            if rows.shape[0] != ids.shape[0]:
                raise ValueError(
                    f"{ids.shape[0]} ids but {rows.shape[0]} rows"
                )
            ids, rows = _dedupe_last(ids, rows)
            n = int(ids.shape[0])
            bucket = bucket_rows(n, cfg.mutation_bucket)
            rows_p = _pad_chunk(rows, bucket, 0.0)
            # the corpus side of the one-pass rule (the dense index's, and
            # a clustered store's: ``ivf/index.py store_onepass``) stops
            # holding with the first row that is no bf16 number; asked of
            # the rows only while the index still holds the fact
            breaks_onepass = onepass_holds(index) and not bf16_exact(rows)
            ex = get_mutation_executable(index, cfg, bucket, KIND_UPSERT)
        with writer_lock(index):
            with mutation_phase("plan"):
                fl = freelist_of(index)
                if serial:
                    # dense layout: no clustering — the freelist's buckets
                    # are the corpus tiles, any free slot will do (lowest
                    # tile first, deterministic); ids already live update
                    # their own tile IN PLACE and consume no slot, so a
                    # zero-headroom index still absorbs pure updates
                    parts = _serial_pick_tiles(fl, ids)
                    sentinel = index.tiles.shape[0]
                else:
                    assign = get_mutation_executable(
                        index, cfg, bucket, KIND_ASSIGN)
                    parts = np.asarray(jax.device_get(assign(
                        *_put_chunk(index, rows_p), index.centroids,
                        index.centroid_sqs,
                    )))[:n]
                    sentinel = fl.total
                part, slot, clear_p, clear_s, commit = plan_upsert(
                    fl, ids, parts
                )
            with mutation_phase("h2d"):
                args = _put_chunk(
                    index,
                    rows_p,
                    _pad_chunk(ids, bucket, -1),
                    _pad_chunk(part, bucket, sentinel),
                    _pad_chunk(slot, bucket, 0),
                    _pad_chunk(clear_p, bucket, sentinel),
                    _pad_chunk(clear_s, bucket, 0),
                )
                off = (jax.device_put(np.bool_(False))
                       if breaks_onepass else None)
            with held(mutation_lock(index), "mutation"):
                with mutation_phase("dispatch"):
                    if serial:
                        index.tiles, index.tile_ids, index.tile_sqs = ex(
                            *args, index.tiles, index.tile_ids,
                            index.tile_sqs
                        )
                    else:
                        out = ex(*args, *_store_args(index))
                        _swap_store(index, *_normalize_store_out(index, out))
                    if off is not None:
                        # the same programs take their other branch
                        index.onepass = off
                        index.__dict__["_onepass_holds"] = False
                with mutation_phase("commit"):
                    commit()
            stats = fl.stats()
        if breaks_onepass:
            reg.gauge(index.layout.onepass_gauge).set(0.0)
        _stamp_gauges(reg, stats)
    _note_mutation(reg, "upserts", n, n, t0)
    return {"upserted": n, "bucket": bucket, **stats}


def _normalize_store_out(index, out):
    """jax drops empty pytree nodes: an unquantized store's 4-tuple
    comes back as (buckets, ids, sqs, None)."""
    if len(out) == 4:
        return out
    return (*out, None)


def _serial_pick_tiles(fl, ids: np.ndarray) -> np.ndarray:
    """Tile choice for dense upserts: an id that is already LIVE keeps
    its own tile (``plan_upsert`` then updates the slot in place,
    consuming nothing — a zero-headroom index absorbs pure updates);
    new ids fill the lowest tile with headroom first (deterministic).
    Raises the shared overflow error when the new rows outnumber the
    free slots — the serial layout has no compactor; rebuild with more
    ``bucket_headroom``."""
    old = fl.lookup(ids)
    parts = old // fl.cap
    new = old < 0
    n_new = int(new.sum())
    if n_new:
        room = np.cumsum(fl.free_count)  # one entry a tile
        if n_new > room[-1]:
            raise BucketOverflowError(
                f"serial tile stack is full ({fl.live} live rows, "
                f"{n_new - int(room[-1])} new rows do not fit): rebuild "
                "the index with a larger bucket_headroom (the dense "
                "layout has no re-cluster pass)",
            )
        parts[new] = np.searchsorted(room, np.arange(n_new), side="right")
    return parts


def delete_rows(index, ids, config: KNNConfig | None = None) -> dict:
    """Tombstone ``ids``: one donated scatter sets their slots' ids to −1
    (``mask_tile`` guarantees they are never again returned), the
    freelist reclaims the slots for future upserts. Unknown ids are
    counted and skipped (idempotent). Returns a stats dict."""
    from mpi_knn_tpu.serve.engine import (
        bucket_rows,
        held,
        mutation_lock,
        writer_lock,
    )

    _require_mutable(index)
    cfg = config or index.cfg
    reg = obs_metrics.get_registry()
    t0 = time.perf_counter()
    serial = index.backend == "serial"
    with obs_spans.span("delete", cat="mutate", rows=int(np.size(ids)),
                        backend=index.backend):
        with mutation_phase("parse"):
            ids = np.asarray(ids, dtype=np.int32).reshape(-1)
            ids, _ = _dedupe_last(ids, None)
            n = int(ids.shape[0])
            bucket = bucket_rows(max(1, n), cfg.mutation_bucket)
            ex = get_mutation_executable(index, cfg, bucket, KIND_DELETE)
        with writer_lock(index):
            with mutation_phase("plan"):
                fl = freelist_of(index)
                part, slot, commit, missing = plan_delete(fl, ids)
            with mutation_phase("h2d"):
                args = _put_chunk(
                    index,
                    _pad_chunk(part, bucket, fl.total),
                    _pad_chunk(slot, bucket, 0),
                )
            with held(mutation_lock(index), "mutation"):
                with mutation_phase("dispatch"):
                    if serial:
                        index.tile_ids = ex(*args, index.tile_ids)
                    else:
                        index.bucket_ids = ex(*args, index.bucket_ids)
                with mutation_phase("commit"):
                    commit()
            stats = fl.stats()
        _stamp_gauges(reg, stats)
    deleted = n - missing
    _note_mutation(reg, "deletes", deleted, n, t0)
    return {"deleted": deleted, "missing": missing, "bucket": bucket,
            **stats}


# ---------------------------------------------------------------------------
# Compaction


def compact_index(index, config: KNNConfig | None = None,
                  retrain: bool = True, reason: str = "manual",
                  min_cap: int | None = None) -> dict:
    """Re-cluster/compact a clustered index in place: k-means retrained
    on a deterministic live-row sample (OFF the mutation lock — training
    blocks nothing), every slot re-assigned on device, and the store
    rebuilt by ONE donated scatter, then swapped atomically under the
    mutation lock (between batches — the dispatch path holds the same
    lock). ``bucket_cap`` is preserved whenever the live set fits, so
    every compiled serve/mutation cell stays valid; a forced cap growth
    clears the in-memory cell cache (the documented recompile path).
    Returns the compaction stats."""
    from mpi_knn_tpu.serve.engine import mutation_lock, writer_lock

    _require_mutable(index)
    if index.backend == "serial":
        raise ValueError(
            "the serial tile stack has no re-cluster pass (tombstoned "
            "slots are reclaimed in place by upserts); rebuild the index "
            "to re-derive headroom"
        )
    cfg = config or index.cfg
    reg = obs_metrics.get_registry()
    t0 = time.perf_counter()
    with obs_spans.span("compact", cat="mutate", backend=index.backend,
                        reason=reason, retrain=retrain):
        maybe_beat("compact-plan")
        # Phase 1, OFF the mutation lock where possible: the sample
        # gather must hold it (resident arrays are donated away by
        # concurrent mutations — an unlocked read could touch a deleted
        # buffer), but it is one ≤16k-row device gather; the k-means
        # retrain then runs on the host-copied SNAPSHOT with queries
        # flowing freely. Mutations landing between sample and scatter
        # are fine: the assignment below re-reads the store under the
        # lock, and sample-fit centroids are approximate by design.
        if retrain:
            with mutation_lock(index):
                from mpi_knn_tpu.ivf.mutate import gather_live_sample

                sample = gather_live_sample(index)
            from mpi_knn_tpu.ivf.mutate import retrain_centroids

            centroids, centroid_sqs = retrain_centroids(index, cfg, sample)
        else:
            centroids, centroid_sqs = index.centroids, index.centroid_sqs
        # the common (cap-preserving) compact executable is fetched —
        # possibly compiled — BEFORE the lock: a cold compile inside it
        # would stall every query dispatch for the XLA wall time
        get_mutation_executable(
            index, cfg, index.bucket_cap, KIND_COMPACT
        )
        # Phase 2, under the lock: assignment against the FINAL store,
        # layout, one donated scatter, atomic swap — all O(store) device
        # work at memory speed, no training, no compiles on the common
        # path (cap growth compiles in-lock: rare, documented)
        # (the writers' lock first: a write that has planned against the
        # old store must not dispatch into the new one)
        with writer_lock(index), mutation_lock(index):
            dst_part, dst_slot, new_cap, stats = plan_compact(
                index, cfg, centroids, centroid_sqs, min_cap=min_cap
            )
            stats["retrained"] = bool(retrain)
            maybe_beat("compact-scatter")
            bucket = new_cap
            dst = make_dst_store(
                index, new_cap, sharding=_bucket_sharding(index)
            )
            if new_cap == index.bucket_cap:
                ex = get_mutation_executable(
                    index, cfg, bucket, KIND_COMPACT
                )
                out = ex(
                    *_put_chunk(index, dst_part, dst_slot),
                    *_store_args(index), *dst,
                )
            else:
                # cap growth: a fresh shape — compile-and-go (rare, the
                # documented path; the in-memory cells of the OLD shape
                # are dropped below)
                out = compact_scatter_jit(
                    *_put_chunk(index, dst_part, dst_slot),
                    *_store_args(index), *dst,
                )
            new_store = _normalize_store_out(index, out)
            _swap_store(index, *new_store)
            index.centroids = centroids
            index.centroid_sqs = centroid_sqs
            cap_changed = new_cap != index.bucket_cap
            index.bucket_cap = new_cap
            if cap_changed:
                index._cache.clear()
                index.__dict__.pop("_cache_key_locks", None)
            index.__dict__.pop("_freelist", None)  # re-derive from store
            maybe_beat("compact-swap")
        _stamp_gauges(reg, freelist_of(index).stats())
    wall = time.perf_counter() - t0
    reg.counter(
        "compactions_total", help="background/manual compaction passes run"
    ).inc()
    reg.histogram(
        "compact_wall_seconds", help="wall time of one compaction pass"
    ).observe(wall)
    return {**stats, "reason": reason, "wall_s": round(wall, 4)}


class Compactor:
    """The background re-cluster/compact worker: a supervised daemon
    thread watching the freelist triggers, heartbeat- and flight-
    recorded, shed FIRST under overload (a session off its full ladder
    rung defers compaction — queries keep the device).

    ``session`` is a :class:`~mpi_knn_tpu.serve.engine.ServeSession`
    (the compactor reads its rung and index); ``interval_s`` is the
    trigger poll period. ``stop()`` joins the thread."""

    def __init__(self, session, interval_s: float = 0.25,
                 retrain: bool = True):
        _require_mutable(session.index)
        if session.index.backend == "serial":
            raise ValueError(
                "the serial layout has no compaction pass — the "
                "compactor supervises clustered indices only"
            )
        self.session = session
        self.interval_s = interval_s
        self.retrain = retrain
        self._stop_evt = threading.Event()
        self._lock = threading.Lock()
        self._history: list[dict] = []  # compaction stats, in order
        self._deferred = 0
        self._thread = threading.Thread(
            target=self._run, name="tknn-compact", daemon=True
        )

    def start(self) -> "Compactor":
        self._thread.start()
        return self

    def stop(self, timeout: float = 60.0) -> None:
        self._stop_evt.set()
        self._thread.join(timeout)

    def snapshot(self) -> dict:
        """{compactions, deferred, last} — consistent copy for other
        threads (/healthz, tests)."""
        with self._lock:
            return {
                "compactions": len(self._history),
                "deferred": self._deferred,
                "last": self._history[-1] if self._history else None,
            }

    def tick(self, force_reason: str | None = None) -> dict | None:
        """One trigger check + (maybe) one compaction — the loop body,
        exposed so tests drive it deterministically. Returns the
        compaction stats when one ran, else None."""
        ses = self.session
        reason = force_reason or should_compact(ses.index, ses.cfg)
        if reason is None:
            return None
        from mpi_knn_tpu.resilience.ladder import FULL_RUNG

        if ses.rung != FULL_RUNG:
            # compaction is the FIRST thing shed under overload: a
            # degraded session is already fighting for the device —
            # deferring costs headroom, not correctness
            with self._lock:
                self._deferred += 1
            obs_metrics.get_registry().counter(
                "compact_deferred_total",
                help="compaction ticks deferred because the session was "
                "shedding load (compaction is shed first)",
            ).inc()
            obs_spans.event("compact-deferred", cat="mutate", reason=reason,
                            rung=ses.rung)
            return None
        stats = compact_index(
            ses.index, ses.cfg, retrain=self.retrain, reason=reason
        )
        with self._lock:
            self._history.append(stats)
        return stats

    def _run(self) -> None:
        maybe_beat("compactor-start")
        while not self._stop_evt.wait(self.interval_s):
            maybe_beat("compactor-tick")
            try:
                self.tick()
            except Exception as e:  # noqa: BLE001 — log, keep supervising
                obs_spans.event(
                    "compact-error", cat="mutate",
                    error=f"{type(e).__name__}: {e}",
                )
                obs_metrics.get_registry().counter(
                    "compact_errors_total",
                    help="compaction passes that raised (the compactor "
                    "keeps running; the store is untouched on failure)",
                ).inc()
