"""Clustered (IVF) corpus index: k-means partitions, padded per-cluster
buckets, recall-targeted probe count.

Layout (all device-resident after build):

- ``centroids (P, d)`` f32 + their squared norms — the routing table;
- ``buckets (P, bucket_cap, d)`` — every partition's rows, padded to one
  static ``bucket_cap`` (max cluster size, lane-aligned) so the probe
  gather is shape-static; padding slots carry id −1 and the standard
  ``mask_tile`` semantics make them +inf candidates, never answers;
- ``bucket_ids (P, bucket_cap)`` int32 global ids;
- ``bucket_sqs (P, bucket_cap)`` squared norms, computed UNDER JIT from
  the at-rest buckets (the serve-index precedent: eager reductions
  produce different bits than traced ones, and the degenerate
  nprobe == partitions scan is parity-tested against the serial backend).

Centring: the rows rest as ``corpus - mu``, ``mu`` the corpus mean — or,
where every element of the corpus is a whole number, the mean ROUNDED to
whole numbers (``ops/distance.py center_corpus``'s rule: L2 is invariant
to the translation, and whole rows stay whole). A float32 store whose
every element is then a bfloat16 number (whole numbers up to 256 in
magnitude are) carries that fact as ``IVFIndex.onepass``, and the
bucket-major walk ranks such a store's lists against such query rows in
one bf16 pass (``ivf/search.py``). What the rounding took off the mean
rides with the fact (``IVFIndex.mean_frac``): the walk's finish takes it
off the survivors and the query rows again, so every returned distance is
computed from the operands it was computed from before the mean was
rounded.

``dtype="bfloat16"`` stores buckets compressed at rest (half the HBM and
half the probe-gather bytes; candidates upcast to f32 after the gather) —
the same measured-recall contract as the compressed serve index.
``dtype="int8"``/``"int4"`` go further down the ladder (ops/quant.py):
buckets reside as block-scaled codes (int4 nibble-packed into int8
lanes) plus a per-row f32 scale table — 4–8× less HBM and probe-gather
traffic than f32 — and the search dequantizes candidates right after the
probe gather into an asymmetric distance (exact f32 queries vs
dequantized candidates; ``bucket_sqs`` holds the DEQUANTIZED store's
norms, so distances are exact w.r.t. the stored values). The recall each
level pays is measured, never assumed: the bench compression axis and
DESIGN.md's ladder table carry the numbers, and the int4 gate's bar is
the honestly measured one.

``nprobe`` auto-tuning: when the build config leaves ``nprobe=None``, a
held-out corpus sample is searched at doubling nprobe values and compared
against the brute-force oracle (``nprobe == partitions`` — the exact full
scan through the same program, so the measured number is pure partition-
pruning loss, no cross-program fp noise); the smallest nprobe reaching
``cfg.recall_target`` becomes the index default.

``save``/``load`` round-trip the whole index through one ``.npz``
bit-identically (bf16 buckets travel as uint16 views — numpy has no
native bfloat16).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.backends.serial import TileCounts
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ivf.kmeans import (
    SPLIT_AT,
    assign_rows,
    column_sums,
    kmeans,
    sample_rows,
)
from mpi_knn_tpu.ivf.search import (
    PROBE_FIELDS,
    bucket_major_engages,
    ivf_query_shapes,
    ivf_serve_chunk,
    search_ivf,
)
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.ops.distance import bf16_exact, sq_norms
from mpi_knn_tpu.ops.quant import (
    QUANT_DTYPES,
    dequantize_rows,
    quantize_rows,
    row_wire_bytes,
)
from mpi_knn_tpu.parallel.partition import pad_to_multiple
from mpi_knn_tpu.serve.index import BatchLayout, onepass_holds

# held-out sample size for recall-targeted nprobe tuning (the CLI/bench
# recall-gate convention: enough rows for a stable estimate, cheap enough
# to run at build time)
TUNE_SAMPLE = 256
# at-rest bucket-store dtypes: the float pair stores rows verbatim (bf16
# halves bytes); int8/int4 are the block-scaled quantized levels — codes
# (int4 nibble-packed into int8 lanes) + a per-row f32 scale table, 4–8×
# less resident HBM, dequantized after the probe gather into an
# asymmetric distance (exact f32 queries vs dequantized candidates).
# int8 costs ~1 recall@10 point on the SIFT-shaped gate; int4 is the
# capacity rung with an explicitly measured (larger) cost — the bench's
# compression axis and DESIGN.md's ladder table carry the numbers.
IVF_DTYPES = ("float32", "bfloat16") + QUANT_DTYPES


class IVFLayout(BatchLayout):
    """The batch program of a clustered store: centroid table + padded
    buckets resident, queries and scratch as (qt, q_tile, ·) stacks in
    float32 whatever the store's at-rest width (bf16-rounding the queries
    would change the math against the one-shot ``search_ivf``)."""

    static_argnames = ("cfg", "nprobe")
    donate_argnums = (2, 3, 4)  # the carry pair and the probe counts' zeros
    tiled = True
    onepass_gauge = "ivf_index_onepass"

    def serve_fn(self):
        return ivf_serve_chunk

    def counts_scratch(self, index):
        """The third donated scratch: where the batch program writes what
        it counted (the sharded layout's is its exchange stats)."""
        return jax.ShapeDtypeStruct((PROBE_FIELDS,), jnp.int32)

    def query_side(self, index, cfg, q_pad, q_tile):
        return super().query_side(index, cfg, q_pad, q_tile) + [
            self.counts_scratch(index)]

    def carry_maker(self, index, cfg, q_pad, q_tile):
        carry = super().carry_maker(index, cfg, q_pad, q_tile)
        return lambda: (*carry(), jnp.zeros(PROBE_FIELDS, jnp.int32))

    def bucket_shapes(self, index, cfg, bucket):
        q_tile, q_pad = ivf_query_shapes(
            cfg, cfg.nprobe, index.bucket_cap, index.dim, bucket
        )
        return q_pad, q_tile

    def resident(self, index):
        return (index.centroids, index.centroid_sqs, index.buckets,
                index.bucket_ids, index.bucket_sqs, index.bucket_scales,
                index.onepass, index.mean_frac)

    def statics(self, index, cfg, bucket):
        # concrete: compatible_cfg resolves None to the tuned default
        return dict(cfg=cfg, nprobe=cfg.nprobe)

    def query_dtype(self, cfg):
        return jnp.dtype("float32")

    carry_dtype = query_dtype

    def batch_counts(self, index, q_pad, q_tile, rest):
        # third output of the batch program: what it probed
        return TileCounts(ivf_probe=rest[0])

    def stamp_gauges(self, index, cfg, registry):
        registry.gauge(
            "ivf_at_rest_bytes",
            help="resident bytes of the clustered bucket store "
            "(codes + scales for quantized stores)",
        ).set(index.nbytes_resident)
        registry.gauge(
            "ivf_bucket_cap",
            help="slots of one partition's padded bucket: the largest "
            "partition at the build, plus headroom",
        ).set(index.bucket_cap)
        registry.gauge(
            "ivf_bucket_fill_pct",
            help="rows of the build over the slots of the bucket store "
            "(partitions x bucket_cap), in percent: what padding every "
            "bucket to the largest leaves empty",
        ).set(100.0 * index.m / (index.partitions * index.bucket_cap))
        registry.gauge(
            self.onepass_gauge,
            help="1 while every element of the clustered store is a bf16 "
            "number, so the bucket-major walk ranks batches of such query "
            "rows in one bf16 pass (serve_index_onepass is the dense "
            "index's); else 0",
        ).set(float(onepass_holds(index)))
        registry.gauge(
            "serve_index_nprobe",
            help="partitions a query row probes in the batch program "
            "built last (a degraded rung's is smaller)",
        ).set(cfg.nprobe)


@dataclasses.dataclass
class IVFIndex:
    """Resident clustered-index state for one (corpus, config) pair.

    Duck-types the corner of ``serve.CorpusIndex`` the serving engine
    touches (``layout``/``backend``/``cfg``/``mu``/``m``/``dim``/``_cache``/
    ``compatible_cfg``/``nbytes_resident``), so the bucketed AOT
    executable cache, ``ServeSession`` and ``api.query_knn`` serve it
    unchanged.
    """

    cfg: KNNConfig  # resolved: backend="serial", concrete nprobe
    m: int
    dim: int
    partitions: int
    bucket_cap: int
    nprobe: int  # index default (tuned or configured)
    mu: object | None  # centering mean (host f64), or None
    centroids: jax.Array  # (P, d) f32
    centroid_sqs: jax.Array  # (P,)
    buckets: jax.Array  # (P, cap, d) at-rest dtype — (P, cap, pd) int8
    # code lanes when the store is quantized (pd = packed_dim)
    bucket_ids: jax.Array  # (P, cap) int32
    bucket_sqs: jax.Array  # (P, cap) f32 (norms of the DEQUANTIZED store
    # when quantized — distances are exact w.r.t. the stored values)
    bucket_scales: jax.Array | None = None  # (P, cap) f32, quantized only
    tuned_recall: float | None = None  # measured recall@k at `nprobe`
    # the store's side of the one-pass rule (:func:`store_onepass`): a
    # bool scalar on the device, TRUE when the index was built — every
    # element of the float32 store a bf16 number — and handed to every
    # batch program, whose walk then holds both dots; an upsert of a row
    # that is not turns it false in place, with no recompile. None (the
    # store did not qualify at the build): the program has no branch.
    onepass: jax.Array | None = None
    # (d,) float32 on the device, beside a fact alone: the corpus mean
    # less ``mu``, what rounding the mean to whole numbers took off. The
    # walk's finish subtracts it from the survivors and the query rows
    # (L2 does not see a common translation), so the returned distances
    # come from the operands the unrounded mean left — the numbers, and
    # the rounding, they had before the rule. None: ``mu`` is the mean.
    mean_frac: jax.Array | None = None
    backend: str = "ivf"
    layout = IVFLayout()  # one for the kind: a class attribute, no field
    # per-index executable cache: {(bucket, cfg) -> engine._BucketExec}
    _cache: dict = dataclasses.field(default_factory=dict)

    @property
    def store_dtype(self) -> str:
        """The at-rest level of the bucket store (cfg.dtype by the build
        contract)."""
        return self.cfg.dtype

    @property
    def live_rows(self) -> int:
        """Rows currently live (non-tombstoned). ``m`` stays the
        BUILD-time row count — it is executable-fingerprint material;
        the dynamic truth lives on the mutation freelist."""
        from mpi_knn_tpu.ivf.mutate import freelist_of

        return freelist_of(self).live

    @property
    def nbytes_resident(self) -> int:
        """Bytes of resident corpus payload (the bucket store: code/row
        array plus the scale table of a quantized store)."""
        n = self.buckets.size * self.buckets.dtype.itemsize
        if self.bucket_scales is not None:
            n += self.bucket_scales.size * self.bucket_scales.dtype.itemsize
        return n

    @property
    def probe_bytes(self) -> int:
        """Bytes one query row's probe gather touches at the index-default
        nprobe — the sublinear bound (≤ nprobe·bucket_bytes, never the
        corpus) that lint rule R2 budgets on the lowered program. Priced
        at the AT-REST width: a quantized store's gather moves code lanes
        plus per-row scales, which is exactly the 4–8× cut."""
        return self.nprobe * self.bucket_cap * row_wire_bytes(
            self.dim,
            self.store_dtype if self.store_dtype in QUANT_DTYPES else None,
            self.buckets.dtype.itemsize,
        )

    def compatible_cfg(self, cfg: KNNConfig) -> KNNConfig:
        """Validate a per-query config against the build-time clustering.

        Query-side knobs (k, nprobe, precision policy, tiling, serving
        pacing, donation) may vary per call — the executable cache keys
        on the full config. Corpus-side knobs (metric, dtype, partitions,
        the k-means training knobs, centering, zero-exclusion) are baked
        into the trained partitions and may NOT vary. A ``nprobe=None``
        query config resolves to the index's tuned default.
        """
        frozen = (
            "backend", "metric", "dtype", "partitions", "kmeans_iters",
            "kmeans_init", "ivf_seed", "kmeans_sample", "center",
            "exclude_zero", "zero_eps", "bucket_headroom", "bucket_cap",
        )
        want = cfg if cfg.backend != "auto" else cfg.replace(backend="serial")
        bad = [
            f for f in frozen
            if getattr(want, f) != getattr(self.cfg, f)
        ]
        if bad:
            raise ValueError(
                "query config changes corpus-side knobs baked into this "
                f"clustered index: {bad}; build a new index (or override "
                "only query-side knobs: k/nprobe/precision_policy/"
                "query_tile/query_bucket/dispatch_depth/donate)"
            )
        _refuse_inert_knobs(want)
        if want.nprobe is None:
            want = want.replace(nprobe=self.nprobe)
        return want


def store_onepass(cfg: KNNConfig, buckets, bucket_scales):
    """The store's side of the one-pass rule, read ONCE from the filled
    store: a TRUE bool scalar on the device where the bucket-major walk
    can take the store (``ivf/search.py bucket_major_engages``'s form:
    float32 rows, no scale table, the bucket a block where it rests) and
    every element of it is a bf16 number (``ops.distance.bf16_exact``, by
    bit pattern, in one fused pass: no store-sized temporary; padding
    slots are zeros and qualify), else None — also where the kernel that
    holds both dots, with its bfloat16 copies of a bucket and a group,
    would pass the walk's share of VMEM that the plain one fits.
    ``precision_policy`` is the query's to vary and is not asked."""
    partitions, cap, dim = buckets.shape
    if bucket_scales is not None or not bucket_major_engages(
            1, 1, partitions, cap, dim, cfg.dtype, onepass=True):
        return None
    if not bool(jax.jit(bf16_exact)(buckets)):
        return None
    return jax.device_put(np.bool_(True))


def _refuse_inert_knobs(cfg: KNNConfig) -> None:
    """Knobs the clustered search cannot honor are refused LOUDLY, never
    silently ignored (the serve-CLI/bench convention): the probed
    candidates always finish with the exact rerank top-k, and the
    centroid-score/rerank dots fix their own precisions — a config (or a
    banked measurement's metadata) claiming otherwise would be lying
    about the program that ran."""
    if cfg.topk_method != "exact":
        raise ValueError(
            f"topk_method={cfg.topk_method!r} cannot be honored by the "
            "clustered (IVF) search: the probed-candidate finish is "
            "always the exact rerank top-k (ops/rerank.rerank_exact_topk)"
            " — unset it, or use a dense backend for approximate "
            "selection"
        )
    if cfg.matmul_precision is not None:
        raise ValueError(
            f"matmul_precision={cfg.matmul_precision!r} cannot be "
            "honored by the clustered (IVF) search: it fixes its own dot "
            "precisions (HIGHEST centroid score + rerank; DEFAULT "
            "compress under precision_policy='mixed')"
        )
    if cfg.merge_schedule != "twolevel":
        raise ValueError(
            f"merge_schedule={cfg.merge_schedule!r} cannot be honored by "
            "the clustered (IVF) search: there is no tile-merge schedule "
            "on the probed path (one gather, one exact finish) — leave "
            "it at the default"
        )


def _corpus_from_serve_index(serve_index):
    """Centered corpus rows + mean back out of a serial-layout
    ``serve.CorpusIndex`` (the tile stack is the corpus, padded — strip
    the sentinel rows, and the zero columns of a stack that rests wider
    than its rows: ``serve/index.py rest_width``)."""
    if serve_index.tiles is None:
        raise ValueError(
            "an IVF index can only be built from a serial-layout "
            "CorpusIndex (tiles resident on one device); the "
            f"{serve_index.backend!r} layout shards or fuses the corpus"
        )
    tiles = serve_index.tiles
    rows = np.asarray(tiles, dtype=np.float32).reshape(
        -1, tiles.shape[-1]
    )[: serve_index.m, : serve_index.dim]
    return rows, serve_index.mu, serve_index.cfg


def build_ivf_index(
    corpus,
    config: Optional[KNNConfig] = None,
    **overrides,
) -> IVFIndex:
    """Train the k-means partitioner and build a device-resident
    :class:`IVFIndex`.

    Args:
      corpus: (m, d) host/device array, or an existing serial-layout
        ``serve.CorpusIndex`` (its centered resident tiles are reused;
        no second centering pass).
      config: build-time :class:`KNNConfig` with ``partitions`` set;
        kwargs override fields. ``nprobe=None`` triggers the
        recall-targeted auto-tune.
    """
    from mpi_knn_tpu.serve.index import CorpusIndex

    if overrides.pop("tags", None) is not None or getattr(
            corpus, "tags", None) is not None:
        raise ValueError(
            "a clustered (ivf) index takes no tags: its probes know no "
            "predicate, and a filter that thins a probed bucket loses "
            "recall silently — serve a tagged corpus from the dense serial "
            "layout (build_index(..., tags=))")
    cfg = (config or KNNConfig()).replace(**overrides)
    if cfg.ivf_shards is not None:
        # the sharded-clustered axis: train here (single-device math —
        # clustering is layout-independent), then distribute over the
        # ring mesh (ivf/sharded.py derives the layout)
        from mpi_knn_tpu.ivf.sharded import build_sharded_ivf_index

        return build_sharded_ivf_index(corpus, cfg)
    if cfg.partitions is None:
        raise ValueError(
            "building a clustered index requires partitions "
            "(KNNConfig.partitions / --partitions)"
        )
    if cfg.backend not in ("auto", "serial"):
        raise ValueError(
            f"the clustered index is a single-device serial-math path; "
            f"backend={cfg.backend!r} cannot honor it (the ring rotation "
            "scans the full corpus by construction) "
            "— use backend='serial' or 'auto'"
        )
    if cfg.dtype not in IVF_DTYPES:
        raise ValueError(
            f"clustered index dtype must be one of {IVF_DTYPES} (float64 "
            f"is the dense backends' debug mode), got {cfg.dtype!r}"
        )
    _refuse_inert_knobs(cfg)
    cfg = cfg.replace(backend="serial")

    m, dim = (corpus.m, corpus.dim) if isinstance(corpus, CorpusIndex) \
        else np.shape(corpus)
    if cfg.partitions > m:
        raise ValueError(
            f"partitions={cfg.partitions} exceeds the corpus rows ({m})"
        )
    with obs_spans.span("index-build", cat="index", backend="ivf",
                        rows=int(m), dim=int(dim), metric=cfg.metric,
                        partitions=cfg.partitions):
        store = _store_on_device if isinstance(corpus, jax.Array) \
            else _store_on_host
        mu, frac, centroids, buckets_f32, bucket_ids, cap = store(corpus, cfg)
        return _finish_index(cfg, m, dim, cap, mu, centroids, buckets_f32,
                             bucket_ids, frac)


def _bucket_cap(counts: np.ndarray, cfg: KNNConfig) -> int:
    """The one static bucket height: the height the configuration states
    (``bucket_cap``), else the largest partition with headroom — which
    also decides where a partition outgrew the stated height.

    Capacity headroom (ISSUE 14): spare slots per bucket are what buy
    STATIC-SHAPE upserts — the freelist hands them out and a donated
    scatter fills them in place, no recompile. The padding slots carry
    id −1 (mask_tile: +inf candidates, never answers), so headroom
    costs padded FLOPs/gather bytes, not correctness — set
    bucket_headroom=0.0 for a frozen corpus."""
    need = max(int(counts.max()), 1)
    return pad_to_multiple(
        max(1, int(np.ceil(need * (1.0 + cfg.bucket_headroom))),
            cfg.bucket_cap or 0), 8
    )


def _fill_span(m: int, partitions: int, cap: int, dim: int):
    """The span around a store's fill: what it writes and how full."""
    return obs_spans.span(
        "ivf-fill", cat="index", rows=int(m),
        bytes=partitions * cap * (dim * 4 + 8), bucket_cap=cap,
        fill_pct=int(round(100.0 * m / (partitions * cap))))


def _train(rows, cfg: KNNConfig, m: int):
    """The partitioner over ``rows`` (centred: the training sample, or
    all ``m`` rows), inside its span. A stated ``bucket_cap`` is honoured
    here: the rounds split what stands over ``SPLIT_AT`` of it."""
    balance = None
    if cfg.bucket_cap is not None:
        if cfg.bucket_cap * cfg.partitions < m:
            raise ValueError(
                f"bucket_cap={cfg.bucket_cap} x partitions="
                f"{cfg.partitions} slots cannot hold the corpus rows ({m})"
            )
        balance = SPLIT_AT * cfg.bucket_cap * cfg.partitions / m
    with obs_spans.span("ivf-train", cat="index", rows=int(rows.shape[0]),
                        partitions=cfg.partitions, iters=cfg.kmeans_iters):
        res = kmeans(
            rows, cfg.partitions, iters=cfg.kmeans_iters, seed=cfg.ivf_seed,
            init=cfg.kmeans_init, balance=balance,
        )
        jax.block_until_ready(res.centroids)
    return res


def _store_on_host(corpus, cfg: KNNConfig):
    """The store of a host corpus (or of a serial ``CorpusIndex``'s centred
    tiles): centred by the float64 mean on the host (rounded to whole
    numbers where the corpus holds nothing else), trained and assigned
    on the device, filled by one numpy scatter and copied up. Returns
    ``(mu, frac, centroids, (P, cap, d) float32 buckets, (P, cap) ids,
    cap)``; ``frac`` is what the rounding took off the mean
    (:func:`_whole_mean`), None where nothing was rounded."""
    from mpi_knn_tpu.serve.index import CorpusIndex

    mu = frac = None
    if isinstance(corpus, CorpusIndex):
        rows, mu, built_cfg = _corpus_from_serve_index(corpus)
        for f in ("metric", "dtype", "center"):
            if getattr(built_cfg, f) != getattr(cfg, f):
                raise ValueError(
                    f"IVF config {f}={getattr(cfg, f)!r} disagrees with "
                    f"the source CorpusIndex ({getattr(built_cfg, f)!r})"
                )
        X = rows  # already centered at serve-index build time
    else:
        X = np.asarray(corpus, dtype=np.float32)
        if cfg.center:
            mu = X.astype(np.float64).mean(axis=0)
            if all((blk == np.rint(blk)).all() for blk in (
                    X[i:i + WHOLE_BLOCK]
                    for i in range(0, X.shape[0], WHOLE_BLOCK))):
                mu, frac = _whole_mean(mu)
            X = X - mu
    m, dim = X.shape
    P = cfg.partitions
    picked = sample_rows(m, cfg.kmeans_sample, cfg.ivf_seed)
    if picked is None:
        res = _train(X, cfg, m)
        assign, counts = res.assignments, res.counts
    else:
        res = _train(X[picked], cfg, m)
        with obs_spans.span("ivf-assign", cat="index", rows=int(m)):
            assign, counts = assign_rows(
                jnp.asarray(X, dtype=jnp.float32),
                jnp.zeros(dim, jnp.float32), res.centroids)
    assign, counts = np.asarray(assign), np.asarray(counts)
    cap = _bucket_cap(counts, cfg)
    with _fill_span(m, P, cap, dim):
        buckets_np = np.zeros((P, cap, dim), dtype=np.float32)
        ids_np = np.full((P, cap), -1, dtype=np.int32)
        # vectorized scatter: rows sorted by cluster, each row's slot is
        # its rank within its cluster (searchsorted finds the cluster's
        # start) — a per-row Python loop here would make SIFT-scale
        # builds interpreter-bound
        order = np.argsort(assign, kind="stable")
        sa = assign[order]
        within = np.arange(m) - np.searchsorted(sa, sa)
        buckets_np[sa, within] = X[order]
        ids_np[sa, within] = order
        buckets, ids = jnp.asarray(buckets_np), jnp.asarray(ids_np)
    return mu, frac, res.centroids, buckets, ids, cap


# rows the host path tests for whole numbers at a time (no corpus-sized
# temporary there either)
WHOLE_BLOCK = 1 << 16


def _whole_mean(mean):
    """``(mu, frac)`` of a whole-number corpus from its float64 ``mean``:
    the float32 nearest it — the number both paths hold — rounded to whole
    numbers, and what the rounding took off, ``mean - mu``, each element
    half a unit at most and exact in float32."""
    mean = np.asarray(mean).astype(np.float32).astype(np.float64)
    mu = np.rint(mean)
    return mu, mean - mu


# elements of the rows one step of the device fill gathers (32 MiB)
FILL_ELEMS = 1 << 23


def _store_on_device(corpus: jax.Array, cfg: KNNConfig):
    """The store of a device corpus, without a host round trip and
    without a second corpus-sized array beside the caller's and the
    store: the mean from per-block sums (float64 only over the few KB
    that cross the host), the partitioner trained on the seeded sample
    (``kmeans_sample``; with every row it trains on one centred copy, as
    the host path does), every row assigned block by block with the
    centring inside the block, and the store filled a few partitions a
    step by a gather of their rows (:func:`_fill_store`). The mean is the
    float32 nearest the float64 one, so that a batch centred in float64 on
    the host and the rows centred in float32 here are the same numbers —
    or, where every block of the corpus holds whole numbers alone
    (``column_sums`` tests each beside its sum), the mean rounded to whole
    numbers, as the host path rounds it.
    Returns what :func:`_store_on_host` returns."""
    m, dim = corpus.shape
    P = cfg.partitions
    mu = frac = None
    mu_dev = jnp.zeros(dim, jnp.float32)
    if cfg.center:
        sums, whole = column_sums(corpus, whole=True)
        sums = np.asarray(sums, dtype=np.float64)
        mu = (sums.sum(axis=0) / m).astype(np.float32).astype(np.float64)
        if bool(np.asarray(whole).all()):
            mu, frac = _whole_mean(mu)
        mu_dev = jnp.asarray(mu, dtype=jnp.float32)
    picked = sample_rows(m, cfg.kmeans_sample, cfg.ivf_seed)
    train = corpus if picked is None else corpus[jnp.asarray(picked)]
    res = _train(train.astype(jnp.float32) - mu_dev, cfg, m)
    del train
    if picked is None:
        assign, counts = res.assignments, res.counts
    else:
        with obs_spans.span("ivf-assign", cat="index", rows=int(m)):
            assign, counts = assign_rows(corpus, mu_dev, res.centroids)
            jax.block_until_ready(assign)
    cap = _bucket_cap(np.asarray(counts), cfg)
    with _fill_span(m, P, cap, dim):
        # the rows in partition order, ascending id inside a partition
        # (the host fill's order). Sorted on the host: 4 B a row each way,
        # where the v5e compiler takes half a minute over a device sort
        # of this length; 16-bit keys take numpy's radix sort
        keys = np.asarray(assign)
        if P <= 1 << 16:
            keys = keys.astype(np.uint16)
        order = jnp.asarray(
            np.argsort(keys, kind="stable").astype(np.int32))
        buckets, ids = _fill_store(
            corpus, mu_dev, order, counts, cap=cap,
            step=_fill_step(P, cap * dim))
        jax.block_until_ready(buckets)
    return mu, frac, res.centroids, buckets, ids, cap


def _fill_step(partitions: int, slot_elems: int) -> int:
    """Partitions one step of the device fill writes: the largest divisor
    of ``partitions`` whose buckets stay within ``FILL_ELEMS``."""
    want = max(1, min(partitions, FILL_ELEMS // max(slot_elems, 1)))
    return next(s for s in range(want, 0, -1) if partitions % s == 0)


@functools.partial(jax.jit, static_argnames=("cap", "step"))
def _fill_store(corpus, mu, order, counts, cap: int, step: int):
    """((P, cap, d) float32 buckets of ``corpus - mu``, (P, cap) int32
    ids, -1 where a slot is empty) from ``order``, the row numbers in
    partition order, and the rows a partition: ``step`` partitions at a
    time — slot c of partition p is row ``order[start[p] + c]`` where
    c < counts[p] — gathered from the corpus where it lies and written
    into the store in place. No scatter, no sorted copy of the corpus:
    the temporaries are one step's rows."""
    m, dim = corpus.shape
    P = counts.shape[0]
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    slot = jnp.arange(cap, dtype=jnp.int32)

    def one_step(s, carry):
        buckets, ids = carry
        p = s * step + jnp.arange(step, dtype=jnp.int32)
        live = slot[None, :] < counts[p][:, None]  # (step, cap)
        at = jnp.minimum(starts[p][:, None] + slot[None, :], m - 1)
        src = jnp.where(live, order[at], 0)
        rows = jnp.where(
            live[:, :, None], corpus[src].astype(jnp.float32) - mu, 0.0)
        return (
            jax.lax.dynamic_update_slice_in_dim(
                buckets, rows, s * step, axis=0),
            jax.lax.dynamic_update_slice_in_dim(
                ids, jnp.where(live, src, -1), s * step, axis=0),
        )

    return jax.lax.fori_loop(
        0, P // step, one_step,
        (jnp.zeros((P, cap, dim), jnp.float32),
         jnp.full((P, cap), -1, jnp.int32)))


def _finish_index(cfg, m, dim, cap, mu, centroids, buckets_f32,
                  bucket_ids, frac=None) -> IVFIndex:
    """The index around a filled float32 store: the at-rest form, the
    norms, the one-pass fact (with ``frac``, what rounding the mean took
    off, beside it), the probe count."""
    P = cfg.partitions
    bucket_scales = None
    if cfg.dtype in QUANT_DTYPES:
        # block-scaled quantized store: per-row codes + scales (padding
        # rows are zero → scale 0, codes 0 — dequantization is exactly
        # zero and the id −1 mask keeps them non-answers anyway); norms
        # computed UNDER JIT from the DEQUANTIZED store so the asymmetric
        # distance is exact w.r.t. the values actually stored
        buckets, bucket_scales = jax.jit(
            functools.partial(quantize_rows, dtype=cfg.dtype)
        )(buckets_f32)
        bucket_sqs = jax.jit(
            lambda c, s: jax.vmap(sq_norms)(
                dequantize_rows(c, s, cfg.dtype, dim)
            )
        )(buckets, bucket_scales)
    else:
        buckets = buckets_f32.astype(jnp.dtype(cfg.dtype))
        # norms from the AT-REST buckets, under jit (bit-parity with the
        # serial serve index's norm construction)
        bucket_sqs = jax.jit(jax.vmap(sq_norms))(buckets)
    del buckets_f32
    centroid_sqs = jax.jit(sq_norms)(centroids)

    index = IVFIndex(
        cfg=cfg, m=m, dim=dim, partitions=P, bucket_cap=cap,
        nprobe=cfg.nprobe or P, mu=mu,
        centroids=centroids, centroid_sqs=centroid_sqs,
        buckets=buckets, bucket_ids=bucket_ids, bucket_sqs=bucket_sqs,
        bucket_scales=bucket_scales,
        onepass=store_onepass(cfg, buckets, bucket_scales),
    )
    if index.onepass is not None and frac is not None and frac.any():
        index.mean_frac = jnp.asarray(frac, dtype=jnp.float32)
    if cfg.nprobe is None:
        tuned, rec = tune_nprobe(index, cfg.recall_target, k=cfg.k)
        index.nprobe = tuned
        index.tuned_recall = rec
        index.cfg = cfg.replace(nprobe=tuned)
    else:
        index.cfg = cfg
    return index


def tune_nprobe(
    index: IVFIndex, recall_target: float, k: int = 10,
    sample: int = TUNE_SAMPLE,
) -> tuple[int, float]:
    """Smallest nprobe whose measured recall@k on a held-out corpus
    sample reaches ``recall_target`` against the brute-force oracle —
    which is the SAME search program at ``nprobe == partitions`` (an
    exact full scan), so the measurement isolates partition-pruning loss
    from every other fp effect. Returns (nprobe, measured_recall)."""
    from mpi_knn_tpu.utils.report import recall_at_k

    P = index.partitions
    ns = min(sample, index.m)
    rows = np.linspace(0, index.m - 1, num=ns, dtype=np.int64)
    # held-out queries are corpus rows WITH their identities, so
    # self-exclusion matches the all-pairs workload the gate mirrors;
    # they come back out of the bucket store (already centered). Only the
    # sampled rows are gathered ON DEVICE — fetching/decompressing the
    # whole store to host for ≤ TUNE_SAMPLE rows would move hundreds of
    # MB at the corpus scales the index targets.
    flat_ids = np.asarray(index.bucket_ids).reshape(-1)
    pos_of = np.full(index.m, -1, dtype=np.int64)
    valid = flat_ids >= 0
    pos_of[flat_ids[valid]] = np.flatnonzero(valid)
    sel = index.buckets.reshape(-1, index.buckets.shape[-1])[
        jnp.asarray(pos_of[rows])
    ]
    if index.bucket_scales is not None:
        # quantized store: the tuner's held-out queries are the
        # DEQUANTIZED rows — still "corpus rows in the centered frame",
        # and still isolating partition-pruning loss (both the probed
        # search and its nprobe=partitions oracle see the same store)
        sel = dequantize_rows(
            sel,
            index.bucket_scales.reshape(-1)[jnp.asarray(pos_of[rows])],
            index.store_dtype,
            index.dim,
        )
    Q = np.asarray(sel.astype(jnp.float32))
    qids = rows.astype(np.int32)

    base_cfg = index.cfg.replace(nprobe=P, k=k)
    _, want = search_ivf(
        index, Q, query_ids=qids, config=base_cfg, assume_centered=True
    )

    def recall_at(n: int) -> float:
        _, got = search_ivf(
            index, Q, query_ids=qids,
            config=index.cfg.replace(nprobe=n, k=k), assume_centered=True,
        )
        return float(recall_at_k(got, want))

    # doubling walk to bracket the target, then a binary refinement so
    # the result is the SMALLEST passing nprobe (the documented
    # contract), not the smallest passing power of two — a power-of-two
    # answer can probe up to ~2x the bytes the contract promises
    lo, hi, hi_rec = 0, P, 1.0
    n = 1
    while n < P:
        rec = recall_at(n)
        if rec >= recall_target:
            hi, hi_rec = n, rec
            break
        lo = n
        n = min(2 * n, P)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        rec = recall_at(mid)
        if rec >= recall_target:
            hi, hi_rec = mid, rec
        else:
            lo = mid
    return hi, hi_rec


def save_ivf_index(index, path: str) -> str:
    """Write the full index to one ``.npz`` (bit-identical round trip;
    bf16 buckets travel as uint16 views). A :class:`~mpi_knn_tpu.ivf.
    sharded.ShardedIVFIndex` saves through its single-device view — the
    shard layout is DERIVED, never stored, so one artifact reloads and
    serves on any shard count. Returns the path written."""
    if getattr(index, "backend", None) == "ivf-sharded":
        from mpi_knn_tpu.ivf.sharded import unshard_ivf_index

        index = unshard_ivf_index(index)
    if not path.endswith(".npz"):
        path += ".npz"
    buckets = np.asarray(index.buckets)
    bf16 = index.buckets.dtype == jnp.bfloat16
    if bf16:
        buckets = buckets.view(np.uint16)
    meta = {
        "cfg": {
            k: v for k, v in dataclasses.asdict(index.cfg).items()
        },
        "m": index.m,
        "dim": index.dim,
        "partitions": index.partitions,
        "bucket_cap": index.bucket_cap,
        "nprobe": index.nprobe,
        "tuned_recall": index.tuned_recall,
        "buckets_bf16": bf16,
        # the at-rest level by name (int8/int4 stores travel as their
        # int8 code lanes — bit-identical by construction); absent in
        # pre-quantization artifacts, defaulted on load
        "store_dtype": index.cfg.dtype,
        "has_mu": index.mu is not None,
        # live-mutation provenance (informational — the freelist itself
        # is DERIVED from bucket_ids on load, so tombstones and headroom
        # round-trip through the id plane; pre-mutation artifacts simply
        # lack this key and derive full headroom from their padding)
        "live_rows": int((np.asarray(index.bucket_ids) >= 0).sum()),
    }
    # write-to-temp + atomic rename: a re-save over a path another
    # process is serving from (or has mmapped mid-load) must never
    # expose a torn archive — the reader keeps the old inode, the new
    # file replaces it whole (the aotcache entry-write convention)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        np.savez(
            f,
            meta=np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8),
            centroids=np.asarray(index.centroids),
            centroid_sqs=np.asarray(index.centroid_sqs),
            buckets=buckets,
            bucket_ids=np.asarray(index.bucket_ids),
            bucket_sqs=np.asarray(index.bucket_sqs),
            bucket_scales=(np.asarray(index.bucket_scales)
                           if index.bucket_scales is not None
                           else np.zeros(0, np.float32)),
            mu=(np.asarray(index.mu)
                if index.mu is not None else np.zeros(0)),
            mean_frac=(np.asarray(index.mean_frac)
                       if index.mean_frac is not None
                       else np.zeros(0, np.float32)),
        )
    os.replace(tmp, path)
    return path


def load_ivf_index(path: str, mmap: bool = True) -> IVFIndex:
    """Reload a :func:`save_ivf_index` ``.npz`` — arrays land back on
    device bit-identically; the executable cache starts empty.

    ``mmap=True`` (the default) maps the archive's uncompressed members
    read-only instead of decompress-copying them into host memory
    (``utils/npz_mmap``): nothing reads until ``jax.device_put`` touches
    the pages, so disk read and H2D transfer fuse into one pass and the
    host never holds a second corpus copy — the cold-start zero-copy
    path (DESIGN.md "Cold start"), pipelining index load under the AOT
    warm pool's compiles. An archive the mapper cannot handle (a
    compressed ``savez_compressed`` file, foreign members) falls back to
    the copying ``np.load`` reader LOUDLY (``RuntimeWarning``), with
    bit-identical results either way."""
    z: dict | None = None
    if mmap:
        from mpi_knn_tpu.utils.npz_mmap import mmap_npz

        try:
            z = mmap_npz(path)
        except ValueError as e:
            import warnings

            warnings.warn(
                f"cannot mmap index {path!r} ({e}); falling back to the "
                "copying np.load reader",
                RuntimeWarning,
                stacklevel=2,
            )
    if z is None:
        with np.load(path) as zf:
            z = {k: zf[k] for k in zf.files}
    meta = json.loads(bytes(np.asarray(z["meta"])).decode())
    cfg = KNNConfig(**meta["cfg"])
    buckets = z["buckets"]
    if meta["buckets_bf16"]:
        import ml_dtypes  # jax dependency; numpy has no native bf16

        buckets = jnp.asarray(buckets.view(ml_dtypes.bfloat16))
    else:
        buckets = jnp.asarray(buckets)
    store = meta.get("store_dtype", cfg.dtype)
    scales = None
    if store in QUANT_DTYPES:
        scales = jnp.asarray(z["bucket_scales"]).reshape(
            meta["partitions"], meta["bucket_cap"]
        )
    onepass = store_onepass(cfg, buckets, scales)
    # (absent from an older archive, whose mean was not rounded)
    frac = z.get("mean_frac")
    return IVFIndex(
        cfg=cfg,
        m=meta["m"],
        dim=meta["dim"],
        partitions=meta["partitions"],
        bucket_cap=meta["bucket_cap"],
        nprobe=meta["nprobe"],
        tuned_recall=meta["tuned_recall"],
        # np.array (a COPY), never np.asarray: on the mmap path asarray
        # would return a view pinning the file mapping for the index's
        # whole lifetime — every other field is copied to device by
        # jnp.asarray, and the zero-copy contract is "the mapping is
        # dropped once load returns"
        mu=np.array(z["mu"]) if meta["has_mu"] else None,
        centroids=jnp.asarray(z["centroids"]),
        centroid_sqs=jnp.asarray(z["centroid_sqs"]),
        buckets=buckets,
        bucket_ids=jnp.asarray(z["bucket_ids"]),
        bucket_sqs=jnp.asarray(z["bucket_sqs"]),
        bucket_scales=scales,
        onepass=onepass,
        mean_frac=(jnp.asarray(np.array(frac)) if onepass is not None
                   and frac is not None and np.size(frac) else None),
    )
