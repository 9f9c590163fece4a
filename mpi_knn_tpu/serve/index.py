"""Device-resident corpus index — the amortized half of the serving loop.

The one-shot ``all_knn`` API re-uploads the corpus, re-derives its tiling,
re-computes its squared norms and re-traces the backend on every call —
fine for a batch job, fatal for the reference's actual workload ("classify
a stream of query points against a resident training corpus",
``knn-serial.c``). ``CorpusIndex`` does all corpus-side work exactly once:

- tiles + global ids + squared norms live on device, MXU-aligned, never
  bounced through the host again (the ``test_device_resident.py``
  contract, extended from "device inputs are not copied" to "the corpus
  is not even re-inspected");
- for the ring backends the padded corpus and its ids are ``device_put``
  sharded over the ring axis ONCE — every subsequent batch pays only its
  own query H2D;
- the centering mean is computed once and applied to each query batch, so
  serving results are bit-identical to a fresh ``all_knn`` call (which
  derives the same mean from the same corpus);
- bf16 compression is ``dtype="bfloat16"`` at build time: the resident
  tiles are stored (and computed) at half width, halving HBM residency —
  the same measured-recall contract as everywhere else in the framework.

The executable cache for the query side lives in ``serve.engine`` and is
keyed per (row bucket, config); the index carries it so two indices can
never collide on a cache entry.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.ops.distance import (
    center_corpus,
    onepass_applies,
    sq_norms,
)
from mpi_knn_tpu.ops.topk import start_lane_bin_import
from mpi_knn_tpu.parallel.partition import (
    make_global_ids,
    pad_rows_any,
    pad_to_multiple,
)


@dataclasses.dataclass
class CorpusIndex:
    """Resident corpus state for one (corpus, config[, mesh]) triple.

    ``backend`` is resolved (never "auto"); exactly one of the two storage
    layouts is populated: the tile stack (serial/pallas) or the sharded
    padded corpus (ring/ring-overlap).
    """

    cfg: KNNConfig  # resolved backend; the serving default config
    backend: str
    m: int
    dim: int
    c_tile: int
    mu: object | None  # centering mean (host f64 or device), or None
    # serial/pallas layout
    tiles: jax.Array | None = None  # (T, c_tile, d)
    tile_ids: jax.Array | None = None  # (T, c_tile)
    tile_sqs: jax.Array | None = None  # (T, c_tile)
    # the corpus side of the one-pass rule (backends.serial
    # masked_dist_tile): a bool scalar on the device, TRUE when the index
    # was built — every centred element a bf16 number — and handed to every
    # batch program, which then carries both branches; an upsert of a row
    # that is not turns it false in place, with no recompile. None (the
    # rule does not apply, or the corpus did not qualify at build): the
    # batch program has no branch and is the one it always was.
    onepass: jax.Array | None = None
    corpus_padded: jax.Array | None = None  # (c_pad, d) — pallas layout
    # ring layout
    mesh: Mesh | None = None
    ring_meta: tuple | None = None  # (q_axis, axis, dp, ring_n)
    corpus_sharded: jax.Array | None = None  # (c_pad, d) over P(axis) —
    # int8 CODES when cfg.ring_transfer_dtype == "int8" (the resident
    # corpus IS the wire representation: quantized once at build, so
    # serving batches pay zero re-quantization and resident HBM shrinks
    # with the wire bytes)
    corpus_ids_sharded: jax.Array | None = None
    corpus_scales_sharded: jax.Array | None = None  # (c_pad,) f32, int8 only
    # per-index executable cache: {(bucket, cfg) -> engine._BucketExec}
    _cache: dict = dataclasses.field(default_factory=dict)

    @property
    def nbytes_resident(self) -> int:
        """Bytes of resident corpus payload (tiles or sharded corpus)."""
        arr = self.tiles if self.tiles is not None else (
            self.corpus_padded
            if self.corpus_padded is not None
            else self.corpus_sharded
        )
        return 0 if arr is None else arr.size * arr.dtype.itemsize

    @property
    def live_rows(self) -> int:
        """Rows currently live (non-tombstoned) in a mutable (serial)
        layout — from the mutation freelist; ``m`` stays the build-time
        count (executable-fingerprint material)."""
        from mpi_knn_tpu.ivf.mutate import freelist_of

        if self.tiles is None:
            raise ValueError(
                f"the {self.backend!r} layout does not track liveness "
                "(only the serial tile stack is mutable)"
            )
        return freelist_of(self).live

    def compatible_cfg(self, cfg: KNNConfig) -> KNNConfig:
        """Validate a per-query config against the build-time layout.

        Query-side knobs (k, topk method/block, merge schedule, precision
        policy, bucket/depth/donate, recall target, tie break) may vary per
        call — the executable cache keys on the full config, so each
        variant compiles its own executable. Corpus-side knobs are baked
        into the resident layout and may NOT vary; accepting them silently
        would serve answers from an index built under different math.
        """
        frozen = (
            "backend", "metric", "dtype", "corpus_tile", "query_tile",
            "center", "mesh_axis", "num_devices", "ring_transfer_dtype",
            "ring_schedule", "max_tile_elems", "pallas_variant",
            "exclude_zero", "zero_eps",
        )
        built = self.cfg.replace(backend=self.backend)
        want = cfg if cfg.backend != "auto" else cfg.replace(
            backend=self.backend
        )
        bad = [
            f for f in frozen
            if getattr(want, f) != getattr(built, f)
        ]
        if bad:
            raise ValueError(
                "query config changes corpus-side knobs baked into this "
                f"index: {bad}; build a new index (or override only "
                "query-side knobs: k/topk_method/merge_schedule/"
                "precision_policy/query_bucket/dispatch_depth/donate)"
            )
        if want.precision_policy == "mixed" and self.cfg.dtype != "float32":
            raise ValueError(
                "precision_policy='mixed' cannot serve from a "
                f"{self.cfg.dtype} index: the exact rerank contract is "
                "void on a corpus compressed at rest"
            )
        return want


def build_index(
    corpus,
    config: Optional[KNNConfig] = None,
    mesh: Optional[Mesh] = None,
    **overrides,
) -> CorpusIndex:
    """Build a device-resident :class:`CorpusIndex` for query serving.

    Args:
      corpus: (m, d) host array or device ``jax.Array`` (device inputs are
        tiled/sharded without a host bounce, same contract as ``all_knn``).
      config: build-time :class:`KNNConfig`; kwargs override fields.
      mesh: optional ring mesh for the distributed backends.
    """
    from mpi_knn_tpu.api import resolve_backend
    from mpi_knn_tpu.obs.spans import span as _flight_span

    start_lane_bin_import()  # under the corpus passes below
    cfg = (config or KNNConfig()).replace(**overrides)
    if not isinstance(corpus, jax.Array):
        corpus = np.asarray(corpus)
    m, dim = corpus.shape
    backend = resolve_backend(cfg, mesh)
    with _flight_span("index-build", cat="index", backend=backend,
                      m=int(m), dim=int(dim)):
        return _build_index_resident(corpus, cfg, mesh, backend, m, dim)


def _build_index_resident(corpus, cfg, mesh, backend, m, dim) -> CorpusIndex:

    mu = None
    onepass = None
    if cfg.center and cfg.metric == "l2":
        # ops.distance.center_for_l2's own offset and centring, computed
        # ONCE here: f64 on host, accumulation dtype on device. Queries
        # are centered per batch with this stored offset, so serving math
        # is bit-identical to a fresh all_knn over the same residency.
        corpus, mu, fact = center_corpus(corpus)
        # a fact of the index, read once here (a build may wait): an index
        # over data that does not qualify compiles today's program only
        if backend == "serial" and onepass_applies(cfg) and bool(fact):
            onepass = jax.device_put(np.bool_(True))
    obs_metrics.get_registry().gauge(
        "serve_index_onepass",
        help="1 when every centred element of the resident corpus is a "
        "bf16 number, so batches of such queries take the one-pass "
        "distance dot (the serial layout's rule); else 0",
    ).set(float(onepass is not None))

    if backend in ("ring", "ring-overlap"):
        from mpi_knn_tpu.backends.ring import parse_ring_mesh, ring_tiles
        from mpi_knn_tpu.parallel.mesh import make_ring_mesh

        if mesh is None:
            mesh = make_ring_mesh(cfg.num_devices, axis_name=cfg.mesh_axis)
        q_axis, axis, dp, ring_n = parse_ring_mesh(mesh)
        if backend == "ring" and q_axis is not None:
            from mpi_knn_tpu.backends.ring import (
                blocking_undefined_on_mesh_error,
            )

            raise blocking_undefined_on_mesh_error(mesh.axis_names)
        # corpus-side padding only: the query-side tile/pad is bucket-
        # dependent and computed per executable (engine.ring_query_shapes);
        # ring_tiles with nq=query_bucket fixes c_tile/c_pad for the index
        _, c_tile, _, c_pad = ring_tiles(cfg, m, cfg.query_bucket, dp, ring_n)
        dtype = jnp.dtype(cfg.dtype)
        csh = NamedSharding(mesh, P(axis))
        corpus_p = pad_rows_any(corpus, c_pad, dtype=dtype)
        corpus_scales = None
        if cfg.ring_transfer_dtype == "int8":
            # quantize ONCE at build: the resident shards hold the wire
            # representation (codes + per-row scales), so every batch's
            # rotation starts from the already-compressed block and the
            # serve program only ever dequantizes (backends.ring)
            from mpi_knn_tpu.backends.ring import quantize_ring_block

            corpus_p, corpus_scales = quantize_ring_block(corpus_p)
            corpus_scales = jax.device_put(corpus_scales, csh)
        corpus_p = jax.device_put(corpus_p, csh)
        corpus_ids = jax.device_put(jnp.asarray(make_global_ids(m, c_pad)), csh)
        return CorpusIndex(
            cfg=cfg.replace(backend=backend), backend=backend, m=m, dim=dim,
            c_tile=c_tile, mu=mu, mesh=mesh,
            ring_meta=(q_axis, axis, dp, ring_n),
            corpus_sharded=corpus_p, corpus_ids_sharded=corpus_ids,
            corpus_scales_sharded=corpus_scales,
        )

    if backend == "pallas":
        if cfg.dtype != "float32":
            raise ValueError(
                "pallas backend computes in float32; build the index with "
                f"dtype='float32' (got {cfg.dtype!r})"
            )
        if cfg.metric != "l2":
            raise ValueError(
                "pallas serving supports metric='l2' only: the cosine "
                "path needs a per-batch zero-row degeneracy probe (a "
                "host round-trip) that a streaming engine cannot honor — "
                "use the serial or ring backends for cosine serving"
            )
        c_tile = min(max(128, pad_to_multiple(cfg.corpus_tile, 128)), 2048,
                     pad_to_multiple(m, 128))
        c_pad = pad_to_multiple(m, c_tile)
        corpus_p = pad_rows_any(corpus, c_pad, dtype=jnp.float32)
        return CorpusIndex(
            cfg=cfg.replace(backend=backend), backend=backend, m=m, dim=dim,
            c_tile=c_tile, mu=mu, corpus_padded=corpus_p,
        )

    # serial: the tile stack + ids + NORMS, all resident (norms are the
    # O(m·d) reduction all_knn redoes per call — here they are index state)
    from mpi_knn_tpu.backends.serial import cap_corpus_tile

    dtype = jnp.dtype(cfg.dtype)
    c_tile = cap_corpus_tile(
        cfg.query_tile,
        min(cfg.corpus_tile, pad_to_multiple(m, 128)),
        cfg.max_tile_elems,
    )
    # capacity headroom (ISSUE 14): extra id −1 rows beyond the corpus
    # are the serial layout's upsert capacity — the mutation freelist
    # fills them by donated in-place scatter with no shape change. They
    # cost padded FLOPs per batch (masked, never answers); build with
    # bucket_headroom=0.0 for a frozen corpus.
    c_pad = pad_to_multiple(
        max(m, int(np.ceil(m * (1.0 + cfg.bucket_headroom)))), c_tile
    )
    tiles = pad_rows_any(corpus, c_pad, dtype=dtype).reshape(-1, c_tile, dim)
    tile_ids = jnp.asarray(make_global_ids(m, c_pad).reshape(-1, c_tile))
    # same norm construction as knn_chunk_update (zeros for cosine, where
    # the metric kernel normalizes internally), computed UNDER JIT: the
    # eager-mode reduction produces different bits than the traced one on
    # CPU, and serving must be bit-identical to a fresh all_knn call
    acc = jnp.float64 if dtype == jnp.float64 else jnp.float32
    tile_sqs = (
        jax.jit(jax.vmap(sq_norms))(tiles)
        if cfg.metric == "l2"
        else jnp.zeros(tiles.shape[:2], dtype=acc)
    )
    return CorpusIndex(
        cfg=cfg.replace(backend=backend), backend=backend, m=m, dim=dim,
        c_tile=c_tile, mu=mu, tiles=tiles, tile_ids=tile_ids,
        tile_sqs=tile_sqs, onepass=onepass,
    )
