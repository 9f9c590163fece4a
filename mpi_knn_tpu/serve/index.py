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
  derives the same mean from the same corpus) — wherever the stack rests
  at its rows' width; a stack that rests zero-padded on the lane grid
  (:func:`rest_width`) answers with the same values up to float32's
  summation order;
- bf16 compression is ``dtype="bfloat16"`` at build time: the resident
  tiles are stored (and computed) at half width, halving HBM residency —
  the same measured-recall contract as everywhere else in the framework.

The executable cache for the query side lives in ``serve.engine`` and is
keyed per (row bucket, config); the index carries it so two indices can
never collide on a cache entry.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_knn_tpu.backends.serial import (
    TileCounts,
    cap_corpus_tile,
    resident_norms,
    screen_rule,
    serve_chunk,
    serve_chunk_filtered,
    tile_counts,
)
from mpi_knn_tpu.config import METRICS, KNNConfig
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.ops.distance import (
    bf16_exact,
    center_corpus,
    first_unfit_row,
    onepass_applies,
    unfit_row_error,
    whole_offset,
)
from mpi_knn_tpu.ops.topk import (
    init_topk,
    init_topk_tiles,
    start_lane_bin_import,
)
from mpi_knn_tpu.parallel.partition import (
    make_global_ids,
    pad_cols,
    pad_rows_any,
    pad_to_multiple,
)
from mpi_knn_tpu.utils.logs import log


def onepass_holds(index) -> bool:
    """Whether ``index`` still holds the one-pass rule's corpus-side fact
    (``index.onepass``, a device scalar of the kinds that keep one: read
    here from its host shadow, never from the device). An upsert of a row
    that is no bf16 number drops it for good (``serve/mutate.py``)."""
    return getattr(index, "onepass", None) is not None and (
        index.__dict__.get("_onepass_holds", True))


class BatchLayout:
    """What one index kind's batch program is and how it is called. One
    instance per kind, chosen when the index is built and carried by it
    (``index.layout``): ``serve.engine`` lowers, signature-checks, prepares
    and dispatches every kind through these answers and never asks a
    kind's name, so a new kind is one subclass beside its arrays.

    Every program is called ``fn(queries, query_ids, *scratch, *resident,
    **statics)``. The defaults are the flat dense convention: ``(q_pad, d)``
    rows in the config's dtype on the default device, an eager
    ``(q_pad, k)`` scratch pair in the accumulation dtype, two outputs."""

    static_argnames: tuple = ("cfg",)
    donate_argnums: tuple = (2, 3)  # the scratch; R5 wants each one aliased
    tiled = False  # the program takes (qt, q_tile, ·) stacks, not rows ...
    pretiled = False  # ... and a prepared batch already has that shape
    exchange_stats = False  # third output: the per-shard exchange stats
    onepass_gauge = "serve_index_onepass"  # what says the fact holds

    def serve_fn(self):
        """The function the engine jits."""
        raise NotImplementedError

    def bucket_shapes(self, index, cfg: KNNConfig, bucket: int):
        """``(q_pad, q_tile)`` of one (bucket, config) cell: shape math."""
        raise NotImplementedError

    def resident(self, index) -> tuple:
        """The index-side arguments in call order. ``None`` entries (the
        scales of an unquantized store) are empty pytree nodes that jax
        drops from the flattened argument list."""
        raise NotImplementedError

    def statics(self, index, cfg: KNNConfig, bucket: int) -> dict:
        return {"cfg": cfg}

    def query_dtype(self, cfg: KNNConfig):
        return jnp.dtype(cfg.compute_dtype)

    def carry_dtype(self, cfg: KNNConfig):
        return jnp.dtype("float64" if cfg.dtype == "float64" else "float32")

    def query_sharding(self, index):
        return None

    @functools.lru_cache(maxsize=None)  # layouts are per-kind constants
    def jit(self, donate: bool):
        return jax.jit(
            self.serve_fn(),
            static_argnames=self.static_argnames,
            donate_argnums=self.donate_argnums if donate else (),
        )

    def rows(self, q_pad: int, q_tile: int) -> tuple:
        """Leading shape of the program's batch-owned arguments."""
        return (q_pad // q_tile, q_tile) if self.tiled else (q_pad,)

    def prepared_rows(self, q_pad: int, q_tile: int) -> tuple:
        """Leading shape of a prepared batch: flat rows, which a tiled
        program gets reshaped at dispatch, unless the layout wants its
        batches tiled from the start (on a sharding, where a reshape
        would be a program)."""
        return self.rows(q_pad, q_tile) if self.pretiled else (q_pad,)

    def query_side(self, index, cfg: KNNConfig, q_pad: int, q_tile: int):
        """The batch-owned arguments (queries, ids, scratch) as
        ``ShapeDtypeStruct``s on their sharding: the ONE description the
        lowering and the persistent cache's signature check both read."""
        rows = self.rows(q_pad, q_tile)
        sds = functools.partial(
            jax.ShapeDtypeStruct, sharding=self.query_sharding(index)
        )
        return [
            sds(rows + (index.dim,), self.query_dtype(cfg)),
            sds(rows, jnp.int32),
            sds(rows + (cfg.k,), self.carry_dtype(cfg)),
            sds(rows + (cfg.k,), jnp.int32),
        ]

    def carry_maker(self, index, cfg: KNNConfig, q_pad: int, q_tile: int):
        """A zero-argument maker of one batch's donated scratch (fresh
        buffers every call: the executable consumes them). Eager on the
        default device; on a query sharding a once-compiled program that
        bears the scratch there, because building it on the default device
        and resharding would allocate and copy on every batch."""
        init = functools.partial(
            init_topk_tiles if self.tiled else init_topk,
            *self.rows(q_pad, q_tile), cfg.k, dtype=self.carry_dtype(cfg),
        )
        qsh = self.query_sharding(index)
        return init if qsh is None else jax.jit(
            init, out_shardings=(qsh, qsh)
        )

    def exchange_bytes(self, index, cfg, bucket, q_pad, q_tile):
        """Static bytes one batch's exchange collectives move, or None."""
        return None

    def stamp_gauges(self, index, cfg: KNNConfig, registry) -> None:
        """The kind's compression gauges, stamped at build (shape math)."""

    def batch_counts(self, index, q_pad: int, q_tile: int, rest: tuple):
        """The batch's ``backends.serial.TileCounts`` for the kinds whose
        batches run ``masked_dist_tile``; ``rest`` is what the program
        returned beyond (dists, ids)."""
        return TileCounts()


class SerialLayout(BatchLayout):
    """The tile stack + ids + norms of one device (``backends.serial``)."""

    tiled = True

    def serve_fn(self):
        return serve_chunk

    def bucket_shapes(self, index, cfg, bucket):
        q_tile = min(cfg.query_tile, pad_to_multiple(bucket, 8))
        return pad_to_multiple(bucket, q_tile), q_tile

    def resident(self, index):
        return (index.tiles, index.tile_ids, index.tile_sqs, index.onepass)

    def batch_counts(self, index, q_pad, q_tile, rest):
        # a program that counts on the device (the one-pass branch taken,
        # the carried selections re-scanned) has a third output
        return tile_counts(
            rest, q_pad // q_tile, index.tiles.shape[0], index.cfg.metric)


class ByteSerialLayout(SerialLayout):
    """The serial layout of a BYTE stack (``dtype="uint8"``): the batch
    program takes the whole-number offset its tile steps widen by, last of
    the resident arguments (``backends.serial.serve_chunk``'s ``offset``);
    query rows are float32."""

    def resident(self, index):
        return (*super().resident(index), index.rest_offset)


class TaggedSerialLayout(SerialLayout):
    """The serial layout of an index built with tags (``serve/tags.py``):
    the batch program takes a predicate a query row — its frequent tags'
    bitset rows, after the scratch — and the bitsets, after the stack."""

    def serve_fn(self):
        return serve_chunk_filtered

    def query_side(self, index, cfg, q_pad, q_tile):
        return [
            *super().query_side(index, cfg, q_pad, q_tile),
            jax.ShapeDtypeStruct(
                self.rows(q_pad, q_tile) + (cfg.max_query_tags,), jnp.int32),
        ]

    def resident(self, index):
        return (*super().resident(index), index.tags.tag_bits)


@dataclasses.dataclass(frozen=True)
class RingLayout(BatchLayout):
    """The padded corpus sharded over the ring axis (``backends.ring``),
    blocking or overlapped rotation."""

    overlap: bool
    static_argnames = (
        "cfg", "overlap", "mesh", "axis", "q_tile", "c_tile", "q_axis"
    )

    def serve_fn(self):
        from mpi_knn_tpu.backends.ring import ring_serve_sharded

        return ring_serve_sharded

    def bucket_shapes(self, index, cfg, bucket):
        # ``ring_tiles`` would re-derive c_tile from the bucket's q_tile,
        # but the resident corpus was padded once at build time: only the
        # query side moves, and the per-step tile cap is honored by
        # shrinking q_tile against the frozen c_tile
        _, _, dp, ring_n = index.ring_meta
        num_dev = dp * ring_n
        q_tile = min(cfg.query_tile, -(-bucket // num_dev))
        while q_tile > 1 and q_tile * index.c_tile > cfg.max_tile_elems:
            q_tile = max(1, q_tile // 2)
        return pad_to_multiple(bucket, num_dev * q_tile), q_tile

    def resident(self, index):
        return (index.corpus_sharded, index.corpus_ids_sharded,
                index.corpus_scales_sharded)

    def statics(self, index, cfg, bucket):
        q_axis, axis, _, _ = index.ring_meta
        return dict(
            cfg=cfg, overlap=self.overlap, mesh=index.mesh, axis=axis,
            q_tile=self.bucket_shapes(index, cfg, bucket)[1],
            c_tile=index.c_tile, q_axis=q_axis,
        )

    def query_sharding(self, index):
        from mpi_knn_tpu.backends.ring import _query_spec

        return NamedSharding(index.mesh, _query_spec(*index.ring_meta[:2]))

    def stamp_gauges(self, index, cfg, registry):
        from mpi_knn_tpu.backends.ring import ring_wire_bytes_per_batch

        registry.gauge(
            "ring_transfer_wire_bytes",
            help="bytes one batch's full corpus rotation moves over "
            "the interconnect, at the wire dtype (static per "
            "executable)",
        ).set(ring_wire_bytes_per_batch(
            cfg, index.corpus_sharded.shape[0], index.dim,
            index.ring_meta[3],
        ))

    def batch_counts(self, index, q_pad, q_tile, rest):
        return tile_counts(
            (), q_pad // q_tile,
            index.corpus_sharded.shape[0] // index.c_tile, index.cfg.metric)


SERIAL = SerialLayout()
BYTE_SERIAL = ByteSerialLayout()
TAGGED_SERIAL = TaggedSerialLayout()
RING, RING_OVERLAP = RingLayout(overlap=False), RingLayout(overlap=True)


@dataclasses.dataclass
class CorpusIndex:
    """Resident corpus state for one (corpus, config[, mesh]) triple.

    ``backend`` is resolved (never "auto"); exactly one of the two storage
    layouts is populated: the tile stack (serial) or the sharded
    padded corpus (ring/ring-overlap).
    """

    cfg: KNNConfig  # resolved backend; the serving default config
    backend: str
    m: int
    dim: int
    c_tile: int
    mu: object | None  # centering mean (host f64 or device), or None
    layout: BatchLayout  # the kind's batch program, chosen at build
    # serial layout
    tiles: jax.Array | None = None  # (T, c_tile, d)
    tile_ids: jax.Array | None = None  # (T, c_tile)
    tile_sqs: jax.Array | None = None  # (T, c_tile); None under "ip" too
    # the corpus side of the one-pass rule (backends.serial
    # masked_dist_tile): a bool scalar on the device, TRUE when the index
    # was built — every centred element a bf16 number — and handed to every
    # batch program, which then carries both branches; an upsert of a row
    # that is not turns it false in place, with no recompile. None (the
    # rule does not apply, or the corpus did not qualify at build): the
    # batch program has no branch and is the one it always was.
    onepass: jax.Array | None = None
    # a BYTE stack's offset (``dtype="uint8"``: ``tiles`` is uint8): (d,)
    # float32 on the device, the whole numbers every tile step takes off
    # its widened tile (``ops/distance.py widen_rows``; ``mu`` holds the
    # same for the query side). None: a float stack, centred at rest.
    rest_offset: jax.Array | None = None
    # the bags of the rows (``serve.tags.TagIndex``), where the index was
    # built with them: every batch then brings a predicate a query row,
    # the layout is :class:`TaggedSerialLayout`, and the index is frozen
    tags: object | None = None
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
        arr = self.tiles if self.tiles is not None else self.corpus_sharded
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
            "ring_schedule", "max_tile_elems", "exclude_zero", "zero_eps",
            "range_cap",
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
        if self.tags is not None and (
                want.precision_policy != "exact"
                or want.max_query_tags != self.cfg.max_query_tags):
            raise ValueError(
                "an index with tags serves precision_policy='exact' at the "
                f"max_query_tags it was built with "
                f"({self.cfg.max_query_tags}): the compress pass of 'mixed' "
                "knows no predicate"
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
    tags=None,
    **overrides,
) -> CorpusIndex:
    """Build a device-resident :class:`CorpusIndex` for query serving.

    Args:
      corpus: (m, d) host array or device ``jax.Array`` (device inputs are
        tiled/sharded without a host bounce, same contract as ``all_knn``).
      config: build-time :class:`KNNConfig`; kwargs override fields.
      mesh: optional ring mesh for the distributed backends.
      tags: optional bag of tag ids a corpus row, as a CSR (``(indptr,
        indices)``, a mapping / ``.npz`` with those names, a scipy CSR
        matrix): query rows may then carry up to ``max_query_tags`` tags
        and are answered among the rows whose bag holds them all
        (``serve/tags.py``). The dense ``serial`` layout only; the index
        is frozen (no upsert, delete or compact).
    """
    from mpi_knn_tpu.api import resolve_backend
    from mpi_knn_tpu.obs.spans import span as _flight_span

    start_lane_bin_import()  # under the corpus passes below
    cfg = (config or KNNConfig()).replace(**overrides)
    if not isinstance(corpus, jax.Array):
        corpus = np.asarray(corpus)
    if cfg.dtype == "uint8":
        # a byte stack has one builder: one array is its one-block case
        if tags is not None:
            raise ValueError(
                "an index with tags holds float32 rows: the predicate's "
                "gather regime finishes gathered rows of the stack as they "
                "rest (serve/tags.py) and has no widened form — build "
                "without tags, or with dtype='float32'")
        return build_index_blocks(corpus.shape, (corpus,), cfg, mesh=mesh)
    m, dim = corpus.shape
    backend = resolve_backend(cfg, mesh)
    refuse_range_build(cfg, dim, True, tags is not None, backend)
    if tags is not None:
        if backend != "serial":
            raise ValueError(
                f"an index with tags is served by the dense serial layout "
                f"only; the {backend!r} layout has no predicate plane "
                "(build with backend='serial')")
        if cfg.metric == "ip":
            raise ValueError(
                "an index with tags answers by a distance: under "
                "metric='ip' neither regime of the predicate (the masked "
                "scan's one-pass branch, the gather's exact finish) has "
                "an inner-product form or a reference — build without "
                "tags, or with metric='l2'")
        if cfg.bucket_headroom:
            raise ValueError(
                "an index with tags is frozen: bucket_headroom="
                f"{cfg.bucket_headroom} reserves slots for upserts it "
                "would refuse (build with bucket_headroom=0)")
    with _flight_span("index-build", cat="index", backend=backend,
                      rows=int(m), dim=int(dim), metric=cfg.metric,
                      bytes=int(corpus.size) * corpus.dtype.itemsize):
        index = _build_index_resident(
            corpus, cfg, mesh, backend, m, dim, tagged=tags is not None)
    if tags is not None:
        from mpi_knn_tpu.serve.tags import build_tag_index

        with _flight_span("tags-build", cat="index", rows=int(m)):
            index.tags = build_tag_index(index, tags)
        index.layout = TAGGED_SERIAL
    return index


@functools.partial(
    jax.jit, static_argnames=("c_pad", "c_tile", "dtype", "width"))
def _pad_and_tile(corpus, c_pad: int, c_tile: int, dtype, width: int):
    """Tile by tile into a zeroed stack ``width`` columns wide
    (:func:`rest_width`: columns past the rows' own stay zero): the
    program's temporaries are one tile's, whatever layouts the device keeps
    the two shapes in (as one ``pad`` + ``reshape`` the v5e compiler takes
    8.3 GB of them at 9.8 M x 100: a padded copy, then its re-layout)."""
    m, dim = corpus.shape
    full = m // c_tile

    def one_tile(t, stack):
        tile = jax.lax.dynamic_slice_in_dim(corpus, t * c_tile, c_tile)
        return jax.lax.dynamic_update_index_in_dim(
            stack, pad_cols(tile.astype(dtype), width), t, 0)

    stack = jnp.zeros((c_pad // c_tile, c_tile, width), dtype)
    if full:  # a corpus under one tile has no whole tile to slice
        stack = jax.lax.fori_loop(0, full, one_tile, stack)
    if m % c_tile:
        tail = jnp.pad(pad_cols(corpus[full * c_tile:].astype(dtype), width),
                       [(0, c_tile - m % c_tile), (0, 0)])
        stack = stack.at[full].set(tail)
    return stack


def _tile_stack(corpus, c_pad: int, c_tile: int, dtype, width: int):
    """The (tiles, c_tile, width) stack of a corpus padded to ``c_pad``
    rows and, where the stack rests wider than its rows
    (:func:`rest_width`), to ``width`` zero-filled columns. A device corpus
    that needs either padding (headroom, a last tile, the lane grid) is
    padded and tiled by ONE program: done eagerly the padded copy stands
    beside the stack, and where the device keeps the two shapes in
    different layouts (a TPU at a width off its 128-lane grid) the
    reshape is a copy too — at 9.8 M x 100 the caller's array, the
    centred copy, the padded copy and the stack were 15.7 GB, and the
    build died 3.4 GB short on a v5e."""
    m, dim = corpus.shape
    if isinstance(corpus, jax.Array) and (c_pad, width) != (m, dim):
        return _pad_and_tile(
            corpus, c_pad=c_pad, c_tile=c_tile, dtype=dtype, width=width)
    corpus = pad_cols(corpus, width)  # a host corpus: one host pad
    return pad_rows_any(corpus, c_pad, dtype=dtype).reshape(
        -1, c_tile, width)


_LANES = 128  # the TPU's lane grid: a float32 row of a multiple rests whole
# What condition (c) of :func:`rest_width` leaves free beside the wider
# stack, so that a build with room by a few bytes keeps the narrower form
# and the layout does not turn on the allocator's last bytes: the build's
# own program and the norm pass (64 MiB of temporaries at most, compiled for
# the v5e: tests/test_pallas.py), then what serving needs beside the stack
# — a batch program's temporaries (64 MiB each, a few in flight), its
# operands, the upsert's (16 MiB) — and as much again for fragmentation
REST_RESERVE_BYTES = 1 << 30


def rest_width(cfg: KNNConfig, dim: int, c_tile: int, slots: int, *,
               onepass: bool, tagged: bool = False,
               free_bytes: int | None = None) -> tuple[int, int]:
    """``(width, short)``: the columns a dense serial stack of ``slots``
    row slots in tiles of ``c_tile`` RESTS at — the rows' own ``dim``, or
    ``dim`` rounded up to the lane grid with the columns past ``dim`` zero
    — and how (c) below fell out: the bytes the device was short by where
    its room ALONE declined the wider form (positive), the bytes it had to
    spare past the reserve where it granted it (negative or 0), 0 where
    room was never asked. Asked once, at the build, from what the build can
    observe; no setting. The stack rests padded iff

    (a) ``dim`` is off the lane grid: there a TPU keeps a float32 (T, c,
        d) stack rows-minor, a tile's slice is a copy every step and a
        candidate's row ``dim`` scalar reads
        (``serve/mutate.py stack_rests_row_major``);
    (b) the certified screen would engage at the padded width for the
        index's serving tile (``backends/serial.py screen_rule`` at
        ``cfg.query_tile`` rows: float32 rows at ``highest``, a metric of
        the static path, carried lists, >= 1024 query rows, no one-pass
        branch — ``onepass``: a whole-number corpus already ranks in one
        pass inside the fused kernel at its own width — and no predicate's
        words, ``tagged``) — three passes for six are what the 28 % more
        bytes at d = 100 buy; without the screen the padding would only
        remove the slice;
    (c) the device has room for the padded stack and its planes AND
        :data:`REST_RESERVE_BYTES` beside what the build holds at that
        moment (the caller's device array, the centred copy):
        ``free_bytes``, from the device's own memory statistics
        (:func:`device_free_bytes`); None — the backend reports none, the
        CPU — assumes room.

    Zero columns add exact zeros to every dot and every norm: a padded
    index answers with the values of the unpadded rows up to float32's
    summation order."""
    wide = pad_to_multiple(dim, _LANES)
    if wide == dim or screen_rule(cfg, cfg.query_tile, c_tile, wide,
                                  branch=onepass, filtered=tagged) is None:
        return dim, 0
    if free_bytes is None:
        return wide, 0
    itemsize = jnp.dtype(cfg.dtype).itemsize
    planes = 4 + (0 if cfg.metric == "ip" else 4)  # ids, norms: a slot
    short = (slots * (wide * itemsize + planes) + REST_RESERVE_BYTES
             - free_bytes)
    return (dim if short > 0 else wide), short


def device_free_bytes(corpus) -> int | None:
    """Bytes the device that will hold the stack has free right now — its
    limit less what is in use, by its own statistics — or None where the
    backend reports none (the CPU). The device is the corpus's own where
    the corpus is a device array, else the default one."""
    device = (next(iter(corpus.devices())) if isinstance(corpus, jax.Array)
              else jax.local_devices()[0])
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats or "bytes_in_use" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats["bytes_in_use"])


def _rest_width_of_build(cfg, dim, c_tile, c_pad, onepass, tagged, corpus):
    """:func:`rest_width` for a build, said where an operator reads it: one
    log line and the gauge ``serve_index_rest_width``."""
    free = device_free_bytes(corpus)
    width, short = rest_width(
        cfg, dim, c_tile, c_pad, onepass=onepass, tagged=tagged,
        free_bytes=free)
    obs_metrics.get_registry().gauge(
        "serve_index_rest_width",
        help="columns a row of the dense tile stack rests at: the rows' "
        "own width, or that rounded up to the 128-lane grid (zero-filled) "
        "where the certified screen then ranks in three passes and the "
        "device has the room (serve/index.py rest_width)",
    ).set(float(width))
    if short > 0:
        log.info(
            "index rests at its rows' width %d: %d columns on the lane "
            "grid and a reserve of %d bytes want %d bytes more than the "
            "device has free", dim, pad_to_multiple(dim, _LANES),
            REST_RESERVE_BYTES, short)
    elif width != dim:
        log.info(
            "index rests zero-padded at %d columns (rows of %d): row-major "
            "on the lane grid, screened scan; %s", width, dim,
            f"{-short} bytes to spare past a reserve of "
            f"{REST_RESERVE_BYTES}" if free is not None else "room "
            "assumed (the backend reports no memory statistics)")
    else:
        log.info("index rests at its rows' width %d", dim)
    return width


def _stamp_index_gauges(cfg: KNNConfig, onepass) -> None:
    """What a build says of its index on ``/metrics``, before the arrays."""
    obs_metrics.get_registry().gauge(
        "serve_index_onepass",
        help="1 when every centred element of the resident corpus is a "
        "bf16 number, so batches of such queries take the one-pass "
        "distance dot (the serial layout's rule); else 0",
    ).set(float(onepass is not None))
    obs_metrics.get_registry().gauge(
        "serve_index_cosine",
        help="1 when the resident index answers by cosine distance (the "
        "serial layout then holds the corpus rows' inverse norms, and "
        "its batches' tile steps count under path=\"cosine\"); else 0",
    ).set(float(cfg.metric == "cosine"))
    for name in METRICS:
        obs_metrics.get_registry().gauge(
            "serve_index_metric",
            help="1 under the metric the resident index answers by: l2 "
            "(squared L2), cosine (1 - cosine similarity) or ip (the "
            "negated inner product; no norm plane, no centring, its "
            "batches' tile steps count under path=\"ip\"); 0 under the "
            "others",
            labels={"metric": name},
        ).set(float(cfg.metric == name))


# the widest row whose sums stay whole numbers a float32 holds: every
# term of a squared distance of whole numbers up to 255 apart is at most
# 255^2, and RANGE_MAX_DIM of them stand under 2^24 (256 x 255^2 =
# 16 646 400 < 16 777 216; 259 do not)
RANGE_MAX_DIM = 256


def refuse_range_build(cfg: KNNConfig, dim: int, onepass, tagged: bool,
                       backend: str) -> None:
    """What only a build can know of an index that is to answer range
    search (``cfg.range_cap`` > 0; ``config.py _refuse_under_range`` holds
    what the configuration alone says): each with its reason."""
    if not cfg.range_cap:
        return
    if backend != "serial":
        raise ValueError(
            f"range search runs over the dense serial index; this build "
            f"resolved to the {backend!r} layout (several devices under "
            "backend='auto' make a ring) — build with backend='serial'")
    if tagged:
        raise ValueError(
            "range search takes no predicate yet: the masked scan and the "
            "gather regime of an index with tags answer a fixed k a row "
            "(serve/tags.py) — build without tags, or with range_cap=0")
    if dim > RANGE_MAX_DIM:
        raise ValueError(
            f"range search wants rows at most {RANGE_MAX_DIM} wide, got "
            f"{dim}: beyond it a sum of a whole-number distance can pass "
            "2^24, where float32 rounds, and a row AT the radius could be "
            "answered as under it")
    if onepass is None:
        raise ValueError(
            "range search needs whole-number rows (every centred element "
            "a bf16 number: a byte corpus, pixels): over fractional "
            "float32 rows the scan ranks in rounded passes (the screened "
            "and six-pass forms) and a row near the radius needs the "
            "exact finish, which has no range form yet — build with "
            "range_cap=0")


def _serial_index(cfg, m, dim, c_tile, mu, tiles, tile_ids, tile_sqs,
                  onepass, layout=None, rest_offset=None) -> CorpusIndex:
    """The dense serial index over a finished stack and its planes, with
    the gauge that says what a row costs at rest."""
    refuse_range_build(cfg, dim, onepass, False, "serial")
    planes = sum(a.size * a.dtype.itemsize
                 for a in (tiles, tile_ids, tile_sqs) if a is not None)
    obs_metrics.get_registry().gauge(
        "serve_index_rest_bytes_per_row",
        help="resident bytes of the dense tile stack and its id and norm "
        "planes over the stack's row slots: 4 w + 8 for float32 rows at "
        "the rest width w (serve_index_rest_width: d, or d rounded up to "
        "the lane grid), d + 8 for a byte stack (dtype=uint8), 4 less "
        "under metric=ip",
    ).set(planes / (tiles.shape[0] * tiles.shape[1]))
    return CorpusIndex(
        cfg=cfg.replace(backend="serial"), backend="serial", m=m, dim=dim,
        c_tile=c_tile, mu=mu, tiles=tiles, tile_ids=tile_ids,
        tile_sqs=tile_sqs, onepass=onepass, layout=layout or SERIAL,
        rest_offset=rest_offset,
    )


def _serial_tiling(cfg: KNNConfig, m: int) -> tuple[int, int]:
    """``(c_tile, c_pad)`` of a dense serial stack over ``m`` rows."""
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
    return c_tile, c_pad


def _build_index_resident(corpus, cfg, mesh, backend, m, dim,
                          tagged=False) -> CorpusIndex:

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
    _stamp_index_gauges(cfg, onepass)

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
        # dependent and computed per executable (RingLayout.bucket_shapes);
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
            layout=RING_OVERLAP if backend == "ring-overlap" else RING,
            ring_meta=(q_axis, axis, dp, ring_n),
            corpus_sharded=corpus_p, corpus_ids_sharded=corpus_ids,
            corpus_scales_sharded=corpus_scales,
        )

    # serial: the tile stack + ids + NORMS, all resident (norms are the
    # O(m·d) reduction all_knn redoes per call — here they are index state)
    dtype = jnp.dtype(cfg.dtype)
    c_tile, c_pad = _serial_tiling(cfg, m)
    width = _rest_width_of_build(
        cfg, dim, c_tile, c_pad, onepass is not None, tagged, corpus)
    tiles = _tile_stack(corpus, c_pad, c_tile, dtype, width)
    tile_ids = jnp.asarray(make_global_ids(m, c_pad).reshape(-1, c_tile))
    # knn_chunk_update's own norm construction (squared norms for L2; for
    # cosine the rows' INVERSE norms, so that no batch normalises a corpus
    # tile): a reduction over the stack, no third corpus-sized array,
    # computed UNDER JIT — the eager-mode reduction produces different
    # bits than the traced one on CPU, and serving must be bit-identical
    # to a fresh all_knn call
    tile_sqs = resident_norms(tiles, cfg.metric)
    return _serial_index(
        cfg, m, dim, c_tile, mu, tiles, tile_ids, tile_sqs, onepass)


# --- rows handed over in blocks ---------------------------------------------


@functools.partial(jax.jit, static_argnames=("sums",), donate_argnums=(0,))
def _ingest_block(stack, block, row0, sums: bool):
    """``stack`` (T, c_tile, w), donated, with the ``n`` rows of ``block``
    (zero-filled to the stack's width, :func:`rest_width`)
    written at row ``row0`` of the stack viewed flat, tile by tile as
    :func:`_pad_and_tile` writes one array: the program's temporaries are
    the block's (its rows at rest, and a copy with a tile of margin either
    side to slice whole tiles from), whatever layouts the device keeps the
    two shapes in. Beside it, what the build has to know of the block:
    ``unfit``, the first row a byte stack cannot hold (``n``: none; of a
    float stack always ``n``), and under ``sums`` the column sums (int32,
    exact, of a byte stack's rows; else float32) and whether every element
    is a whole number."""
    n_tiles, c_tile, width = stack.shape
    n = block.shape[0]
    bytes_rest = stack.dtype == jnp.uint8
    unfit = first_unfit_row(block) if bytes_rest else jnp.int32(n)
    rows = pad_cols(block.astype(stack.dtype), width)
    col = whole = None
    if sums and bytes_rest:
        col = jnp.sum(rows, axis=0, dtype=jnp.int32)
    elif sums:
        col = jnp.sum(block, axis=0, dtype=jnp.float32)
        whole = jnp.all(block == jnp.rint(block))
    padded = jnp.pad(rows, ((c_tile, c_tile), (0, 0)))
    first = row0 // c_tile

    def one_tile(j, stack):
        t = jnp.minimum(first + j, n_tiles - 1)
        # the tile's first row, counted in the padded block: a tile the
        # block does not reach lands in the margins and keeps what it has
        start = jnp.clip(t * c_tile - row0 + c_tile, 0, n + c_tile)
        piece = jax.lax.dynamic_slice_in_dim(padded, start, c_tile)
        at = t * c_tile + jnp.arange(c_tile, dtype=jnp.int32) - row0
        old = jax.lax.dynamic_index_in_dim(stack, t, keepdims=False)
        new = jnp.where(((at >= 0) & (at < n))[:, None], piece, old)
        return jax.lax.dynamic_update_index_in_dim(stack, new, t, 0)

    touched = min(-(-n // c_tile) + 1, n_tiles)
    return jax.lax.fori_loop(0, touched, one_tile, stack), unfit, col, whole


@functools.partial(jax.jit, static_argnames=("m",), donate_argnums=(0,))
def _centre_stack(stack, mu, m: int):
    """A float stack of RAW rows centred in place by ``mu``, tile by tile
    (the slots past row ``m`` stay zero, as a padded centred corpus has
    them), and the one-pass rule's fact of the centred rows."""
    n_tiles, c_tile, _ = stack.shape

    def one_tile(t, carry):
        stack, fact = carry
        tile = jax.lax.dynamic_index_in_dim(stack, t, keepdims=False)
        live = t * c_tile + jnp.arange(c_tile, dtype=jnp.int32) < m
        centred = jnp.where(live[:, None], tile - mu.astype(tile.dtype), tile)
        return (jax.lax.dynamic_update_index_in_dim(stack, centred, t, 0),
                fact & bf16_exact(centred))

    return jax.lax.fori_loop(0, n_tiles, one_tile, (stack, jnp.asarray(True)))


@functools.partial(jax.jit, static_argnames=("m", "shape"))
def _id_plane(m: int, shape: tuple):
    """``make_global_ids`` on the device: a hundred million ids are no
    host array to make and move."""
    at = jnp.arange(shape[0] * shape[1], dtype=jnp.int32)
    return jnp.where(at < m, at, -1).reshape(shape)


@functools.partial(jax.jit, donate_argnums=(0,))
def _live_norms(tile_sqs, tile_ids):
    return jnp.where(tile_ids >= 0, tile_sqs, 0.0)


def _each_block(blocks):
    """``blocks`` as an iterator: an iterable of row blocks, or a callable
    ``blocks(i)`` that returns block ``i`` and None after the last."""
    if not callable(blocks):
        yield from blocks
        return
    i = 0
    while (block := blocks(i)) is not None:
        yield block
        i += 1


def _is_whole(block) -> bool:
    """Every element of ``block`` a whole number (``x == rint(x)``), read
    where the block is (numpy or the device)."""
    xp = jnp if isinstance(block, jax.Array) else np
    block = xp.asarray(block)
    return bool(xp.all(block == xp.rint(block)))


def build_index_blocks(
    shape,
    blocks,
    config: Optional[KNNConfig] = None,
    mesh: Optional[Mesh] = None,
    **overrides,
) -> CorpusIndex:
    """:func:`build_index` for rows handed over in BLOCKS: the dense
    ``serial`` index over ``shape = (m, d)`` rows that arrive as (n_i, d)
    arrays, in order, n_i of any sizes that add up to ``m`` — an iterable
    of them, or a callable ``blocks(i)`` (None after the last), so that a
    caller who reads a file or draws from a generator never holds the
    corpus: the tile stack is allocated once, each block is written into
    it in place (donated, tile by tile) and dropped, and the build's peak
    is the stack, its id and norm planes and ONE block's temporaries. A
    host block crosses to the device as it is (a ``uint8`` block as
    bytes).

    ``dtype="uint8"`` (a byte stack, ``ops/distance.py widen_rows``): a
    ``uint8`` block rests as it is; any other is first checked ON THE
    DEVICE — every element a whole number in [0, 255] — and a block that
    fails raises ``ValueError`` naming the first offending row of the
    corpus once the blocks are in: nothing is ever rounded. The offset is
    the rounded mean from exact integer column sums.

    ``dtype="float32"``: the raw rows rest first, the mean comes from the
    blocks' float32 column sums added in float64 (rounded to whole numbers
    for a whole-number corpus, as ``center_corpus`` rounds it) and the
    stack is centred in place: the same index as :func:`build_index`'s to
    the bit for whole-number rows, to the mean's last bits otherwise
    (off the lane grid the FIRST block's rows say whether the stack rests
    at its rows' width or zero-padded: :func:`rest_width`).
    Other dtypes (a bfloat16 stack is centred BEFORE it is narrowed), the
    ring backends, tags and headroom take :func:`build_index`."""
    from mpi_knn_tpu.api import resolve_backend
    from mpi_knn_tpu.obs.spans import span as _flight_span

    start_lane_bin_import()  # under the corpus passes below
    cfg = (config or KNNConfig()).replace(**overrides)
    m, dim = (int(n) for n in shape)
    backend = resolve_backend(cfg, mesh)
    if backend != "serial":
        raise ValueError(
            f"rows come in blocks to the dense serial index only; the "
            f"{backend!r} layout places its shards by one transfer of the "
            "padded corpus (build with backend='serial')")
    if cfg.dtype not in ("uint8", "float32"):
        raise ValueError(
            f"rows come in blocks at dtype='uint8' or 'float32', got "
            f"{cfg.dtype!r}: a narrower float stack is centred before it "
            "is narrowed, which takes the whole array (build_index)")
    if cfg.bucket_headroom:
        raise ValueError(
            "rows come in blocks to a stack without headroom (an index "
            "that takes writes takes build_index's one array)")
    rest = jnp.dtype(cfg.dtype)
    centred = cfg.center and cfg.metric == "l2"
    c_tile, c_pad = _serial_tiling(cfg, m)
    ingested = []  # (first row, rows, unfit, column sums, whole)
    with _flight_span("index-build", cat="index", backend=backend, rows=m,
                      dim=dim, metric=cfg.metric,
                      bytes=m * dim * rest.itemsize):
        # Whether float32 rows are whole numbers is known after the last
        # block, the stack's width before the first: the FIRST block
        # speaks for the rest (read only where the answer can move the
        # width). Whole numbers there and the stack rests at the rows'
        # width, as build_index rests a whole-number corpus (it ranks in
        # one pass already); should a later block hold a fraction the
        # stack stays so and carries no fact — the program a fractional
        # stack ran before there was a rule. A fraction there and no later
        # block can grant the fact.
        each = _each_block(blocks)
        first = next(each, None)
        whole = bool(dim % _LANES and centred and rest != jnp.uint8
                     and onepass_applies(cfg) and first is not None
                     and _is_whole(first))
        width = _rest_width_of_build(
            cfg, dim, c_tile, c_pad, whole, False, None)
        each = itertools.chain(() if first is None else (first,), each)
        del first  # (a block is dropped once it is in the stack)
        tiles = jnp.zeros((c_pad // c_tile, c_tile, width), rest)
        at = 0
        for block in each:
            if not isinstance(block, jax.Array):
                block = np.asarray(block)
            n = int(block.shape[0])
            if block.ndim != 2 or block.shape[1] != dim or at + n > m:
                raise ValueError(
                    f"block of shape {block.shape} at row {at} does not "
                    f"lie in a corpus of {(m, dim)}")
            with _flight_span("block-ingest", cat="index", rows=n,
                              bytes=n * dim * block.dtype.itemsize):
                tiles, *facts = _ingest_block(
                    tiles, block, np.int32(at), sums=centred)
            del block
            ingested.append((at, n, *facts))
            at += n
        if at != m:
            raise ValueError(f"the blocks held {at} rows of {m}")
        for first, n, unfit, _, _ in ingested:
            if int(unfit) < n:  # (waits for the block's program, no more)
                raise unfit_row_error(first + int(unfit))
        mu = offset = onepass = None
        if centred:
            total = np.sum([np.asarray(col, dtype=np.float64)
                            for *_, col, _ in ingested], axis=0)
            if rest == jnp.uint8:
                mu = whole_offset(total, m)
                offset = jnp.asarray(mu)
                fact = True
            else:
                mu = total / max(m, 1)
                if all(bool(whole) for *_, whole in ingested):
                    mu = np.rint(mu)
                tiles, fact = _centre_stack(
                    tiles, pad_cols(jnp.asarray(mu, jnp.float32), width),
                    m=m)
            mu = np.asarray(mu, dtype=np.float64)  # the query side's, as
            # an index from a host array holds it
            if onepass_applies(cfg) and bool(fact):
                onepass = jax.device_put(np.bool_(True))
        _stamp_index_gauges(cfg, onepass)
        tile_ids = _id_plane(m, (c_pad // c_tile, c_tile))
        tile_sqs = resident_norms(tiles, cfg.metric, offset)
        if offset is not None:
            # an empty slot's bytes are zeros, which widen to -offset: its
            # norm reads 0 all the same, as a float stack's padding has it
            tile_sqs = _live_norms(tile_sqs, tile_ids)
    return _serial_index(
        cfg, m, dim, c_tile, mu, tiles, tile_ids, tile_sqs, onepass,
        layout=BYTE_SERIAL if rest == jnp.uint8 else SERIAL,
        rest_offset=offset)
