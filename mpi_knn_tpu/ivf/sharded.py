"""Sharded clustered (IVF) index: the bucket store distributed over the
ring mesh with a routed candidate exchange — TPU-KNN's actual deployment
shape (PAPERS.md), and the first configuration in this repo that scales
corpus CAPACITY with devices while keeping per-query work SUBLINEAR.

Layout (derived, never stored — one saved index serves on any shard
count):

- the trained ``(P, d)`` centroid table and its norms are REPLICATED on
  every shard: routing is a small dot, and replicating it means every
  shard can score its own resident queries without a collective;
- the padded bucket store ``(P, cap, d)`` + ids + norms shard over the
  ring axis in CONTIGUOUS, capacity-balanced slices: every bucket has the
  same static ``bucket_cap``, so ``ceil(P / S)`` clusters per shard
  balances resident bytes exactly; cluster ``c`` lives on shard
  ``c // per_shard`` at local slot ``c % per_shard`` (padding clusters on
  the last shard carry id −1 rows and are unreachable — the routing table
  only has P real rows);
- query batches shard over the same axis: each device is the HOME shard
  of its resident query tiles.

Routed two-stage search, per query tile (all shapes static — the serving
bucket cache stays zero-recompile):

1. **score at home** — every shard scores the replicated centroid table
   for its resident tile (the shared ``ivf/search.score_centroids``:
   exact HIGHEST dot + static top-nprobe) → the routing table of
   ``(q_tile, nprobe)`` global partition ids;
2. **request exchange** — each (query, probe) pair is a ROUTE to the
   owning shard. Routes to the same owner are ranked PROBE-RANK-major
   (every query's rank-0 probe outranks any query's rank-1 probe, so a
   tight cap is spent on the highest-value probes tile-wide) and padded
   to the static per-(home, owner) ``route_cap`` (−1 = empty slot; ranks
   beyond the cap are DROPPED and counted — see
   ``KNNConfig.ivf_route_cap``); ONE static ``all_to_all`` delivers every
   shard its incoming request table;
3. **candidate exchange** — each owner gathers the requested buckets from
   its resident slice and three ``all_to_all``s return the
   ``(rows, ids, norms)`` tiles to the requesting home shards (rows
   travel at the at-rest dtype — a bf16 store halves exchange bytes,
   the EQuARX-cheap-collective direction);
4. **rerank at home** — the returned candidates are scattered back to
   ``(q_tile, nprobe·cap, d)`` in EXACTLY the probe order the
   single-device gather produces, then the shared
   ``ivf/search.finish_candidates`` runs: the mixed compress pass and the
   exact HIGHEST rerank are the same code as the single-device path, so
   ``precision_policy="mixed"`` composes and S=1 is bit-identical to the
   unsharded index.

Cost model: per query the exchange moves ≤ nprobe·cap·(d·itemsize + 8)
bytes and the rerank touches nprobe·cap·d elements — both independent of
P and m, while each shard's resident slice is m/S. Lint rule R2 runs in
STRICT mode per shard (the exchange + rerank working set is the declared
budget; the resident slice is exempt plumbing) and R4 accounts the
all-to-alls (count, full-ring replica groups, payload bytes ≤ the
declared exchange budget).

Per-shard exchange stats ride out of the program as a third output
``(3·S,)`` — [routed, dropped, served] per shard — aliased to a donated
scratch like the top-k carry, so R5's every-output-aliased contract
holds and the serving engine can stamp routed-candidate counters,
exchange bytes, and probe-cap overflow drops into the metrics registry
without an extra device program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_knn_tpu.backends.serial import TileCounts
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ivf.index import IVFIndex, IVFLayout, _refuse_inert_knobs
from mpi_knn_tpu.ivf.search import finish_candidates, score_centroids
from mpi_knn_tpu.ops.quant import (
    QUANT_DTYPES,
    dequantize_rows,
    row_wire_bytes,
)
from mpi_knn_tpu.ops.topk import init_topk_tiles, merge_topk
from mpi_knn_tpu.parallel.mesh import make_ring_mesh
from mpi_knn_tpu.parallel.partition import pad_to_multiple

# per-shard exchange stats vector: [routed (non-dropped probe routes this
# shard's resident queries issued), dropped (probe-cap overflow), served
# (real incoming requests this shard answered as owner)]
STATS_FIELDS = ("routed", "dropped", "served")
N_STATS = len(STATS_FIELDS)


def resolve_route_cap(cfg: KNNConfig, q_tile: int, nprobe: int) -> int:
    """The static per-(home, owner) route capacity for one query tile:
    ``cfg.ivf_route_cap`` clamped to the safe cap ``q_tile·nprobe`` (a
    bigger table could never fill), or the safe cap itself when unset
    (no probe can ever drop)."""
    safe = max(1, q_tile * nprobe)
    if cfg.ivf_route_cap is None:
        return safe
    return min(cfg.ivf_route_cap, safe)


def exchange_elems(shards: int, route_cap: int, cap: int, dim: int) -> int:
    """Largest single exchange buffer of one tile's candidate exchange, in
    elements — the (S, route_cap, cap, d) candidate-rows all-to-all (the
    ids/norms tables are d× smaller). This is what R2's strict per-shard
    budget must cover beyond the rerank working set."""
    return shards * route_cap * cap * dim


def exchange_bytes_per_tile(
    shards: int, route_cap: int, cap: int, dim: int, itemsize: int,
    scale_bytes: int = 0,
) -> int:
    """Total bytes the exchange all-to-alls of ONE query tile move per
    shard: the s32 request table plus rows (at-rest width — a quantized
    store's rows are its int8 code lanes, so callers pass the PACKED dim
    and itemsize 1) + ids (s32) + norms (f32) + ``scale_bytes`` (4 for a
    quantized store's per-row f32 scale, which rides its own all-to-all)
    per route. Static per executable — the serving engine stamps it into
    the exchange-bytes counter without reading the device, and R4 holds
    the compiled payload to it at the WIRE dtype."""
    per_route = 4 + cap * (dim * itemsize + 4 + 4 + scale_bytes)
    return shards * route_cap * per_route


def exchange_wire_args(index) -> tuple[int, int, int]:
    """(dim_lanes, itemsize, scale_bytes) of one candidate row on the
    exchange wire for an index — the adapter every
    :func:`exchange_bytes_per_tile` caller shares so the declared budget
    always prices the store that actually ships."""
    if getattr(index, "store_dtype", None) in QUANT_DTYPES:
        return index.buckets.shape[-1], 1, 4
    return index.dim, index.buckets.dtype.itemsize, 0


def expected_exchange_alltoalls(index) -> int:
    """Collectives of one routed tile: the request table + the
    rows/ids/norms returns (4), plus the scale-table return of a
    quantized store (5) — the count R4 pins in the lowered program."""
    return 5 if getattr(index, "store_dtype", None) in QUANT_DTYPES else 4


def sharded_query_shapes(
    cfg: KNNConfig, nprobe: int, bucket_cap: int, dim: int, nq: int,
    shards: int,
) -> tuple[int, int, int]:
    """(q_tile, q_pad, route_cap) for a sharded batch: q_tile shrinks
    until BOTH the per-tile rerank working set (q_tile·nprobe·cap·d) and
    the exchange buffer (shards·route_cap·cap·d) fit
    ``cfg.max_tile_elems`` — the same hard per-step bound the dense and
    single-device IVF paths enforce, applied to this path's dominant
    intermediates. q_pad is a multiple of shards·q_tile so every shard
    holds the same number of whole tiles (the SPMD program needs equal
    trip counts)."""
    per_row = max(1, nprobe * bucket_cap * dim)
    q_tile = min(cfg.query_tile, pad_to_multiple(max(1, -(-nq // shards)), 8))

    def biggest(qt: int) -> int:
        rc = resolve_route_cap(cfg, qt, nprobe)
        return max(qt * per_row, exchange_elems(shards, rc, bucket_cap, dim))

    while q_tile > 1 and biggest(q_tile) > cfg.max_tile_elems:
        q_tile = max(1, q_tile // 2)
    if biggest(q_tile) > cfg.max_tile_elems:
        raise ValueError(
            f"one sharded query tile's working set ({biggest(q_tile)} "
            f"elems: nprobe={nprobe} × bucket_cap={bucket_cap} × d={dim} "
            f"per row, exchanged over {shards} shards) exceeds "
            f"max_tile_elems={cfg.max_tile_elems}; lower nprobe/"
            "partitions, set a smaller ivf_route_cap, raise "
            "max_tile_elems, or serve unsharded"
        )
    q_pad = pad_to_multiple(nq, shards * q_tile)
    return q_tile, q_pad, resolve_route_cap(cfg, q_tile, nprobe)


def routed_query_tile(
    q_x: jax.Array,  # (q_tile, d) resident query tile (home shard)
    q_ids: jax.Array,  # (q_tile,)
    centroids: jax.Array,  # (P, d) replicated routing table
    centroid_sqs: jax.Array,  # (P,)
    buckets: jax.Array,  # (per_shard, cap, d) THIS shard's slice —
    # (per_shard, cap, pd) int8 code lanes for a quantized store
    bucket_ids: jax.Array,  # (per_shard, cap)
    bucket_sqs: jax.Array,  # (per_shard, cap)
    bucket_scales: jax.Array | None,  # (per_shard, cap) f32, quantized only
    cfg: KNNConfig,
    nprobe: int,
    axis: str,
    shards: int,
    route_cap: int,
):
    """One resident query tile through the routed two-stage search →
    ((q_tile, k) dists, ids, (N_STATS,) int32 stats). Runs inside
    shard_map: every shard executes this body over its own tile while
    serving its peers' bucket requests through the same four static
    all-to-alls."""
    acc = jnp.float32
    q_x = q_x.astype(acc)
    q_sq, probe = score_centroids(q_x, centroids, centroid_sqs, nprobe)

    per_shard, cap = buckets.shape[0], buckets.shape[1]
    qt = q_x.shape[0]
    n = qt * nprobe
    # routes are prioritized PROBE-RANK-major (every query's rank-0 probe
    # outranks any query's rank-1 probe at the same owner): under cap
    # pressure the cap is spent on the highest-value probes across the
    # whole tile, and a query can lose ALL its probes only when an
    # owner's rank-0 demand alone exceeds the cap — not merely because
    # an earlier query spent the budget on its low-value probes
    flat_t = probe.T.reshape(n)  # route t = j·qt + q (probe-rank major)
    dest_t = flat_t // per_shard  # owning shard of each route
    slot_t = (flat_t % per_shard).astype(jnp.int32)
    # rank of each route within its destination group, in priority order
    # (cumsum over one-hot — deterministic, stable, n·S ops); ranks
    # beyond route_cap are dropped (and counted), never mis-sent
    onehot = (
        dest_t[:, None] == jnp.arange(shards, dtype=dest_t.dtype)
    ).astype(jnp.int32)
    rank_t = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(n), dest_t]
    dropped_t = rank_t >= route_cap

    # request exchange: row s of the (S, route_cap) table is this home
    # shard's request list for owner s; after the all-to-all, row s is
    # the request list FROM home shard s against our resident slice
    req = jnp.full((shards, route_cap), -1, jnp.int32)
    req = req.at[dest_t, jnp.where(dropped_t, route_cap, rank_t)].set(
        slot_t, mode="drop"
    )
    req_in = jax.lax.all_to_all(req, axis, 0, 0, tiled=True)

    # owner side: gather the requested buckets from the resident slice
    # (empty slots gather slot 0 but their ids are masked to −1, which
    # the shared mask_tile semantics turn into +inf candidates)
    take = jnp.clip(req_in, 0, per_shard - 1)
    rows_out = buckets[take]  # (S, route_cap, cap, d|pd) at-rest dtype
    ids_out = jnp.where((req_in < 0)[..., None], -1, bucket_ids[take])
    sqs_out = bucket_sqs[take]

    # candidate exchange: after these, row s holds owner s's answers to
    # OUR requests — rows travel at the at-rest dtype (bf16 store = half
    # the exchange bytes; a quantized store ships its int8 code lanes at
    # a 4–8× cut, with the per-row scale table riding a fifth, d×-smaller
    # all-to-all — ids and norms are unchanged)
    rows_home = jax.lax.all_to_all(rows_out, axis, 0, 0, tiled=True)
    ids_home = jax.lax.all_to_all(ids_out, axis, 0, 0, tiled=True)
    sqs_home = jax.lax.all_to_all(sqs_out, axis, 0, 0, tiled=True)
    scl_home = None
    if bucket_scales is not None:
        scl_home = jax.lax.all_to_all(
            bucket_scales[take], axis, 0, 0, tiled=True
        )

    # scatter back to per-query candidate tiles in QUERY-major flat probe
    # order — the exact (q_tile, nprobe·cap) layout the single-device
    # gather produces, so the shared finish is bit-compatible; dropped
    # routes point at a clamped slot with ids forced to −1. t_of maps the
    # query-major flat index f = q·nprobe + j back to its priority-order
    # position t = j·qt + q.
    t_of = (jnp.arange(n) % nprobe) * qt + jnp.arange(n) // nprobe
    dest, rank, dropped = dest_t[t_of], rank_t[t_of], dropped_t[t_of]
    src = dest * route_cap + jnp.minimum(rank, route_cap - 1)
    rows = rows_home.reshape(shards * route_cap, cap, -1)[src]
    ids = jnp.where(
        dropped[:, None], -1, ids_home.reshape(shards * route_cap, cap)[src]
    )
    sqs = sqs_home.reshape(shards * route_cap, cap)[src]
    v = nprobe * cap
    rows = rows.reshape(qt, v, rows.shape[-1])
    if scl_home is not None:
        # dequantize AT HOME, after the scatter: the exchange moved only
        # code lanes; the f32 candidate rows exist for exactly one tile's
        # finish (the same asymmetric-distance shape as the single-device
        # quantized gather, so the shared finish stays bit-compatible)
        scl = scl_home.reshape(shards * route_cap, cap)[src].reshape(qt, v)
        rows = dequantize_rows(rows, scl, cfg.dtype, q_x.shape[1])
    rows = rows.astype(acc)
    d_out, i_out = finish_candidates(
        q_x, q_ids, q_sq, rows, ids.reshape(qt, v), sqs.reshape(qt, v), cfg
    )
    stats = jnp.stack([
        jnp.sum(~dropped).astype(jnp.int32),
        jnp.sum(dropped).astype(jnp.int32),
        jnp.sum(req_in >= 0).astype(jnp.int32),
    ])
    return d_out, i_out, stats


def ivf_sharded_serve_chunk(
    q_tiles: jax.Array,  # (QT, q_tile, d) one padded batch, q-sharded
    qid_tiles: jax.Array,  # (QT, q_tile)
    carry_d: jax.Array,  # (QT, q_tile, k) donated scratch
    carry_i: jax.Array,
    stats_scratch: jax.Array,  # (N_STATS·S,) donated zeros
    centroids: jax.Array,  # (P, d) replicated
    centroid_sqs: jax.Array,
    buckets: jax.Array,  # (S·per_shard, cap, d|pd) sharded over axis
    bucket_ids: jax.Array,
    bucket_sqs: jax.Array,
    bucket_scales: jax.Array | None,  # sharded like buckets, quantized only
    cfg: KNNConfig,
    nprobe: int,
    mesh: Mesh,
    axis: str,
    shards: int,
    route_cap: int,
):
    """One serving batch against a resident :class:`ShardedIVFIndex` —
    the engine's uniform (queries, query_ids, carry_d, carry_i, <scratch>,
    <resident…>) convention with the stats vector as a THIRD donated
    scratch (``donate_argnums=(2, 3, 4)``): every output aliases a
    donated input, so R5's contract holds with the stats riding along."""
    qspec = P(axis)

    def per_shard_search(qt, qidt, cd, ci, st, cent, cent_sq, bks, bids,
                         bsqs, bscls):
        def per_tile(args):
            q_x, q_ids, cd0, ci0 = args
            d, i, ts = routed_query_tile(
                q_x, q_ids, cent, cent_sq, bks, bids, bsqs, bscls,
                cfg, nprobe, axis, shards, route_cap,
            )
            d2, i2 = merge_topk(
                cd0, ci0, d.astype(cd0.dtype), i, method="exact"
            )
            return d2, i2, ts

        d, i, ts = jax.lax.map(per_tile, (qt, qidt, cd, ci))
        # dtype pinned: under x64 an un-annotated integer sum promotes to
        # int64, and a widened stats output could not alias its donated
        # int32 scratch (R5 would rightly flag the dropped donation)
        return d, i, st + jnp.sum(ts, axis=0, dtype=jnp.int32)

    if bucket_scales is None:

        def shard_body(qt, qidt, cd, ci, st, cent, cent_sq, bks, bids,
                       bsqs):
            return per_shard_search(
                qt, qidt, cd, ci, st, cent, cent_sq, bks, bids, bsqs, None
            )

        fn = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(qspec, qspec, qspec, qspec, qspec, P(), P(),
                      qspec, qspec, qspec),
            out_specs=(qspec, qspec, qspec),
        )
        return fn(
            q_tiles, qid_tiles, carry_d, carry_i, stats_scratch,
            centroids, centroid_sqs, buckets, bucket_ids, bucket_sqs,
        )

    fn = jax.shard_map(
        per_shard_search,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, qspec, qspec, P(), P(),
                  qspec, qspec, qspec, qspec),
        out_specs=(qspec, qspec, qspec),
    )
    return fn(
        q_tiles, qid_tiles, carry_d, carry_i, stats_scratch,
        centroids, centroid_sqs, buckets, bucket_ids, bucket_sqs,
        bucket_scales,
    )


_ivf_sharded_jit = jax.jit(
    ivf_sharded_serve_chunk,
    static_argnames=("cfg", "nprobe", "mesh", "axis", "shards", "route_cap"),
)


# ---------------------------------------------------------------------------
# The resident sharded index


class ShardedIVFLayout(IVFLayout):
    """The clustered batch program under ``shard_map``: a prepared batch
    is already (qt, q_tile, d) on the query sharding, and the per-shard
    exchange stats ride as a THIRD donated scratch so all three outputs
    alias donated inputs."""

    static_argnames = ("cfg", "nprobe", "mesh", "axis", "shards", "route_cap")
    donate_argnums = (2, 3, 4)
    pretiled = True
    exchange_stats = True

    def serve_fn(self):
        return ivf_sharded_serve_chunk

    def _shapes(self, index, cfg, bucket):
        return sharded_query_shapes(
            cfg, cfg.nprobe, index.bucket_cap, index.dim, bucket, index.shards
        )

    def bucket_shapes(self, index, cfg, bucket):
        q_tile, q_pad, _ = self._shapes(index, cfg, bucket)
        return q_pad, q_tile

    def resident(self, index):
        # (no one-pass fact: the routed tile keeps the row-major program)
        return (index.centroids, index.centroid_sqs, index.buckets,
                index.bucket_ids, index.bucket_sqs, index.bucket_scales)

    def statics(self, index, cfg, bucket):
        return dict(
            cfg=cfg, nprobe=cfg.nprobe, mesh=index.mesh, axis=index.axis,
            shards=index.shards,
            route_cap=self._shapes(index, cfg, bucket)[2],
        )

    def query_sharding(self, index):
        return NamedSharding(index.mesh, P(index.axis))

    def counts_scratch(self, index):
        return jax.ShapeDtypeStruct(
            (N_STATS * index.shards,), jnp.int32,
            sharding=self.query_sharding(index),
        )

    def batch_counts(self, index, q_pad, q_tile, rest):
        return TileCounts()

    def carry_maker(self, index, cfg, q_pad, q_tile):
        return scratch_maker(
            q_pad // q_tile, q_tile, cfg.k, index.shards, index.mesh,
            index.axis,
        )

    def exchange_bytes(self, index, cfg, bucket, q_pad, q_tile):
        return (q_pad // q_tile) * exchange_bytes_per_tile(
            index.shards, self._shapes(index, cfg, bucket)[2],
            index.bucket_cap, *exchange_wire_args(index),
        )


@dataclasses.dataclass
class ShardedIVFIndex:
    """Mesh-resident sharded clustered index. Duck-types the engine corner
    of :class:`~mpi_knn_tpu.ivf.index.IVFIndex` (``layout``/``backend``/
    ``cfg``/``mu``/``m``/``dim``/``_cache``/``compatible_cfg``/
    ``nbytes_resident``) so the bucketed AOT executable cache,
    ``ServeSession`` and ``api.query_knn`` serve it unchanged."""

    cfg: KNNConfig  # resolved: backend="serial", concrete nprobe + shards
    m: int
    dim: int
    partitions: int
    bucket_cap: int
    nprobe: int
    mu: object | None
    shards: int
    per_shard: int  # clusters per shard (incl. derived padding clusters)
    mesh: Mesh
    axis: str
    centroids: jax.Array  # (P, d) replicated on every shard
    centroid_sqs: jax.Array  # (P,) replicated
    buckets: jax.Array  # (S·per_shard, cap, d|pd) sharded over the ring axis
    bucket_ids: jax.Array  # (S·per_shard, cap) sharded
    bucket_sqs: jax.Array  # (S·per_shard, cap) sharded
    bucket_scales: jax.Array | None = None  # sharded; quantized stores only
    tuned_recall: float | None = None
    backend: str = "ivf-sharded"
    layout = ShardedIVFLayout()  # one for the kind: a class attribute
    _cache: dict = dataclasses.field(default_factory=dict)

    @property
    def store_dtype(self) -> str:
        """The at-rest level of the bucket store (cfg.dtype by the build
        contract)."""
        return self.cfg.dtype

    @property
    def nbytes_resident(self) -> int:
        """Bytes of resident corpus payload across ALL shards (the global
        bucket store incl. derived padding clusters, plus a quantized
        store's scale table)."""
        n = self.buckets.size * self.buckets.dtype.itemsize
        if self.bucket_scales is not None:
            n += self.bucket_scales.size * self.bucket_scales.dtype.itemsize
        return n

    @property
    def shard_nbytes_resident(self) -> int:
        """Bytes of ONE shard's resident bucket slice — the denominator of
        the per-shard probed-bytes claim."""
        return self.nbytes_resident // self.shards

    @property
    def probe_bytes(self) -> int:
        """Bytes one query row's routed probe touches at the index-default
        nprobe — identical to the single-device bound (the routing moves
        the same nprobe buckets, just across the mesh), priced at the
        at-rest wire width."""
        return self.nprobe * self.bucket_cap * row_wire_bytes(
            self.dim,
            self.store_dtype if self.store_dtype in QUANT_DTYPES else None,
            self.buckets.dtype.itemsize,
        )

    def compatible_cfg(self, cfg: KNNConfig) -> KNNConfig:
        """Validate a per-query config against the sharded layout: the
        single-device corpus-side freeze plus ``ivf_shards`` (the layout
        is derived from it — serving a 4-shard index with a 2-shard
        config would route to devices that do not hold the clusters).
        ``ivf_route_cap`` is query-side: it shapes the exchange program
        only, and the executable cache keys on the full config."""
        frozen = (
            "backend", "metric", "dtype", "partitions", "kmeans_iters",
            "kmeans_init", "ivf_seed", "center", "exclude_zero", "zero_eps",
            "ivf_shards",
        )
        want = cfg if cfg.backend != "auto" else cfg.replace(backend="serial")
        bad = [
            f for f in frozen
            if getattr(want, f) != getattr(self.cfg, f)
        ]
        if bad:
            raise ValueError(
                "query config changes corpus-side knobs baked into this "
                f"sharded clustered index: {bad}; build (or re-shard) a "
                "new index, or override only query-side knobs: k/nprobe/"
                "precision_policy/ivf_route_cap/query_tile/query_bucket/"
                "dispatch_depth/donate"
            )
        _refuse_inert_knobs(want)
        if want.nprobe is None:
            want = want.replace(nprobe=self.nprobe)
        return want


def shard_ivf_index(
    index: IVFIndex,
    shards: int | None = None,
    mesh: Mesh | None = None,
    route_cap: int | None = None,
) -> ShardedIVFIndex:
    """Distribute a trained single-device :class:`IVFIndex` over the ring
    mesh. The shard layout is DERIVED here from (partitions, shards) —
    nothing about it is stored in the index, so one ``save_ivf_index``
    artifact serves on any shard count (bit-compatibly: the per-query
    candidate tiles and every dot shape are shard-count-independent).

    Args:
      index: a trained (or loaded) single-device clustered index.
      shards: ring size; default ``index.cfg.ivf_shards`` or the mesh
        size or all visible devices.
      mesh: optional 1-D mesh to place on (defaults to the first
        ``shards`` visible devices).
      route_cap: optional ``KNNConfig.ivf_route_cap`` override recorded
        on the index's default config.
    """
    if shards is None:
        shards = (
            index.cfg.ivf_shards
            if index.cfg.ivf_shards is not None
            else (mesh.devices.size if mesh is not None
                  else len(jax.devices()))
        )
    if shards < 1:
        raise ValueError(f"ivf_shards must be >= 1, got {shards}")
    if mesh is None:
        mesh = make_ring_mesh(shards, axis_name=index.cfg.mesh_axis)
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"the sharded clustered index wants a 1-D ring mesh, got axes "
            f"{mesh.axis_names} (the candidate exchange is one all-to-all "
            "group over the ring axis)"
        )
    if mesh.devices.size != shards:
        raise ValueError(
            f"mesh has {mesh.devices.size} device(s) but ivf_shards="
            f"{shards}; build the mesh over exactly the shard count"
        )
    axis = mesh.axis_names[0]
    P_real = index.partitions
    per_shard = -(-P_real // shards)
    P_pad = per_shard * shards

    # host-staged slice + pad of the cluster axis, then ONE device_put
    # per array onto its layout — the plain index's device arrays are not
    # kept alive (callers may drop the unsharded copy)
    quantized = index.cfg.dtype in QUANT_DTYPES
    buckets = np.asarray(index.buckets)
    bids = np.asarray(index.bucket_ids)
    bsqs = np.asarray(index.bucket_sqs)
    bscl = (
        np.asarray(index.bucket_scales)
        if index.bucket_scales is not None else None
    )
    if P_pad > P_real:
        padc = P_pad - P_real
        buckets = np.concatenate(
            [buckets, np.zeros((padc,) + buckets.shape[1:], buckets.dtype)]
        )
        bids = np.concatenate(
            [bids, np.full((padc,) + bids.shape[1:], -1, bids.dtype)]
        )
        bsqs = np.concatenate(
            [bsqs, np.zeros((padc,) + bsqs.shape[1:], bsqs.dtype)]
        )
        if bscl is not None:
            bscl = np.concatenate(
                [bscl, np.zeros((padc,) + bscl.shape[1:], bscl.dtype)]
            )
    csh = NamedSharding(mesh, P(axis))
    rsh = NamedSharding(mesh, P())  # replicated
    cfg = index.cfg.replace(
        ivf_shards=shards,
        ivf_route_cap=(route_cap if route_cap is not None
                       else index.cfg.ivf_route_cap),
    )
    if quantized:
        # the codes are ALREADY the at-rest representation — a cast here
        # would corrupt them; they shard verbatim alongside their scales
        buckets_dev = jax.device_put(jnp.asarray(buckets), csh)
    else:
        buckets_dev = jax.device_put(
            jnp.asarray(buckets).astype(jnp.dtype(index.cfg.dtype)), csh
        )
    return ShardedIVFIndex(
        cfg=cfg,
        m=index.m,
        dim=index.dim,
        partitions=P_real,
        bucket_cap=index.bucket_cap,
        nprobe=index.nprobe,
        mu=index.mu,
        shards=shards,
        per_shard=per_shard,
        mesh=mesh,
        axis=axis,
        centroids=jax.device_put(np.asarray(index.centroids), rsh),
        centroid_sqs=jax.device_put(np.asarray(index.centroid_sqs), rsh),
        buckets=buckets_dev,
        bucket_ids=jax.device_put(bids, csh),
        bucket_sqs=jax.device_put(bsqs, csh),
        bucket_scales=(
            jax.device_put(bscl, csh) if bscl is not None else None
        ),
        tuned_recall=index.tuned_recall,
    )


def unshard_ivf_index(index: ShardedIVFIndex) -> IVFIndex:
    """The plain single-device view of a sharded index (host gather, strip
    the derived padding clusters) — what ``save_ivf_index`` persists, so
    a sharded build round-trips through the SAME .npz as an unsharded one
    and reloads on any shard count."""
    Pn = index.partitions
    return IVFIndex(
        cfg=index.cfg.replace(ivf_shards=None, ivf_route_cap=None),
        m=index.m,
        dim=index.dim,
        partitions=Pn,
        bucket_cap=index.bucket_cap,
        nprobe=index.nprobe,
        mu=index.mu,
        centroids=jnp.asarray(np.asarray(index.centroids)),
        centroid_sqs=jnp.asarray(np.asarray(index.centroid_sqs)),
        buckets=jnp.asarray(np.asarray(index.buckets)[:Pn]),
        bucket_ids=jnp.asarray(np.asarray(index.bucket_ids)[:Pn]),
        bucket_sqs=jnp.asarray(np.asarray(index.bucket_sqs)[:Pn]),
        bucket_scales=(
            jnp.asarray(np.asarray(index.bucket_scales)[:Pn])
            if index.bucket_scales is not None else None
        ),
        tuned_recall=index.tuned_recall,
    )


def build_sharded_ivf_index(
    corpus,
    config: KNNConfig | None = None,
    mesh: Mesh | None = None,
    **overrides,
) -> ShardedIVFIndex:
    """Train the k-means partitioner (single-device math — clustering is
    layout-independent) and distribute the result over the ring mesh.
    ``cfg.ivf_shards`` must be set; ``nprobe=None`` auto-tunes on the
    single-device index before sharding (recall is layout-independent at
    the safe route cap, so the tuned number transfers)."""
    from mpi_knn_tpu.ivf.index import build_ivf_index

    cfg = (config or KNNConfig()).replace(**overrides)
    if cfg.ivf_shards is None:
        raise ValueError(
            "building a sharded clustered index requires ivf_shards "
            "(KNNConfig.ivf_shards); for a single-device index use "
            "build_ivf_index"
        )
    plain = build_ivf_index(
        corpus, cfg.replace(ivf_shards=None, ivf_route_cap=None)
    )
    return shard_ivf_index(
        plain, shards=cfg.ivf_shards, mesh=mesh,
        route_cap=cfg.ivf_route_cap,
    )


# ---------------------------------------------------------------------------
# One-shot search (prepare/run split for the bench's timer placement)


def prepare_sharded_tiles(index: ShardedIVFIndex, queries, query_ids,
                          cfg: KNNConfig, assume_centered: bool = False):
    """Host-side half of :func:`search_ivf_sharded`: center with the
    index's stored mean, pad to shards·q_tile and tile, place the tiles
    on the query sharding. Returns (q_tiles, qid_tiles, q_pad, q_tile,
    route_cap)."""
    queries = np.asarray(queries)
    nq = queries.shape[0]
    if query_ids is None:
        q_ids = np.full(nq, -1, dtype=np.int32)
    else:
        q_ids = np.asarray(query_ids, dtype=np.int32)
    if cfg.center and index.mu is not None and not assume_centered:
        queries = queries - index.mu
    q_tile, q_pad, route_cap = sharded_query_shapes(
        cfg, cfg.nprobe, index.bucket_cap, index.dim, nq, index.shards
    )
    qt = q_pad // q_tile
    qsh = NamedSharding(index.mesh, P(index.axis))
    q_tiles = jax.device_put(
        np.pad(queries.astype(np.float32), ((0, q_pad - nq), (0, 0)))
        .reshape(qt, q_tile, index.dim),
        qsh,
    )
    qid_tiles = jax.device_put(
        np.pad(q_ids, (0, q_pad - nq), constant_values=-1)
        .reshape(qt, q_tile),
        qsh,
    )
    return q_tiles, qid_tiles, q_pad, q_tile, route_cap


@functools.lru_cache(maxsize=None)
def scratch_maker(qt: int, q_tile: int, k: int, shards: int, mesh: Mesh,
                  axis: str):
    """A once-compiled maker of the (carry_d, carry_i, stats) donated
    scratch, born directly under the query sharding (the ring-serve
    trick: building on the default device and resharding would pay an
    allocate-then-copy on every batch) — cached so repeated one-shot
    calls and the serving engine share one executable per shape."""
    qsh = NamedSharding(mesh, P(axis))
    return jax.jit(
        functools.partial(_sharded_scratch, qt, q_tile, k, shards),
        out_shardings=(qsh, qsh, qsh),
    )


def run_sharded_tiles(index: ShardedIVFIndex, q_tiles, qid_tiles,
                      cfg: KNNConfig, route_cap: int):
    """Device half: fresh sharded carries + the jitted routed search.
    Returns padded ((QT, q_tile, k) dists, ids, (N_STATS·S,) stats)
    device arrays (not synchronized)."""
    qt, q_tile = q_tiles.shape[0], q_tiles.shape[1]
    carry_d, carry_i, stats = scratch_maker(
        qt, q_tile, cfg.k, index.shards, index.mesh, index.axis
    )()
    return _ivf_sharded_jit(
        q_tiles, qid_tiles, carry_d, carry_i, stats,
        index.centroids, index.centroid_sqs, index.buckets,
        index.bucket_ids, index.bucket_sqs, index.bucket_scales,
        cfg, cfg.nprobe, index.mesh, index.axis, index.shards, route_cap,
    )


def _sharded_scratch(qt: int, q_tile: int, k: int, shards: int):
    carry_d, carry_i = init_topk_tiles(qt, q_tile, k, dtype=jnp.float32)
    return carry_d, carry_i, jnp.zeros(N_STATS * shards, jnp.int32)


def search_ivf_sharded(index: ShardedIVFIndex, queries, query_ids=None,
                       config=None, assume_centered=False, **overrides):
    """One-shot query batch against a :class:`ShardedIVFIndex` (no
    executable cache — the serving engine owns that). Returns
    ((q, k) dists ascending, (q, k) ids, per-shard stats (S, N_STATS))
    as numpy arrays."""
    cfg = index.compatible_cfg((config or index.cfg).replace(**overrides))
    nq = np.shape(queries)[0]
    q_tiles, qid_tiles, q_pad, _, route_cap = prepare_sharded_tiles(
        index, queries, query_ids, cfg, assume_centered=assume_centered
    )
    d, i, stats = run_sharded_tiles(index, q_tiles, qid_tiles, cfg, route_cap)
    return (
        np.asarray(d.reshape(q_pad, cfg.k)[:nq]),
        np.asarray(i.reshape(q_pad, cfg.k)[:nq]),
        np.asarray(stats).reshape(index.shards, N_STATS),
    )
