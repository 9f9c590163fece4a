"""Static-shape live mutation of clustered indices: freelist slot math,
donated in-place upsert/delete scatters, and the re-cluster/compact
rebuild (the ISSUE 14 tentpole's clustered half).

Why headroom buys static shapes: every TPU-KNN program in this repo is
fast *because* its shapes are frozen (one AOT executable per cell, zero
steady-state compiles). A growing corpus would normally force new shapes
— so instead each bucket is built with spare capacity
(``KNNConfig.bucket_headroom``: ``bucket_cap = pad(max_cluster · (1 +
headroom))``), and mutation happens INSIDE the fixed shapes:

- **upsert** — the new row's partition comes from the same exact-HIGHEST
  centroid score the build assignment and the stage-1 routing use; a
  free slot comes from the host-side per-bucket freelist; the device
  program is ONE donated in-place scatter over the resident store
  (rows + ids + norms + scales), so a million-row index absorbs an
  upsert at the cost of the touched bucket rows, never a corpus-sized
  copy (machine-checked: lint R5 reads ``input_output_alias`` and a
  copy census off the compiled program, R2-strict budgets the
  touched-chunk working set);
- **delete** — a tombstone: the slot's id goes to −1, which the standard
  ``mask_tile`` semantics already treat as "never an answer" (the stale
  row data keeps riding the fixed-shape FLOPs, masked). The freelist
  gets the slot back, so a later upsert reclaims it in place;
- **compact** — when headroom runs low or tombstones accumulate
  (``compact_fill_threshold`` / ``compact_tombstone_fraction``), the
  background pass re-clusters: k-means retrained on a deterministic
  sample of the LIVE rows, every slot re-assigned on device
  (``compact_assign``), and the store rebuilt by ONE donated scatter
  from the old resident arrays into fresh ones (``compact_scatter``) —
  row payload never round-trips the host. ``bucket_cap`` is kept
  whenever the live set still fits (so every serve/mutation executable
  stays valid — compaction is invisible to the cache) and grows only
  when it must (the documented recompile path).

Chunk programs pad to ``mutation_bucket · 2^j`` rows (the serve bucket
discipline applied to mutation), with padding rows carrying an
out-of-range partition index: the scatters run in ``mode='drop'`` so
padding is a true no-op, bit-identically.

The freelist is HOST state (a mirror of ``bucket_ids``), deterministic
(lowest free slot first) and derivable from any saved artifact — a
legacy pre-mutation ``.npz`` loads with its full padding reclaimed as
headroom, because "free slot" and "id −1 slot" are the same thing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.distance import (
    cosine_inv_norms,
    pairwise_sq_l2,
    sq_norms,
)
from mpi_knn_tpu.ops.quant import QUANT_DTYPES, dequantize_rows, quantize_rows


class BucketOverflowError(RuntimeError):
    """An upsert chunk needs more slots than some bucket has free — the
    headroom is exhausted for those partitions. Carries the partitions so
    the caller (``ServeSession.upsert`` / the compactor) can compact and
    retry instead of guessing."""

    def __init__(self, msg: str, partitions=()):
        super().__init__(msg)
        self.partitions = tuple(partitions)


# ---------------------------------------------------------------------------
# Freelist — the host mirror of slot occupancy


class Freelist:
    """The host mirror of slot occupancy, in array form: which slots are
    used (``used``, one bool a slot), how many are free in each bucket
    (``free_count``), and each live id's flat slot ``partition * cap +
    slot`` (a table indexed by id). Made with numpy from one fetch of the
    id plane: at 10 M rows a fraction of a second and ~60 MB, where one
    dict entry and one tuple a row took tens of seconds and gigabytes.

    Derived from ``bucket_ids`` (id −1 = free), never stored: any saved
    artifact — including pre-mutation ones — reconstructs it exactly.
    Slot allocation is deterministic (lowest free slot first), so a
    mutation replayed against a reloaded index lands every row in the
    same slot.

    ``tombstones`` counts deleted-not-yet-reused slots (an upsert that
    reclaims a tombstoned slot decrements it); the compaction triggers
    read ``max_fill`` and ``tombstone_fraction`` from here.

    The table covers ids below ``dense_limit`` (eight times the slots,
    at least 2**22: ids that follow the corpus's size); an id beyond it
    goes into a dict, the one per-row path left, for callers that hash
    their ids over all of int32.
    """

    def __init__(self, bucket_ids: np.ndarray, partitions: int):
        ids = np.asarray(bucket_ids)
        self.partitions = int(partitions)  # REAL partitions (a sharded
        # store's derived padding clusters hold no centroids and can
        # never be assigned to — they contribute no capacity)
        # the scatter drop sentinel: one past the STORE's bucket count
        # (a sharded store is padded past `partitions` — an index at the
        # real partition count would land in a padding cluster, so drop
        # must be out of range of the padded store)
        self.total = int(ids.shape[0])
        self.cap = int(ids.shape[1])
        real = ids[: self.partitions]
        self.used = real >= 0
        self.free_count = self.cap - self.used.sum(axis=1, dtype=np.int64)
        flat = np.flatnonzero(self.used.reshape(-1))
        live_ids = real.reshape(-1)[flat]
        self.live = int(flat.size)
        self.dense_limit = max(1 << 22, 8 * self.total * self.cap)
        self._far: dict[int, int] = {}
        if self.live and int(live_ids.max()) >= self.dense_limit:
            far = live_ids >= self.dense_limit
            self._far = dict(zip(live_ids[far].tolist(), flat[far].tolist()))
            flat, live_ids = flat[~far], live_ids[~far]
        # (flat slots fit int32 up to 2**31 slots: 4 bytes an id)
        self._slot = np.full(
            int(live_ids.max()) + 1 if live_ids.size else 0, -1,
            np.int32 if self.total * self.cap < 2**31 else np.int64)
        self._slot[live_ids] = flat
        self.tombstones = 0
        self._tomb_free = np.zeros(self.partitions, np.int64)

    @property
    def nbytes(self) -> int:
        """Host bytes of the mirror's arrays."""
        return int(self.used.nbytes + self.free_count.nbytes
                   + self._slot.nbytes + self._tomb_free.nbytes)

    def lookup(self, ids) -> np.ndarray:
        """Flat slot of each id (int64), −1 where the id is not live."""
        ids = np.asarray(ids, dtype=np.int64).reshape(-1)
        out = np.full(ids.shape[0], -1, np.int64)
        near = (ids >= 0) & (ids < self._slot.shape[0])
        out[near] = self._slot[ids[near]]
        if self._far:
            for i in np.flatnonzero(ids >= self.dense_limit):
                out[i] = self._far.get(int(ids[i]), -1)
        return out

    def where(self, rid: int):
        """(partition, slot) of one live id, else None."""
        flat = int(self.lookup([rid])[0])
        return None if flat < 0 else divmod(flat, self.cap)

    def live_ids(self) -> np.ndarray:
        """Every live id, ascending."""
        near = np.flatnonzero(self._slot >= 0)
        if not self._far:
            return near
        return np.concatenate([near, np.sort(np.fromiter(
            self._far, np.int64, len(self._far)))])

    def lowest_free(self, partition: int, count: int = 1) -> np.ndarray:
        """The ``count`` lowest free slots of one bucket."""
        return np.flatnonzero(~self.used[partition])[:count]

    def _record(self, ids: np.ndarray, flat: np.ndarray) -> None:
        near = ids < self.dense_limit
        if near.any():
            top = int(ids[near].max()) + 1
            if top > self._slot.shape[0]:
                grown = np.full(
                    min(self.dense_limit, max(top, 2 * self._slot.shape[0])),
                    -1, self._slot.dtype)
                grown[: self._slot.shape[0]] = self._slot
                self._slot = grown
            self._slot[ids[near]] = flat[near]
        for i in np.flatnonzero(~near):
            self._far[int(ids[i])] = int(flat[i])

    def occupy(self, ids: np.ndarray, flat: np.ndarray) -> None:
        """Commit an allocation: ``ids`` now live at free slots ``flat``
        (an id that was live elsewhere has had its old slot released by
        the caller)."""
        was = self.lookup(ids)
        self.used.reshape(-1)[flat] = True
        parts, counts = np.unique(flat // self.cap, return_counts=True)
        self.free_count[parts] -= counts
        reused = np.minimum(self._tomb_free[parts], counts)
        self._tomb_free[parts] -= reused
        self.tombstones -= int(reused.sum())
        self._record(ids, flat)
        self.live += int((was < 0).sum())

    def release(self, flat: np.ndarray, ids: np.ndarray | None = None
                ) -> None:
        """Commit tombstones: slots ``flat`` are free again; ``ids``
        (where given) leave the index."""
        self.used.reshape(-1)[flat] = False
        parts, counts = np.unique(flat // self.cap, return_counts=True)
        self.free_count[parts] += counts
        self._tomb_free[parts] += counts
        self.tombstones += int(flat.size)
        if ids is not None:
            near = ids < self.dense_limit
            self._slot[ids[near]] = -1
            for rid in ids[~near].tolist():
                self._far.pop(rid, None)
            self.live -= int(ids.shape[0])

    @property
    def max_fill(self) -> float:
        """Largest bucket fill fraction (used slots / cap)."""
        if not self.partitions:
            return 0.0
        return float(self.cap - self.free_count.min()) / self.cap

    @property
    def tombstone_fraction(self) -> float:
        return self.tombstones / max(1, self.live)

    def stats(self) -> dict:
        return {
            "live": self.live,
            "tombstones": self.tombstones,
            "cap": self.cap,
            "partitions": self.partitions,
            "max_fill": round(self.max_fill, 6),
            "tombstone_fraction": round(self.tombstone_fraction, 6),
            "free_slots": int(self.free_count.sum()),
            "max_used": (int(self.cap - self.free_count.min())
                         if self.partitions else 0),
        }


def freelist_of(index) -> Freelist:
    """The index's cached freelist, derived on first use from the
    resident id plane (one host fetch of 4 bytes a slot, inside the span
    ``knn:index.freelist-build``). ``warm_mutation`` makes it during
    set-up; a writer that finds none makes it before it takes the
    mutation lock, never under it. Cached on the instance like
    ``_cache`` — mutation plans commit into it. Works for both mutable
    layouts: the clustered bucket store (per-partition buckets) and the
    serial tile stack (every tile is a "bucket" of c_tile slots)."""
    fl = index.__dict__.get("_freelist")
    if fl is None:
        from mpi_knn_tpu.obs import spans as obs_spans

        dense = getattr(index, "tiles", None) is not None
        plane = index.tile_ids if dense else index.bucket_ids
        with obs_spans.span("freelist-build", cat="index",
                            rows=int(plane.size),
                            bytes=int(plane.size) * 4):
            ids = np.asarray(jax.device_get(plane))
            fl = Freelist(ids, ids.shape[0] if dense else index.partitions)
        index.__dict__["_freelist"] = fl
    return fl


def _lowest_free_slots(fl: Freelist, parts: np.ndarray) -> np.ndarray:
    """A free slot for each entry of ``parts``: the entries that name
    one bucket get its lowest free slots in their own order. One cumsum
    and one search over the touched buckets' ``used`` rows. Raises
    :class:`BucketOverflowError` naming the buckets that lack slots."""
    uniq, inv, counts = np.unique(parts, return_inverse=True,
                                  return_counts=True)
    short = counts > fl.free_count[uniq]
    if short.any():
        overflow = uniq[short].tolist()
        raise BucketOverflowError(
            f"bucket headroom exhausted for partition(s) "
            f"{overflow} (cap={fl.cap}); compact the index "
            "(re-cluster rebalances and re-derives headroom) and retry",
            partitions=overflow,
        )
    order = np.argsort(inv, kind="stable")
    rank = np.empty(inv.shape[0], np.int64)  # place among its bucket's
    rank[order] = np.arange(inv.shape[0]) - np.repeat(
        np.cumsum(counts) - counts, counts)
    # free slots counted along each touched bucket, the buckets' counts
    # strung into one ascending sequence so that one search serves all
    stride = fl.cap + 1
    free_before = np.cumsum(~fl.used[uniq], axis=1) + (
        np.arange(uniq.shape[0]) * stride)[:, None]
    at = np.searchsorted(free_before.reshape(-1), rank + 1 + inv * stride)
    return at - inv * fl.cap


def plan_upsert(fl: Freelist, ids: np.ndarray, parts: np.ndarray):
    """Allocate slots for one upsert chunk WITHOUT committing: returns
    ``(part, slot, clear_part, clear_slot, commit)`` where the first four
    are the scatter index vectors and ``commit()`` applies the
    allocation to the freelist once the device scatter has been
    dispatched (plan → dispatch → commit, so a failed dispatch leaves
    the host mirror untouched). An id that is already live is an UPDATE:
    same partition → its own slot is overwritten in place; moved
    partition → the old slot is tombstoned via the clear pair and a
    fresh slot allocated. ``ids`` must be unique within one chunk (the
    orchestration dedupes — duplicate scatter indices would race).
    Raises :class:`BucketOverflowError` (freelist untouched) when any
    target bucket is out of free slots. A bounded number of numpy calls
    a chunk: nothing iterates over rows or over all buckets."""
    ids = np.asarray(ids, dtype=np.int64).reshape(-1)
    parts = np.asarray(parts, dtype=np.int64).reshape(-1)
    old = fl.lookup(ids)
    old_part, old_slot = np.divmod(old, fl.cap)
    stay = (old >= 0) & (old_part == parts)  # in-place update: the id's
    # own occupied slot (the row/norm/scale scatter replaces the payload,
    # the id scatter rewrites the same id)
    moved = (old >= 0) & ~stay
    fresh = ~stay
    slot = np.where(stay, old_slot, 0)
    if fresh.any():
        slot[fresh] = _lowest_free_slots(fl, parts[fresh])
    clear_part = np.where(moved, old_part, fl.total).astype(np.int32)
    clear_slot = np.where(moved, old_slot, 0).astype(np.int32)

    def commit():
        if fresh.any():
            fl.occupy(ids[fresh], parts[fresh] * fl.cap + slot[fresh])
        if moved.any():
            fl.release(old[moved])

    return (parts.astype(np.int32), slot.astype(np.int32), clear_part,
            clear_slot, commit)


def plan_delete(fl: Freelist, ids: np.ndarray):
    """(part, slot, commit, missing): scatter index vectors tombstoning
    every LIVE id in ``ids`` (unknown ids are counted in ``missing`` and
    dropped — deleting an absent id is idempotent, not an error)."""
    ids = np.asarray(ids, dtype=np.int64).reshape(-1)
    flat = fl.lookup(ids)
    found = flat >= 0
    part = np.where(found, flat // fl.cap, fl.total).astype(np.int32)
    slot = np.where(found, flat % fl.cap, 0).astype(np.int32)

    def commit():
        if found.any():
            fl.release(flat[found], ids[found])

    return part, slot, commit, int((~found).sum())


# ---------------------------------------------------------------------------
# Device programs (jitted once at module level, store args donated — the
# serving engine's convention, extended to mutation)


# kernel scopes of the scatter programs in the HLO ``op_name``, so a
# device trace can say what the writes cost
UPSERT_SCOPE = "knn.mutate/upsert"
DELETE_SCOPE = "knn.mutate/delete"


def store_rows_and_sqs(rows: jax.Array, cfg: KNNConfig, dim: int):
    """(at-rest rows, scales-or-None, norms) of a chunk of centered f32
    rows — the SAME per-row math the build uses (cast for float stores,
    block-scaled quantize + norms-of-the-dequantized for int8/int4), so
    a mutated slot is indistinguishable from a built one."""
    if cfg.dtype in QUANT_DTYPES:
        codes, scales = quantize_rows(rows, dtype=cfg.dtype)
        sqs = sq_norms(dequantize_rows(codes, scales, cfg.dtype, dim))
        return codes, scales, sqs
    at_rest = rows.astype(jnp.dtype(cfg.dtype))
    if cfg.metric != "l2":
        # a cosine tile stack keeps its rows' inverse norms
        # (backends.serial.stack_norms) — mirror the build exactly
        return at_rest, None, cosine_inv_norms(at_rest)
    return at_rest, None, sq_norms(at_rest)


def ivf_assign_chunk(rows, centroids, centroid_sqs):
    """Nearest partition per centered row — the exact-HIGHEST centroid
    score (the build assignment / stage-1 routing geometry). (B, d) →
    (B,) int32."""
    cd = pairwise_sq_l2(
        rows, centroids, x_sq=sq_norms(rows), y_sq=centroid_sqs,
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.argmin(cd, axis=1).astype(jnp.int32)


def ivf_upsert_chunk(
    rows,        # (B, d) f32 centered
    new_ids,     # (B,) int32
    part, slot,  # (B,) int32 target slots (part == P_total -> drop)
    clear_part, clear_slot,  # (B,) int32 old slots of updated ids
    buckets, bucket_ids, bucket_sqs, bucket_scales,  # DONATED store
    cfg: KNNConfig,
):
    """One donated in-place upsert chunk: tombstone any old slots of
    updated ids, then scatter the chunk's at-rest rows + ids + norms
    (+ scales) into their allocated slots. Every output aliases its
    donated input (R5's contract over the mutation programs) and the
    only new payload materialized is the (B, ·) chunk itself (R2-strict's
    touched-bucket budget)."""
    with jax.named_scope(UPSERT_SCOPE):
        at_rest, scales, sqs = store_rows_and_sqs(rows, cfg, rows.shape[-1])
        bucket_ids = bucket_ids.at[clear_part, clear_slot].set(
            -1, mode="drop")
        bucket_ids = bucket_ids.at[part, slot].set(new_ids, mode="drop")
        buckets = buckets.at[part, slot].set(at_rest, mode="drop")
        bucket_sqs = bucket_sqs.at[part, slot].set(
            sqs.astype(bucket_sqs.dtype), mode="drop"
        )
        if bucket_scales is not None:
            bucket_scales = bucket_scales.at[part, slot].set(
                scales, mode="drop"
            )
    return buckets, bucket_ids, bucket_sqs, bucket_scales


def ivf_delete_chunk(part, slot, bucket_ids):
    """One donated tombstone chunk: ids at the given slots go to −1
    (``mask_tile`` makes them +inf candidates — never answers). Row data
    stays resident and masked; the freelist reclaims the slots."""
    with jax.named_scope(DELETE_SCOPE):
        return bucket_ids.at[part, slot].set(-1, mode="drop")


def ivf_compact_assign(buckets, bucket_scales, centroids, centroid_sqs,
                       cfg: KNNConfig):
    """Partition assignment of EVERY slot in the resident store against
    (possibly retrained) centroids — tiled per bucket so the distance
    intermediate stays (cap, P), never (P·cap, P). Returns (P_total·cap,)
    int32; the host plan masks dead/padding slots via ``bucket_ids``."""
    dim = centroids.shape[1]

    def per_bucket(args):
        b, s = args
        rows = b
        if s is not None:
            rows = dequantize_rows(b, s, cfg.dtype, dim)
        rows = rows.astype(jnp.float32)
        return ivf_assign_chunk(rows, centroids, centroid_sqs)

    if bucket_scales is not None:
        parts = jax.lax.map(per_bucket, (buckets, bucket_scales))
    else:
        parts = jax.lax.map(lambda b: per_bucket((b, None)), buckets)
    return parts.reshape(-1)


def ivf_compact_scatter(
    dst_part, dst_slot,  # (N,) int32 per OLD flat slot; drop for dead rows
    src_buckets, src_ids, src_sqs, src_scales,  # the old resident store
    dst_buckets, dst_ids, dst_sqs, dst_scales,  # DONATED fresh store
):
    """The compact rebuild as ONE donated scatter: every live row moves
    from its old flat slot into its re-clustered (part, slot) without the
    payload ever leaving the device. Outputs alias the donated
    destination arrays; the source store is a read-only input (reshape,
    not copy). Dead and padding slots carry an out-of-range ``dst_part``
    and drop."""
    flat_rows = src_buckets.reshape(-1, src_buckets.shape[-1])
    flat_ids = src_ids.reshape(-1)
    flat_sqs = src_sqs.reshape(-1)
    dst_buckets = dst_buckets.at[dst_part, dst_slot].set(
        flat_rows, mode="drop"
    )
    dst_ids = dst_ids.at[dst_part, dst_slot].set(flat_ids, mode="drop")
    dst_sqs = dst_sqs.at[dst_part, dst_slot].set(flat_sqs, mode="drop")
    if dst_scales is not None:
        dst_scales = dst_scales.at[dst_part, dst_slot].set(
            src_scales.reshape(-1), mode="drop"
        )
    return dst_buckets, dst_ids, dst_sqs, dst_scales


# module-level jits, donation fixed (mutation programs are always
# donated — an un-donated store update would copy the corpus per chunk,
# exactly what the lint counterexamples prove the rules catch)
assign_jit = jax.jit(ivf_assign_chunk)
upsert_jit = jax.jit(
    ivf_upsert_chunk, static_argnames=("cfg",), donate_argnums=(6, 7, 8, 9)
)
delete_jit = jax.jit(ivf_delete_chunk, donate_argnums=(2,))
compact_assign_jit = jax.jit(ivf_compact_assign, static_argnames=("cfg",))
compact_scatter_jit = jax.jit(
    ivf_compact_scatter, donate_argnums=(6, 7, 8, 9)
)

# donated parameter positions of each mutation program, by kind — what
# the lint meta (and DESIGN.md's table) reference
UPSERT_DONATED = (6, 7, 8, 9)
DELETE_DONATED = (2,)
COMPACT_DONATED = (6, 7, 8, 9)


# ---------------------------------------------------------------------------
# Compaction planning (host) — sample-retrained k-means + one device
# scatter; bucket_cap kept whenever the live set still fits


COMPACT_SAMPLE = 16384  # deterministic live-row sample for the retrain


def gather_live_sample(index, limit: int = COMPACT_SAMPLE) -> np.ndarray:
    """Up to ``limit`` live rows (dequantized, centered frame) fetched
    via a SMALL device gather — the tune_nprobe precedent: the retrain
    must not round-trip the whole store through the host."""
    fl = freelist_of(index)
    ids = fl.live_ids()
    if not ids.size:
        raise ValueError("cannot compact an empty index (no live rows)")
    take = np.linspace(0, len(ids) - 1, num=min(limit, len(ids)),
                       dtype=np.int64)
    flat = fl.lookup(ids[take])
    sel = index.buckets.reshape(-1, index.buckets.shape[-1])[
        jnp.asarray(flat)
    ]
    if index.bucket_scales is not None:
        sel = dequantize_rows(
            sel,
            index.bucket_scales.reshape(-1)[jnp.asarray(flat)],
            index.store_dtype,
            index.dim,
        )
    return np.asarray(jax.device_get(sel.astype(jnp.float32)))


def retrain_centroids(index, cfg: KNNConfig, sample: np.ndarray):
    """K-means over a host-copied live-row sample (deterministic per
    ``ivf_seed``) → (centroids, centroid_sqs). Pure compute over the
    SNAPSHOT — it touches no resident array, so the caller runs it OFF
    the mutation lock (training must block nothing)."""
    from mpi_knn_tpu.ivf.kmeans import kmeans

    res = kmeans(
        sample, index.partitions, iters=cfg.kmeans_iters,
        seed=cfg.ivf_seed, init=cfg.kmeans_init,
    )
    return res.centroids, jax.jit(sq_norms)(res.centroids)


def plan_compact(index, cfg: KNNConfig, centroids, centroid_sqs,
                 min_cap: int | None = None):
    """The LOCK-HELD half of a compaction: assign every slot on device
    against the (possibly retrained) centroids and lay out the new
    store. Returns ``(dst_part, dst_slot, new_cap, stats)`` — the device
    scatter itself is the caller's job (it owns the executable cache and
    the donation). ``new_cap`` equals the current cap whenever the
    re-clustered live set fits (compaction then stays invisible to every
    compiled cell); ``min_cap`` forces growth — the overflow backstop
    for a burst that must fit after this pass."""
    from mpi_knn_tpu.parallel.partition import pad_to_multiple

    fl = freelist_of(index)
    parts = np.asarray(jax.device_get(compact_assign_jit(
        index.buckets, index.bucket_scales, centroids, centroid_sqs,
        cfg=index.cfg,
    )))
    ids_flat = np.asarray(
        jax.device_get(index.bucket_ids)
    ).reshape(-1)
    live = ids_flat >= 0
    counts = np.bincount(parts[live], minlength=index.partitions)
    need = int(counts.max()) if counts.size else 1
    headroom_cap = pad_to_multiple(
        max(1, int(np.ceil(need * (1.0 + cfg.bucket_headroom)))), 8
    )
    new_cap = index.bucket_cap if need <= index.bucket_cap else headroom_cap
    if min_cap is not None:
        new_cap = max(new_cap, pad_to_multiple(int(min_cap), 8))
    # destination layout: live rows in flat-slot order get consecutive
    # slots within their new partition (deterministic). The drop
    # sentinel is the STORE's total bucket count (a sharded store pads
    # past the real partitions — see Freelist.total)
    n = ids_flat.shape[0]
    dst_part = np.full(n, index.buckets.shape[0], np.int32)
    dst_slot = np.zeros(n, np.int32)
    next_slot = np.zeros(index.partitions, np.int64)
    for i in np.flatnonzero(live):
        p = int(parts[i])
        dst_part[i] = p
        dst_slot[i] = next_slot[p]
        next_slot[p] += 1
    stats = {
        "live": int(live.sum()),
        "tombstones_reclaimed": fl.tombstones,
        "cap_before": index.bucket_cap,
        "cap_after": int(new_cap),
        "max_bucket": need,
    }
    return dst_part, dst_slot, int(new_cap), stats


def should_compact(index, cfg: KNNConfig) -> str | None:
    """The trigger: the reason string ("fill" / "tombstones") when a
    compaction threshold is crossed, else None."""
    fl = freelist_of(index)
    if fl.max_fill >= cfg.compact_fill_threshold:
        return "fill"
    if (
        fl.tombstones > 0
        and fl.tombstone_fraction >= cfg.compact_tombstone_fraction
    ):
        return "tombstones"
    return None


@functools.lru_cache(maxsize=None)
def _zeros_maker(shape, dtype_str, sharding=None):
    """A jitted zero-store maker (compiled once per shape, shared by
    every compaction at that shape): the donated destination scratch
    must be born on device without an eager host corpus-sized buffer or
    an uncounted eager fill. ``sharding`` places the scratch on a
    sharded index's bucket layout directly."""
    fn = lambda: jnp.zeros(shape, jnp.dtype(dtype_str))  # noqa: E731
    if sharding is not None:
        return jax.jit(fn, out_shardings=sharding)
    return jax.jit(fn)


def make_dst_store(index, new_cap: int, sharding=None):
    """Fresh (donatable) destination arrays for a compact scatter — ids
    start at −1 (everything free), rows/norms/scales at zero. A sharded
    index's scratch is born on its bucket sharding."""
    P = index.buckets.shape[0]
    pd = index.buckets.shape[-1]
    buckets = _zeros_maker(
        (P, new_cap, pd), str(index.buckets.dtype), sharding
    )()
    # the id plane starts all-free (−1): a small host buffer, device_put
    # (a transfer, never a compiled fill — the engine's qids precedent)
    ids_np = np.full((P, new_cap), -1, np.int32)
    ids = (jax.device_put(ids_np, sharding) if sharding is not None
           else jax.device_put(ids_np))
    sqs = _zeros_maker((P, new_cap), str(index.bucket_sqs.dtype), sharding)()
    scales = (
        _zeros_maker((P, new_cap), "float32", sharding)()
        if index.bucket_scales is not None else None
    )
    return buckets, ids, sqs, scales
