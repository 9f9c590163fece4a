"""The freelist as it was before ISSUE 34, kept as the tests' oracle: one
dict entry and one tuple a live row, one Python list of free slots a
bucket, a Python iteration a row in every plan. The array mirror
(``mpi_knn_tpu.ivf.mutate.Freelist``) has to choose the same slots,
slot for slot (``tests/test_mutation.py``)."""

from __future__ import annotations

import numpy as np


class DictFreelist:
    """Per-bucket free-slot stacks + the id → (partition, slot) map.

    Derived from ``bucket_ids`` (id −1 = free), never stored: any saved
    artifact — including pre-mutation ones — reconstructs it exactly.
    Slot allocation is deterministic (lowest free slot first), so a
    mutation replayed against a reloaded index lands every row in the
    same slot.

    ``tombstones`` counts deleted-not-yet-reused slots (an upsert that
    reclaims a tombstoned slot decrements it); the compaction triggers
    read ``max_fill`` and ``tombstone_fraction`` from here.
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
        # free stacks in REVERSE slot order so .pop() yields the lowest
        # free slot (deterministic, replayable allocation)
        self.free: list[list[int]] = [
            sorted(np.flatnonzero(ids[p] < 0).tolist(), reverse=True)
            for p in range(self.partitions)
        ]
        self.pos: dict[int, tuple[int, int]] = {}
        for p in range(self.partitions):
            for s in np.flatnonzero(ids[p] >= 0):
                self.pos[int(ids[p, s])] = (p, int(s))
        self.tombstones = 0
        self._tomb_free = [0] * self.partitions

    @property
    def live(self) -> int:
        return len(self.pos)

    @property
    def max_fill(self) -> float:
        """Largest bucket fill fraction (used slots / cap)."""
        if not self.partitions:
            return 0.0
        return max(
            (self.cap - len(f)) / self.cap for f in self.free
        )

    @property
    def tombstone_fraction(self) -> float:
        return self.tombstones / max(1, self.live)

    def stats(self) -> dict:
        used = [self.cap - len(f) for f in self.free]
        return {
            "live": self.live,
            "tombstones": self.tombstones,
            "cap": self.cap,
            "partitions": self.partitions,
            "max_fill": round(self.max_fill, 6),
            "tombstone_fraction": round(self.tombstone_fraction, 6),
            "free_slots": int(sum(len(f) for f in self.free)),
            "max_used": max(used) if used else 0,
        }


def plan_upsert(fl: "DictFreelist", ids: np.ndarray, parts: np.ndarray):
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
    target bucket is out of free slots."""
    n = len(ids)
    part = np.empty(n, np.int32)
    slot = np.empty(n, np.int32)
    clear_part = np.full(n, fl.total, np.int32)  # default: drop
    clear_slot = np.zeros(n, np.int32)
    taken: dict[int, int] = {}  # partition -> slots consumed this plan
    moves: list[tuple] = []  # (rid, old_pos|None, new_p, new_s)
    overflow = set()
    for i, (rid, p) in enumerate(zip(ids, parts)):
        rid, p = int(rid), int(p)
        old = fl.pos.get(rid)
        if old is not None and old[0] == p:
            # in-place update: reuse the id's own occupied slot (the
            # row/norm/scale scatter replaces the payload, the id
            # scatter rewrites the same id)
            part[i], slot[i] = p, old[1]
            continue
        if old is not None:
            clear_part[i], clear_slot[i] = old
        depth = taken.get(p, 0)
        stack = fl.free[p]
        if depth >= len(stack):
            overflow.add(p)
            continue
        s = int(stack[-1 - depth])
        taken[p] = depth + 1
        part[i], slot[i] = p, s
        moves.append((rid, old, p, s))
    if overflow:
        raise OverflowError(
            f"bucket headroom exhausted for partition(s) "
            f"{sorted(overflow)} (cap={fl.cap}); compact the index "
            "(re-cluster rebalances and re-derives headroom) and retry",
        )

    def commit():
        for rid, old, p, s in moves:
            if old is not None:
                op, os_ = old
                fl.free[op].append(int(os_))
                fl.free[op].sort(reverse=True)
                fl._tomb_free[op] += 1
                fl.tombstones += 1
            fl.free[p].remove(s)
            if fl._tomb_free[p] > 0:
                fl._tomb_free[p] -= 1
                fl.tombstones -= 1
            fl.pos[rid] = (p, s)

    return part, slot, clear_part, clear_slot, commit


def plan_delete(fl: "DictFreelist", ids: np.ndarray):
    """(part, slot, commit, missing): scatter index vectors tombstoning
    every LIVE id in ``ids`` (unknown ids are counted in ``missing`` and
    dropped — deleting an absent id is idempotent, not an error)."""
    n = len(ids)
    part = np.full(n, fl.total, np.int32)  # default: drop
    slot = np.zeros(n, np.int32)
    found = []
    missing = 0
    for i, rid in enumerate(ids):
        old = fl.pos.get(int(rid))
        if old is None:
            missing += 1
            continue
        part[i], slot[i] = old
        found.append(int(rid))

    def commit():
        for rid in found:
            p, s = fl.pos.pop(rid)
            fl.free[p].append(s)
            fl.free[p].sort(reverse=True)
            fl._tomb_free[p] += 1
            fl.tombstones += 1

    return part, slot, commit, missing
