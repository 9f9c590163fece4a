"""Tags on a dense index: a predicate on every query row.

A corpus row may carry a bag of tags (ids in ``[0, vocab)``), a query row
up to ``cfg.max_query_tags`` of them, and the answer to a query row is the
k nearest AMONG THE ROWS WHOSE BAG HOLDS EVERY TAG OF THE ROW — exactly,
at any selectivity. ``build_index(corpus, cfg, tags=<CSR>)`` attaches a
:class:`TagIndex` to a serial ``CorpusIndex``; the engine then serves every
batch through one of two regimes, chosen a query row on the host
(:meth:`TagIndex.plan`):

- *masked scan*, for rows all of whose tags are FREQUENT (on more than
  ``threshold`` corpus rows): the index keeps a bitset over the stack's
  slots for each frequent tag (``tag_bits``, (F + 1, tiles, corpus_tile /
  32) uint32 on the device, the last row all ones for "no tag"), the batch
  program gathers a query tile's words once ahead of its scan and every
  tile step masks by them (``backends/serial.py serve_chunk_filtered``,
  scope ``knn.filter_mask``). A row with no tag rides here with all ones.
- *gather and finish*, for rows with a RARE tag: the host has every rare
  tag's posting list (sorted slots), takes the rarest tag's list, tests
  the row's other tags against it (a frequent one in the host's copy of
  the bitsets, a rare one by a search in its own list), pads the
  candidate slots to a bucket and the device gathers the candidates' rows
  and finishes them exactly (:func:`gather_finish`: ``ops/rerank.py
  rerank_exact_topk``, scope ``knn.filter_gather``). A row whose tags
  match nothing (an unknown tag id, an empty intersection) is answered on
  the host: k empty slots.

The threshold is derived, not set: the smallest candidate bucket whose
frequent tags' bitsets fit ``BITSET_BUDGET_BYTES`` of HBM (:func:`derive`).
Where the device keeps the stack's rows apart (a TPU at a width off its
128-lane grid rests a (T, c, d) float32 stack rows-minor, and a gather of
rows from it is compiled as a copy of the WHOLE stack into a padded
row-major buffer: 6.4 GB at 6.3 M x 192, read in the program compiled for
the v5e), the index also keeps a row-major copy of the rows for the gather,
``pack`` rows side by side so that its width lies on the lane grid
(:func:`gather_source`); elsewhere the gather reads the stack itself.

A tagged index is frozen: ``/upsert``, ``/delete`` and ``compact`` are
refused (a write without a bag would be a row no filtered query can
reach), and so are the ``ivf`` and ``ring`` layouts.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.ops.rerank import rerank_exact_topk

GATHER_SCOPE = "knn.filter_gather"
# HBM the frequent tags' bitsets may take; the threshold follows from it
BITSET_BUDGET_BYTES = 1 << 30
# the candidate buckets the threshold is chosen among: powers of four from
# here
_MIN_BUCKET = 256
# the gather regime's dispatches: a query row's candidates go out in
# SEGMENTS of one of these sizes (up to the first in one segment, beyond it
# in as many of the second as they fill; a row's segments' survivors are
# merged on the host), a dispatch holds up to ``GATHER_HEIGHTS[-1]`` segments
# and is padded to the least of these heights that holds it: one program a
# (height, segment size), eight in all, whatever the threshold
GATHER_SEGMENTS = (256, 1024)
GATHER_HEIGHTS = (16, 64, 256, 1024)
# candidate slots whose gathered rows are alive at once inside a dispatch
_CHUNK_SLOTS = 1 << 16
# what a gathered candidate slot costs, in corpus rows scanned for one query
# row: the break-even of the two regimes. On the v5e at 6.3 M x 192 a slot
# read 14.7 ns and a scanned (query row, corpus row) pair 20.5 ps (PERF.md
# §6, PR 39): ~700, and the segments' padding about doubles it
SCAN_ROWS_PER_CANDIDATE = 1024
# query rows by what became of them (``filter_rows_total{regime=...}``)
REGIMES = ("none", "scan", "gather", "empty")
NONE, SCAN, GATHER, EMPTY = range(4)


def as_csr(tags, rows: int) -> tuple[np.ndarray, np.ndarray]:
    """``(indptr (rows + 1,) int64, indices int32)`` of a bag a corpus row:
    a pair of arrays, a mapping or ``.npz`` with those two names, or
    anything with the two attributes (a ``scipy.sparse.csr_matrix``)."""
    if isinstance(tags, (tuple, list)):
        indptr, indices = tags
    elif hasattr(tags, "indptr"):
        indptr, indices = tags.indptr, tags.indices
    else:
        indptr, indices = tags["indptr"], tags["indices"]
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int32)
    if indptr.shape != (rows + 1,) or indptr[0] != 0 \
            or indptr[-1] != indices.shape[0] or (np.diff(indptr) < 0).any():
        raise ValueError(
            f"tags must be a CSR over the corpus's {rows} rows: indptr of "
            f"{rows + 1} ascending offsets from 0 to len(indices); got "
            f"indptr {indptr.shape} ending at {indptr[-1] if len(indptr) else None}"
            f" for {indices.shape[0]} indices")
    if indices.size and indices.min() < 0:
        raise ValueError("tag ids must be >= 0")
    return indptr, indices


def derive(counts: np.ndarray, slots: int) -> int:
    """The threshold between the regimes, a candidate bucket: the largest
    at which a row still costs less gathered than scanned — a candidate
    gathered costs what ``SCAN_ROWS_PER_CANDIDATE`` corpus rows scanned
    cost, so up to ``slots`` over that — and beyond it as far as it takes
    for the bitsets of the tags on MORE rows (``slots / 8`` bytes each) to
    fit ``BITSET_BUDGET_BYTES``."""
    bucket = _MIN_BUCKET
    while bucket * 4 * SCAN_ROWS_PER_CANDIDATE <= slots:
        bucket *= 4
    top = int(counts.max()) if counts.size else 0
    while (int((counts > bucket).sum()) * (slots // 8) > BITSET_BUDGET_BYTES
           and bucket < top):
        bucket *= 4
    return bucket


def _invert(rows, tags, vocab: int):
    """Posting lists from non-zeros ``(rows, tags)``: ``(ptr (vocab + 1,)
    int64, slots int32)``, a tag's slots ascending. One sort of (tag,
    slot) packed into an int64 — a pair comes once, so no order needs
    keeping — which numpy sorts several times faster than it argsorts."""
    key = (tags.astype(np.int64) << 32) | rows
    key.sort()
    ptr = np.zeros(vocab + 1, dtype=np.int64)
    np.cumsum(np.bincount(tags, minlength=vocab), out=ptr[1:])
    return ptr, (key & 0xFFFFFFFF).astype(np.int32)


def gather_pack(dim: int) -> tuple[int, int]:
    """``(pack, width)`` of the gather's row-major copy for ``dim``-wide
    rows: ``pack`` rows side by side where that lands on the 128-lane grid
    with nothing wasted (192 -> 2 x 192 = 384), else one row padded with
    zero columns up to it (100 -> 128)."""
    for pack in (1, 2, 4):
        if (pack * dim) % 128 == 0:
            return pack, pack * dim
    return 1, -(-dim // 128) * 128


@functools.partial(jax.jit, static_argnames=("pack", "width"))
def gather_source(tiles, pack: int, width: int):
    """The (slots / pack, width) row-major copy of a (T, c, d) stack, tile
    by tile into a zeroed buffer: the program's temporaries are one
    tile's, whatever layouts the device keeps the two shapes in (as one
    reshape the v5e compiler first copies the whole rows-minor stack into
    a padded row-major one: 6.4 GB of temporaries at 6.3 M x 192, read in
    the compiled program; ``serve/index.py _pad_and_tile`` met the same)."""
    t, c, d = tiles.shape

    def one_tile(i, out):
        tile = jax.lax.dynamic_index_in_dim(tiles, i, keepdims=False)
        if pack * d == width:
            rows = tile.reshape(c // pack, width)
        else:
            rows = jnp.pad(tile, ((0, 0), (0, width - d)))
        return jax.lax.dynamic_update_slice_in_dim(
            out, rows, i * (c // pack), axis=0)

    return jax.lax.fori_loop(
        0, t, one_tile, jnp.zeros((t * c // pack, width), tiles.dtype))


def _candidate_rows(src, slots, dim: int, pack: int):
    """(g, M, dim) rows of the candidate ``slots`` (g, M) from the gather's
    source: the stack itself (T, c, d), or its row-major copy."""
    if src.ndim == 3:
        return src.reshape(-1, dim)[slots]
    rows = src[slots // pack]
    if pack == 1:
        return rows[..., :dim]
    which = (slots % pack)[..., None]
    out = rows[..., :dim]
    for p in range(1, pack):
        out = jnp.where(which == p, rows[..., p * dim:(p + 1) * dim], out)
    return out


def gather_finish(queries, cand, src, *, cfg: KNNConfig, dim: int,
                  pack: int):
    """The gather regime's batch program: ``queries`` (R, d) centred rows,
    ``cand`` (R, M) int32 candidate slots a row (-1: padding), ``src`` the
    gather's source. A frozen index's slot IS its row id, so the candidates
    name themselves; their norms are taken from the gathered rows. Rows go
    through ``_CHUNK_SLOTS`` candidates at a time, so that the gathered
    rows alive at once stay a few hundred MB whatever the bucket. Returns
    ((R, k) distances ascending, (R, k) ids; +inf past a row's matches,
    where the id means nothing: ``BatchResult`` empties those slots)."""
    r, m = cand.shape
    g = max(1, min(r, _CHUNK_SLOTS // m))
    while r % g:
        g -= 1

    def chunk(args):
        q, c = args
        with jax.named_scope(GATHER_SCOPE):
            rows = _candidate_rows(src, jnp.maximum(c, 0), dim, pack)
            return rerank_exact_topk(
                q, None, None, rows, c, None, cfg.k, metric=cfg.metric,
                exclude_self=False, exclude_zero=cfg.exclude_zero,
                zero_eps=cfg.zero_eps)

    d, i = jax.lax.map(
        chunk, (queries.reshape(r // g, g, -1), cand.reshape(r // g, g, m)))
    return d.reshape(r, cfg.k), i.reshape(r, cfg.k)


@dataclasses.dataclass
class GatherPart:
    """One gather dispatch of a plan: a segment of candidate slots a row,
    padded to the segment size and the dispatch's height with -1; ``rows``
    names the batch row each leading segment belongs to (a row with more
    candidates than a segment holds comes several times)."""

    rows: np.ndarray  # (r,) positions in the batch, r <= R
    cand: np.ndarray  # (R, M) int32


@dataclasses.dataclass
class Plan:
    """What :meth:`TagIndex.plan` makes of some rows' filters: every row's
    regime and each regime's operand. Plans of requests that meet in one
    batch are joined by :func:`merge_plans`."""

    regime: np.ndarray  # (n,) int8: NONE / SCAN / GATHER / EMPTY
    scan_rows: np.ndarray  # positions of the NONE and SCAN rows
    scan_tags: np.ndarray  # (len(scan_rows), W) int32 bitset rows
    # the gather regime's segments by size: {size: (row of each segment
    # (s,), its candidate slots (s, size) int32, -1 past them)}
    segments: dict
    candidates: int  # candidate slots before padding

    def parts(self) -> list:
        """The gather dispatches: a size's segments ``GATHER_HEIGHTS[-1]``
        at a time, the last padded to the least height that holds it."""
        out = []
        top = GATHER_HEIGHTS[-1]
        for size, (rows, cand) in sorted(self.segments.items()):
            for lo in range(0, len(rows), top):
                part = cand[lo:lo + top]
                height = GATHER_HEIGHTS[
                    np.searchsorted(GATHER_HEIGHTS, len(part))]
                if len(part) < height:
                    part = np.concatenate([part, np.full(
                        (height - len(part), size), -1, np.int32)])
                out.append(GatherPart(rows[lo:lo + top], part))
        return out


def merge_plans(plans: list) -> Plan:
    """The plan of a batch from the plans of its requests, in row order."""
    if len(plans) == 1:
        return plans[0]
    start = np.cumsum([0] + [len(p.regime) for p in plans])
    segments: dict = {}
    for size in sorted({m for p in plans for m in p.segments}):
        got = [(p.segments[size][0] + off, p.segments[size][1])
               for p, off in zip(plans, start) if size in p.segments]
        segments[size] = tuple(np.concatenate(x) for x in zip(*got))
    return Plan(
        np.concatenate([p.regime for p in plans]),
        np.concatenate([p.scan_rows + off for p, off in zip(plans, start)]),
        np.concatenate([p.scan_tags for p in plans]),
        segments, sum(p.candidates for p in plans))


@dataclasses.dataclass
class TagIndex:
    """The tags of a frozen serial index: what its two regimes need."""

    vocab: int
    width: int  # tags a query row may carry (cfg.max_query_tags)
    threshold: int
    counts: np.ndarray  # (vocab,) rows a tag
    bit_row: np.ndarray  # (vocab,) int32 bitset row of a tag; F: it has none
    post_ptr: np.ndarray  # (vocab + 1,) posting lists of the rare tags
    post_slots: np.ndarray
    host_bits: np.ndarray  # (F + 1, T, c_tile / 32) uint32
    tag_bits: jax.Array  # the same on the device
    src: jax.Array | None  # the gather's row-major copy; None: the stack
    pack: int
    c_tile: int

    @property
    def n_bitsets(self) -> int:
        return self.host_bits.shape[0] - 1

    def hbm_bytes(self) -> int:
        copy = 0 if self.src is None else self.src.size * self.src.dtype.itemsize
        return int(self.host_bits.nbytes + copy)

    def host_bytes(self) -> int:
        return int(self.host_bits.nbytes + self.post_slots.nbytes
                   + self.post_ptr.nbytes + self.counts.nbytes
                   + self.bit_row.nbytes)

    def summary(self) -> dict:
        return {
            "vocab": self.vocab, "max_query_tags": self.width,
            "threshold": self.threshold, "bitsets": self.n_bitsets,
            "gather_segments": list(GATHER_SEGMENTS),
            "row_major_copy": self.src is not None, "pack": self.pack,
            "hbm_bytes": self.hbm_bytes(), "host_bytes": self.host_bytes(),
        }

    @staticmethod
    def gather_shapes() -> list:
        """Every (height, segment size) a gather dispatch may have."""
        return [(h, m) for m in GATHER_SEGMENTS for h in GATHER_HEIGHTS]

    def _holds(self, tag: np.ndarray, slot: np.ndarray) -> np.ndarray:
        """Whether ``slot``'s row holds ``tag``, element by element: a
        frequent tag by its bit, a rare one by a search in its list."""
        out = np.zeros(tag.shape, dtype=bool)
        brow = self.bit_row[tag]
        freq = brow < self.n_bitsets
        if freq.any():
            s = slot[freq].astype(np.int64)
            wt = self.c_tile // 32
            cin = s % self.c_tile
            word = self.host_bits.reshape(-1)[
                (brow[freq] * self.host_bits.shape[1] + s // self.c_tile)
                * wt + cin % wt]
            out[freq] = (word >> (cin // wt).astype(np.uint32)) & 1 != 0
        rare = ~freq
        if rare.any() and self.post_slots.size:
            t, s = tag[rare], slot[rare]
            lo, end = self.post_ptr[t], self.post_ptr[t + 1]
            hi, last = end, self.post_slots.size - 1
            while True:  # a binary search a needle, side by side
                open_ = lo < hi
                if not open_.any():
                    break
                mid = (lo + hi) >> 1
                below = open_ & (self.post_slots[np.minimum(mid, last)] < s)
                lo = np.where(below, mid + 1, lo)
                hi = np.where(open_ & ~below, mid, hi)
            out[rare] = (lo < end) & (
                self.post_slots[np.minimum(lo, last)] == s)
        return out

    def plan(self, filters: np.ndarray) -> Plan:
        """Split rows by regime and make each regime's operand. ``filters``
        (n, W) int32 tag ids, -1 none. Array code throughout: nothing here
        iterates a row."""
        n, w = filters.shape
        f_rows = self.n_bitsets
        given = filters >= 0
        known = given & (filters < self.vocab)
        tag = np.where(known, filters, 0)
        # rows a tag is on; "no tag" constrains nothing, an unknown id
        # matches nothing
        cnt = np.where(known, self.counts[tag],
                       np.where(given, 0, np.iinfo(np.int64).max))
        regime = np.full(n, SCAN, dtype=np.int8)
        regime[~given.any(axis=1)] = NONE
        regime[(cnt == 0).any(axis=1)] = EMPTY
        by_list = (regime == SCAN) & (cnt <= self.threshold).any(axis=1)
        regime[by_list] = GATHER

        segments: dict = {}
        candidates = 0
        rows = np.flatnonzero(by_list)
        if rows.size:
            rarest = np.argmin(cnt[rows], axis=1)
            a = tag[rows, rarest]
            lens = self.counts[a]
            owner = np.repeat(np.arange(rows.size, dtype=np.int32), lens)
            cand = self.post_slots[
                np.arange(int(lens.sum()), dtype=np.int64)
                + np.repeat(self.post_ptr[a] - (np.cumsum(lens) - lens), lens)]
            for j in range(w):  # the row's other tags: each has to hold too
                other = given[rows, j] & (rarest != j)
                if other.any():
                    test = np.flatnonzero(other[owner])
                    drop = test[~self._holds(
                        tag[rows, j][owner[test]], cand[test])]
                    owner, cand = np.delete(owner, drop), np.delete(cand, drop)
            lens = np.bincount(owner, minlength=rows.size)
            regime[rows[lens == 0]] = EMPTY
            rank = np.arange(cand.size) - (np.cumsum(lens) - lens)[owner]
            candidates = int(cand.size)
            small = lens <= GATHER_SEGMENTS[0]
            for size, mine in zip(GATHER_SEGMENTS, (
                    np.flatnonzero(small & (lens > 0)),
                    np.flatnonzero(~small))):
                if not mine.size:
                    continue
                n_seg = -(-lens[mine] // size)
                first = np.full(rows.size, -1, dtype=np.int64)
                first[mine] = np.cumsum(n_seg) - n_seg
                padded = np.full((int(n_seg.sum()), size), -1, np.int32)
                pick = first[owner] >= 0
                padded[first[owner[pick]] + rank[pick] // size,
                       rank[pick] % size] = cand[pick]
                segments[size] = (rows[np.repeat(mine, n_seg)], padded)
        scan_rows = np.flatnonzero(regime <= SCAN)
        scan_tags = np.where(
            known[scan_rows], self.bit_row[tag[scan_rows]], f_rows
        ).astype(np.int32)
        return Plan(regime, scan_rows, scan_tags, segments, candidates)


def build_tag_index(index, tags, *, threshold: int | None = None,
                    row_major_copy: bool | None = None) -> TagIndex:
    """The :class:`TagIndex` of a serial ``CorpusIndex`` from the bags of
    its rows (:func:`as_csr`). ``threshold`` and ``row_major_copy`` are the
    tests' way to either extreme; a deployment derives both."""
    from mpi_knn_tpu.serve.mutate import stack_rests_row_major

    cfg = index.cfg
    if cfg.max_query_tags < 1:
        raise ValueError("an index with tags needs max_query_tags >= 1")
    indptr, indices = as_csr(tags, index.m)
    n_tiles, c_tile, dim = index.tiles.shape
    if dim != index.dim:
        raise ValueError(
            f"this index rests zero-padded at {dim} columns (rows of "
            f"{index.dim}): the gather's programs read the stack at the "
            "rows' width — hand the tags to build_index(tags=), which "
            "rests a tagged stack there (serve/index.py rest_width)")
    vocab = int(indices.max()) + 1 if indices.size else 1
    counts = np.bincount(indices, minlength=vocab).astype(np.int64)
    bitset_bytes = n_tiles * c_tile // 8
    if threshold is None:
        threshold = derive(counts, n_tiles * c_tile)
    frequent = counts > threshold
    f_rows = int(frequent.sum())
    if f_rows * bitset_bytes > BITSET_BUDGET_BYTES:
        raise ValueError(
            f"{f_rows} tags lie on more than {threshold} rows and their "
            f"bitsets ({bitset_bytes} B each) pass the budget of "
            f"{BITSET_BUDGET_BYTES} B")
    bit_row = np.full(vocab, f_rows, dtype=np.int32)
    bit_row[frequent] = np.arange(f_rows, dtype=np.int32)

    # the frequent tags' bitsets: slot c of a tile is bit c // wt of word
    # c % wt (backends/serial.py filter_keep). A (slot, tag) pair comes
    # once, so adding the bits is or-ing them
    wt = c_tile // 32
    host_bits = np.zeros((f_rows + 1, n_tiles, wt), dtype=np.uint32)
    host_bits[f_rows] = 0xFFFFFFFF
    slot = np.repeat(np.arange(index.m, dtype=np.int32), np.diff(indptr))
    sel = frequent[indices]
    on, cin = slot[sel], slot[sel] % c_tile
    np.add.at(
        host_bits.reshape(-1),
        (bit_row[indices[sel]].astype(np.int64) * n_tiles
         + on // c_tile) * wt + cin % wt,
        np.uint32(1) << (cin // wt).astype(np.uint32))
    np.logical_not(sel, out=sel)
    post_ptr, post_slots = _invert(slot[sel], indices[sel], vocab)
    del sel, slot, on, cin

    if row_major_copy is None:
        row_major_copy = not stack_rests_row_major(index)
    pack, src = 1, None
    if row_major_copy:
        pack, width = gather_pack(dim)
        src = gather_source(index.tiles, pack=pack, width=width)
    ti = TagIndex(
        vocab=vocab, width=cfg.max_query_tags, threshold=int(threshold),
        counts=counts, bit_row=bit_row, post_ptr=post_ptr,
        post_slots=post_slots, host_bits=host_bits,
        tag_bits=jnp.asarray(host_bits), src=src, pack=pack, c_tile=c_tile,
    )
    reg = obs_metrics.get_registry()
    reg.gauge(
        "serve_index_tag_bitsets",
        help="frequent tags of the resident index: each has a bitset over "
        "the stack's slots and its query rows take the masked scan",
    ).set(f_rows)
    reg.gauge(
        "serve_index_tag_threshold",
        help="rows a tag may lie on and still be rare: its query rows "
        "take the gather regime (derived from the tag counts and the "
        "bitsets' byte budget)",
    ).set(ti.threshold)
    for where, value in (("hbm", ti.hbm_bytes()), ("host", ti.host_bytes())):
        reg.gauge(
            "serve_index_tag_bytes",
            help="bytes the index's tags take: bitsets and the gather's "
            "row-major copy on the device, bitsets and posting lists on "
            "the host",
            labels={"where": where},
        ).set(value)
    return ti
