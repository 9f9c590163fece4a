"""Bounded top-k maintenance — the replacement for the reference's
insert-and-qsort neighbor list (SURVEY.md C3).

The reference keeps, per query, NN=30 slots initialized to INFINITY and
re-``qsort``s all 30 on every accepted candidate
(``/root/reference/knn-serial.c:57-63,86-91``) — O(k log k) *per candidate*.
Here a whole (q_tile × c_tile) distance tile is reduced at once to its k
smallest and cross-tile/cross-round state is merged associatively::

    merge(carry, tile) = top_k(concat(carry, top_k(tile)))

which is exactly the property the distributed ring needs (merge is
commutative/associative over candidate sets — tested in test_topk.py).

The per-tile reduction of a wide tile and a small k does not sort the tile
(``lax.top_k`` is a full sort of every row: 3.6 ms for 4096 × 8192 on a v5e,
65-80 % of the device's time, PERF.md §6 PR 27). ``smallest_k``'s "exact"
method picks, by a rule on the shapes alone (``lane_bin_depth``), the
**lane-bin selection**: (1) *bins* — the c columns are c/128 groups of 128
lanes, and every (row, lane) keeps the R smallest of its c/128 values with
their ids by compare-exchange on the VPU (``ops/lane_bin.py``, a Pallas
kernel with no dot in it); (2) *finish* — the k smallest of the R·128
candidates a row, by k passes of row-min and knock-out; (3) *certificate* —
with tau the k-th smallest candidate, if every lane's R-th kept value is
>= tau, nothing that was dropped can belong to the answer; (4) *fallback* —
a ``lax.cond`` runs the full-width ``lax.top_k`` for a tile step in which
some row fails the certificate (R of its k-1 smallest in one lane: at
k = 10, R = 5 and random placement 4.7e-7 a row). The answer is exact for any
data; only the speed depends on how neighbours fall into lanes. The scopes
``bins``, ``finish`` and ``fallback`` sit under the caller's ``knn.select``
in a trace. Merges, the cascade and IVF (2-D ids) keep ``lax.top_k``.

Nothing in the mechanism is per tile. A scan over the tiles of a stack can
carry the lists (``ops/lane_bin.py lane_bin_insert``): they are then every
(row, lane)'s R smallest of the WHOLE stack, ONE finish (``lane_bin_result``)
gives the
stack's k smallest, and the same certificate says whether anything dropped
anywhere could have belonged — with the same chance a row, whatever the
stack's width. That is what ``backends/serial.py merge_tiles_into_carry``
runs where this rule engages for its tiles: a tile step is *bins* only.

Such a scan can also know how good a row's answer already is. Where
``lane_bin_bound_rides`` says so (by the tile's shape), a ROW BOUND rides
beside the lists: one value a row that the row's final k-th smallest cannot
pass — the k-th smallest of any k values the row has seen, here of its lane
minima (``ops/lane_bin.py lane_bin_bound``), taken anew at a few steps and
only falling — and *bins* runs its compare-exchange network on the chunks
of the tile (16 rows x 1024 columns) that hold a value AT OR UNDER it,
which after t tiles is about one in t / 20. Nothing that ``lane_bin_result``
returns changes: a dropped value is above a bound at or above the final
k-th smallest, so it is larger than every list entry at or under the final
bound and moves none of them; entries under a bound never become fewer
after it is taken (a lane evicts one only for a smaller one), so k of them
remain and they are the k smallest candidates; a lane whose last kept value
is under tau holds R values under tau in either program, so the same rows
are flagged and the re-scan runs exactly when it did. Only list slots ABOVE
the final bound may hold other values. The bound must bound the answer of
the stack the LISTS cover: an incoming carry's k-th column bounds the
merged answer only, and a stack with fewer than k values under it would
leave its rows short of k candidates, flagged one and all — a scan starts
from +inf.

All distances flow in "smaller is better" space; +inf marks invalid slots and
``INVALID_ID`` (−1) marks their ids.
"""

from __future__ import annotations

import importlib
import math
import threading

import jax
import jax.numpy as jnp

from mpi_knn_tpu.types import INVALID_ID

_INF = jnp.inf


def init_topk(num_queries: int, k: int, dtype=jnp.float32):
    """Empty carry: all-inf distances, invalid ids — like the reference's
    INFINITY-filled slots (``knn-serial.c:57-63``) but SoA and batched."""
    d = jnp.full((num_queries, k), _INF, dtype=dtype)
    i = jnp.full((num_queries, k), INVALID_ID, dtype=jnp.int32)
    return d, i


def init_topk_tiles(num_tiles: int, tile_rows: int, k: int, dtype=jnp.float32):
    """``init_topk`` pre-shaped to a (num_tiles, tile_rows, k) query-tile
    stack — the carry layout of the tiled serial core and the serving
    engine's per-batch scratch (one construction, so the backends and the
    executable cache can never disagree about the scratch shape)."""
    d, i = init_topk(num_tiles * tile_rows, k, dtype=dtype)
    return (
        d.reshape(num_tiles, tile_rows, k),
        i.reshape(num_tiles, tile_rows, k),
    )


def _fold_topk(dists: jax.Array, ids: jax.Array, k: int, width: int):
    """Fold (q, c) candidate rows into (q, ceil(c/width)·k) by a per-chunk
    top-k: pad the columns to a multiple of ``width`` with (+inf, -1), sort
    each width-column chunk, keep k survivors each. Every global top-k
    element survives its own chunk's top-k, so folding is exact. The shared
    primitive behind the "block" method and the cascade merge."""
    q, c = dists.shape
    nch = -(-c // width)
    pad = nch * width - c
    if pad:
        dists = jnp.pad(dists, ((0, 0), (0, pad)), constant_values=_INF)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=INVALID_ID)
    neg, pos = jax.lax.top_k(-dists.reshape(q, nch, width), k)
    out_ids = jnp.take_along_axis(ids.reshape(q, nch, width), pos, axis=-1)
    return (-neg).reshape(q, nch * k), out_ids.reshape(q, nch * k)


def _pad_lanes(dists: jax.Array, ids: jax.Array, multiple: int = 128):
    """Lane-align the reduction input with (+inf, INVALID_ID) columns:
    ``approx_min_k`` over a width that is not a multiple of 128 (e.g. the
    stream schedule's carry‖tile concat, k+8192 wide) hung the device on
    an earlier transport, while 128-aligned widths ran clean (BASELINE.md
    r3); not re-tested on this machine (ROADMAP Speed 6). The sentinels
    can never enter a k-smallest result. Every ``approx_min_k`` call site
    pads through this helper."""
    pad = (-dists.shape[-1]) % multiple
    if pad:
        dists = jnp.pad(dists, ((0, 0), (0, pad)), constant_values=_INF)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=INVALID_ID)
    return dists, ids


def preselect_smallest(dists: jax.Array, n: int, half_width: bool = False):
    """Column positions of each row's ``n`` smallest entries — the overfetch
    preselect shared by ``smallest_k``'s "bf16" method and the mixed-
    precision compress pass (ops/rerank.py). With ``half_width`` the sort
    keys are rounded to bf16 first (monotone in the f32 values they round
    from — narrower VPU compares); either way the returned positions index
    the ORIGINAL columns, so the caller reranks/gathers exact values."""
    keys = (
        dists.astype(jnp.bfloat16)
        if half_width and dists.dtype == jnp.float32
        else dists
    )
    _, pos = jax.lax.top_k(-keys, n)
    return pos


# --- lane-bin partial selection (the engaged form of method="exact") -------

_LANES = 128  # the bins' column groups are a vreg's 128 lanes wide
_MIN_BIN_WIDTH = 1024  # narrower tiles: one lax.top_k is already cheap
_MAX_BIN_DEPTH = 8
_MAX_BIN_K = _LANES  # the finish kernel answers in 128-lane accumulators
# the rule keeps the EXPECTED share of selections that take the fallback
# (neighbours falling into lanes at random) under this: of query tiles
# where a scan carries the lists, of tile steps in a per-tile call
_MAX_FALLBACK_SHARE = 0.01


def lane_bin_depth(q: int, c: int, k: int, ids_ndim: int = 1) -> int | None:
    """The engage rule of ``smallest_k(method="exact")``: the per-lane depth
    R of the lane-bin selection for a (q, c) tile and k, or None when the
    call keeps the full-width ``lax.top_k``.

    Engaged when ``ids`` is the 1-D (c,) tile id vector (the per-tile call;
    merges, the cascade and IVF pass 2-D ids), ``c`` is a multiple of 128
    and at least 1024, k is at most 128 (the finish kernel's accumulators
    are one vreg wide; only tiles of a few rows get that far under the
    next condition), and some R <= 8 (and below the group count) keeps the
    expected flagged share of query tiles under 1 %: a row is flagged when R
    of its k-1 smallest share a lane, which for neighbours placed at random
    has probability about C(k-1, R) / 128^(R-1), whether the k-1 are a
    tile's or a whole stack's; a selection falls back when any of its q rows
    is flagged — once a query tile where a scan carries the lists over the
    stack, once a tile step in a per-tile call. k = 10 gives R = 5 at 1024
    and 4096 rows and R = 4 at 64; k in the hundreds bypasses."""
    if (ids_ndim != 1 or c % _LANES or c < _MIN_BIN_WIDTH
            or k > _MAX_BIN_K):
        return None
    for depth in range(1, min(_MAX_BIN_DEPTH, c // _LANES - 1) + 1):
        p_row = math.comb(k - 1, depth) / _LANES ** (depth - 1)
        if q * p_row < _MAX_FALLBACK_SHARE:
            return depth
    return None


# the tiles a carried scan's *bins* tests against a row bound, by what the
# chip measured at 8192 columns (PERF.md §6, PR 35): from 256 rows (a step
# 6 % shorter; at 64 rows the kernel's fixed costs pass what is left to
# save) to 2048 (27 % shorter; the 1024-row tile, 32 MiB, is the cells').
# A 4096-row tile, 128 MiB, streams from HBM, where the test's strips and
# chunks cost more than the arithmetic they save: 12 % longer
_BOUND_MIN_ROWS = 256
_BOUND_MAX_TILE_BYTES = 64 << 20


def lane_bin_bound_rides(q: int, c: int, itemsize: int = 4) -> bool:
    """Whether a scan that carries the lane-bin lists over (q, c) tiles
    also carries a row bound, under which *bins* inserts only the chunks
    that hold a value at or under it (``ops/lane_bin.py
    lane_bin_candidates_under``): by the tile's shape alone, as
    :func:`lane_bin_depth` chooses the depth."""
    return (q >= _BOUND_MIN_ROWS
            and q * c * itemsize <= _BOUND_MAX_TILE_BYTES)


# the VMEM the fused scan's own buffers may take: half of a v5e core's 128
# MiB (the call asks for 24 MiB more, for what Mosaic keeps of its own: a
# piece's dot as a value, spills)
_FUSED_VMEM_BYTES = 64 << 20


def fused_scan_engages(q: int, c: int, d: int, depth: int,
                       itemsize: int = 4, passes: int = 1,
                       filtered: bool = False) -> int | None:
    """Whether the one-pass branch of a scan that carries the lane-bin
    lists over (q, c) tiles of float32 (or byte) rows ``d`` wide runs as ONE kernel
    over the whole stack (``ops/fused_scan.py``), by the shapes alone: the
    height of the row blocks the kernel walks the query tile in, or None.
    The height is q, or q halved until it passes (4096 and 2048 go to
    1024), in whole strips of 16 rows:

    - *the row bound rides* a scan of tiles that tall
      (:func:`lane_bin_bound_rides`: the kernel IS *bins* under the bound
      with the dot in front): q itself from 256 to 2048 rows; a 4096-row
      tile goes in four blocks of 1024, each with its own lists, bound and
      count from the first tile to the last (the stack is read once a
      block, which a block's dot outlasts from 481 rows);
    - *memory*: the lists, the bound, the block's distances, two buffers
      of what is fetched of the tile and the query side fit the kernel's
      share of VMEM (``fused_scan_vmem_bytes``, from which
      ``vmem_limit_bytes`` is set: at 1024 rows, 8192 columns, d = 128,
      5.2 MB of lists + 33.6 MB of distances + 8.7 MB of tile + 2.6 MB of
      query side + 2.3 MB of bound, bits, hit words and planes = 52.4 MB;
      2048 rows do not pass and go in two blocks; d = 1536 does not pass
      at any height the bound rides);
    - *the stack's layout at rest*: a kernel's operand is taken
      row-major, and the v5e rests a float32 (T, c, d) array in the order
      that pads nothing under its (8, 128) tiles — a function of the shape
      alone, whatever program reads it, read for every case below in
      programs compiled for the chip (``tests/test_pallas.py -k
      rest_layout``, and ``-k one_pass_rule`` for the cells' shapes). With
      c a multiple of 128 (a carried scan's always is): d on the 128-lane
      grid (128, 1536) rests row-major, ``{2,1,0}``; every other multiple
      of 8 (784, 192, 104) rests as (T, d, c), ``{1,2,0}``, at EVERY tile
      count (1, 3, 127, 128, 768, 1221: a multiple of 128 does not draw T
      onto the lanes) — rows on the lanes, d on the sublanes — and there
      the kernel takes the view ``swapaxes(1, 2)``, the bytes at rest
      under another shape, a 1024-column piece of a tile a grid step
      (``ops/fused_scan.py rests_rows_minor``). At any other width (100)
      d would be padded on the sublanes, so the order follows the tile
      count ((d, T, c), ``{1,0,2}``, at 1224 tiles; (T, d, c) at 3) and
      the compiler would re-lay ALL of the stack ahead of the call:
      d % 8 == 0 and nothing else.
    - *a byte stack* (``itemsize`` 1: ``dtype="uint8"``, whole-number rows
      one byte an element, widened and centred a piece at a time inside
      the kernel): on the 128-lane grid alone, where a uint8 (T, c, d)
      array rests row-major under (32, 128) tiles, ``{2,1,0:T(8,128)(4,1)}``
      at every tile count, and the kernel's ``BlockSpec`` takes a tile
      where it lies (read at d = 128 and 784, up to the 12 208 tiles a
      chip holds, in programs compiled for the chip: ``tests/test_pallas.py
      -k byte_stack``). Off the grid (192, 784) it rests rows-minor like
      the float32 stack, a form this kernel's widening has not been read
      in: the scan of tile steps takes those. A tile's two buffers are
      2 x 1.05 MB, not 2 x 4.2 MB; the distances are float32 either way.
    - *the three-pass form* (``passes`` 3: the screened scan of float32
      rows that are no bf16 numbers, ``backends/serial.py
      fused_screen_rule``): float32 rows on the 128-lane grid alone — a
      candidate's row is gathered from the stack where it rests row-major
      — with its own VMEM: the lists at the screen's depth (7: 7.3 MB),
      the query side and a piece's bf16 copy three widths wide (at 1024
      rows, 8192 columns, d = 128: 57.2 MB).
    - *a predicate* (``filtered``: a tagged index's masked scan, whose
      words are one operand more, ``backends/serial.py filter_words``): a
      float32 stack in the one-pass form with c / 32 a multiple of 128 —
      slot ``s`` of a tile is bit ``s // (c / 32)`` of word ``s % (c /
      32)``, so a column group's bits are one bit of 128 whole lane-aligned
      words only there (8192 columns: 256 words; 2048: 64, out) — and a
      tile's words in their two buffers counted (1 MB at 512 rows).

    Where it says None, the scan of tile steps stays as it is."""
    if itemsize not in (1, 4) or d % 8 or (itemsize == 1 and d % _LANES):
        return None
    if passes != 1 and (passes != 3 or itemsize != 4 or d % _LANES):
        return None
    if filtered and (itemsize != 4 or passes != 1 or c % (32 * _LANES)):
        return None
    from mpi_knn_tpu.ops.fused_scan import fused_scan_vmem_bytes

    block = q
    while block and block % 16 == 0:
        # (the distance tile the bound rides is float32 whatever rests)
        if (lane_bin_bound_rides(block, c)
                and fused_scan_vmem_bytes(
                    block, c, d, depth, itemsize, passes,
                    filtered=filtered) <= _FUSED_VMEM_BYTES):
            return block
        block //= 2
    return None


def lane_bin_flagged_share(dists, k: int) -> tuple[float, float] | None:
    """Host-side counter of the lane-bin selection on one real (q, c) tile:
    (share of rows flagged, 1.0 if the tile step would take the fallback
    else 0.0), or None where the rule bypasses the tile. What the tests and
    chip checks call for ONE tile; a chunk program that carries the lists
    over its stack counts its own query tiles (``backends/serial.py
    select_tiles``), and its re-scans show in a trace under
    ``knn.select/fallback``."""
    dists = jnp.asarray(dists)
    q, c = dists.shape
    depth = lane_bin_depth(q, c, k)
    if depth is None:
        return None
    flagged = _lane_bin_select(
        dists, jnp.arange(c, dtype=jnp.int32), k, depth)[2]
    return float(jnp.mean(flagged)), float(jnp.any(flagged))


def _lane_bin_select(dists: jax.Array, ids: jax.Array, k: int, depth: int):
    """``ops/lane_bin.py lane_bin_select``, imported at the first call:
    pallas costs ~0.8 s to import, and a process that never selects from a
    wide tile should not pay it."""
    from mpi_knn_tpu.ops.lane_bin import lane_bin_select

    return lane_bin_select(dists, ids, k, depth)


_lane_bin_import: threading.Thread | None = None


def start_lane_bin_import() -> None:
    """Start importing ``ops/lane_bin.py`` on a thread, once a process; the
    first trace's own ``import`` joins it through the module lock. Called by
    the owners of the chunk programs (``api._all_knn``, ``serve.build_index``)
    ahead of their corpus passes: those wait on tiny compiles and on the
    device, which hides most of the 0.8-2 s that ``jax.experimental.pallas``
    takes to import (PERF.md §6, PR 27)."""
    global _lane_bin_import
    if _lane_bin_import is None:
        _lane_bin_import = threading.Thread(
            target=importlib.import_module,
            args=("mpi_knn_tpu.ops.lane_bin",),
            name="tknn-import-lane-bin", daemon=True,
        )
        _lane_bin_import.start()


def _top_k_with_ids(dists: jax.Array, ids: jax.Array, k: int):
    """The full-width exact selection: ``lax.top_k`` over every column, the
    survivors' ids gathered after. ``ids`` (c,) or (q, c)."""
    neg, pos = jax.lax.top_k(-dists, k)
    vals = -neg
    return vals, _survivor_ids(ids, pos, vals)


@jax.named_scope("knn.ids")
def _survivor_ids(ids: jax.Array, pos: jax.Array, vals: jax.Array):
    """The ids at the survivors' column positions; slots that hold +inf are
    by definition invalid. ``ids`` (c,) or (q, c)."""
    if ids.ndim == 1:
        out_ids = jnp.take(ids, pos, axis=0)
    else:
        out_ids = jnp.take_along_axis(ids, pos, axis=-1)
    return jnp.where(jnp.isinf(vals), INVALID_ID, out_ids)


def _lane_bin_smallest_k(dists: jax.Array, ids: jax.Array, k: int, depth: int):
    """Exact k smallest of a wide tile without sorting it: lane-bin
    candidates, narrow finish, certificate; the full-width ``lax.top_k``
    runs for this tile step only if some row is flagged."""
    if jax.typeof(dists).vma and jax.default_backend() != "tpu":
        # off the TPU the kernels are interpreted, and jax 0.9.0 cannot
        # interpret a kernel on device-varying operands under a shard_map
        # that checks varying axes (the XLA ring's): its interpreter binds
        # the body's constants unvarying against them. Same values, the
        # full-width way; on the chip Mosaic compiles the kernels there
        # (tests/test_pallas.py compiles the ring's program for the v5e).
        return _top_k_with_ids(dists, ids, k)
    vals, out_ids, flagged = _lane_bin_select(dists, ids, k, depth)
    with jax.named_scope("finish"):
        any_flagged = jnp.any(flagged)
    # the scope holds the cond and all its branch holds, nothing else: its
    # device time in a trace is what failed certificates cost
    with jax.named_scope("fallback"):
        return jax.lax.cond(
            any_flagged,
            lambda: _top_k_with_ids(dists, ids, k),
            lambda: (vals, out_ids),
        )


def smallest_k(
    dists: jax.Array,
    ids: jax.Array,
    k: int,
    method: str = "exact",
    recall_target: float = 0.95,
    block: int = 128,
):
    """Per-row k smallest entries of a (q, c) tile.

    Args:
      dists: (q, c) distances.
      ids: (c,) or (q, c) int32 global candidate ids.
      k: how many to keep. If k > c the result is padded with (+inf, -1).
      method: "exact" = the exact k smallest, ascending: the lane-bin
        selection (module docstring) where ``lane_bin_depth`` engages it —
        ``ids`` 1-D, c a multiple of 128 and >= 1024, k small — else
        ``lax.top_k`` on negated distances. Same values either way; among
        exactly equal distances the two may order ids differently. "approx" =
        lax.approx_min_k (TPU-optimized partial reduction, PAPERS.md TPU-KNN);
        "block" = EXACT two-level reduction — per-``block``-column top-k
        (narrow sorts) followed by a top-k over the nb·k survivors. Every
        global top-k element is in its own block's top-k, so the result is
        identical to "exact"; what changes is the sort width (``block``
        instead of ``c``) — a very wide sort (c ≳ 60k) hung the device on
        an earlier transport; not re-tested on this machine (BASELINE.md);
        "bf16" = near-exact half-width-key preselect (4k candidates by
        bf16 sort, exact f32 finish) — no exactness guarantee, recall is
        measured by the caller's gate;
        "approx-rerank" = the TPU-KNN paper's peak-FLOPs recipe
        (PAPERS.md, arxiv 2206.14286): approx_min_k PRESELECTS 4k
        candidates with overfetch (the per-candidate recall_target can be
        far below the caller's gate — a true top-k member is lost only if
        it falls out of the top-4k of the partial reduction), then an
        exact f32 top-k reranks the survivors. Distinct from "approx",
        which asks the partial reduction for the final k directly and
        therefore needs recall_target ≈ 1 (measured slow, BASELINE.md r3).
      recall_target: recall target for "approx" / the preselect of
        "approx-rerank".
      block: column width of the first-level sort for "block".

    Returns:
      (q, k) dists ascending, (q, k) ids.
    """
    q, c = dists.shape
    if method == "exact":
        depth = lane_bin_depth(q, c, k, ids.ndim)
        if depth is not None:
            return _lane_bin_smallest_k(dists, ids, k, depth)
    if ids.ndim == 1:
        with jax.named_scope("knn.ids"):  # the tile-id plane
            ids = jnp.broadcast_to(ids[None, :], (q, c))
    if k > c:
        pad = k - c
        dists = jnp.pad(dists, ((0, 0), (0, pad)), constant_values=_INF)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=INVALID_ID)
        c = k
    if method == "block" and k <= block and c > block:
        dists, ids = _fold_topk(dists, ids, k, block)
        c = dists.shape[-1]
    if method == "approx-rerank" and c > 4 * k:
        # overfetched approx preselect (cheap partial reduction), exact
        # rerank below. aggregate_to_topk=False: the paper's recipe — the
        # partial reduction's RAW per-bin winners go straight to the exact
        # rerank; the default True would insert a redundant exact top-4k
        # aggregation between the reduce and the rerank. Recall can only
        # improve: the raw winner set is a superset of its own top-4k.
        dists, ids = _pad_lanes(dists, ids)
        dists, pos = jax.lax.approx_min_k(
            dists, 4 * k, recall_target=recall_target,
            aggregate_to_topk=False,
        )
        ids = jnp.take_along_axis(ids, pos, axis=-1)
        c = dists.shape[-1]
    if method == "bf16" and c > 4 * k and dists.dtype == jnp.float32:
        # preselect 4k candidates by sorting HALF-WIDTH keys (bf16 compare
        # is monotone in the f32 values it rounds from), then finish with
        # an exact f32 top-k over the survivors. Near-exact: a true top-k
        # member can only be lost if >3k candidates round into the same
        # bf16 value at the boundary — the recall gate measures it (the
        # method makes no exactness claim).
        pre = 4 * k
        pos = preselect_smallest(dists, pre, half_width=True)
        dists = jnp.take_along_axis(dists, pos, axis=-1)
        ids = jnp.take_along_axis(ids, pos, axis=-1)
        c = pre
    if method == "approx" and c > k:
        dists, ids = _pad_lanes(dists, ids)
        vals, pos = jax.lax.approx_min_k(dists, k, recall_target=recall_target)
        return vals, _survivor_ids(ids, pos, vals)
    return _top_k_with_ids(dists, ids, k)


def cascade_smallest_k(
    dists: jax.Array,
    ids: jax.Array,
    k: int,
    method: str = "exact",
    recall_target: float = 0.95,
    block: int = 128,
    max_width: int = 8192,
):
    """``smallest_k`` for arbitrarily wide candidate rows: while the row is
    wider than ``max_width``, fold it by a per-chunk top-k (chunks of
    ``max_width`` columns → k survivors each), then finish with one narrow
    ``smallest_k``. Exact when ``method`` is exact/block (each fold keeps
    every possible global top-k element). Used by the two-level merge
    schedule, whose concatenated per-tile survivors can reach
    n_tiles·k ≫ 8k columns at SIFT scale with large k."""
    q, c = dists.shape
    if ids.ndim == 1:
        ids = jnp.broadcast_to(ids[None, :], (q, c))
    # fold width must be >= 2k: chunks narrower than k would break top_k, and
    # chunks of exactly k would make no progress (ceil(c/k)·k >= c)
    fold_w = max(max_width, 2 * k)
    while dists.shape[-1] > fold_w:
        dists, ids = _fold_topk(dists, ids, k, fold_w)
    return smallest_k(
        dists, ids, k, method=method, recall_target=recall_target, block=block
    )


def merge_topk(
    carry_d: jax.Array,
    carry_i: jax.Array,
    new_d: jax.Array,
    new_i: jax.Array,
    method: str = "exact",
    recall_target: float = 0.95,
    block: int = 128,
):
    """Merge two per-query top-k lists into one: top_k over the concatenation.

    O(k log k) per query on device; replaces the reference's per-candidate
    qsort churn. Associative and commutative over candidate multisets, which
    is what lets the ring rotate corpus blocks in any order.
    """
    k = carry_d.shape[-1]
    d = jnp.concatenate([carry_d, new_d], axis=-1)
    i = jnp.concatenate([carry_i, new_i], axis=-1)
    return smallest_k(d, i, k, method=method, recall_target=recall_target,
                      block=block)


# relative tolerance for "numerically zero" squared distances: the matmul form
# ‖x‖²+‖y‖²−2xy leaves an exact-duplicate pair at cancellation-error scale
# (a few ulps of ‖x‖²) rather than exactly 0, so the zero test must be
# relative to the pair's magnitude or it never fires at realistic data scales.
# Measured error at Precision.HIGHEST is ~2e-7·scale (f32); 1e-6 gives ~5x
# margin while staying far below genuine neighbor distances on *centered*
# data (the backends mean-center L2 inputs precisely so this holds).
_ZERO_RTOL = {jnp.dtype(jnp.float64): 1e-12}
_ZERO_RTOL_DEFAULT = 1e-6


def mask_tile(
    dists: jax.Array,
    cand_ids: jax.Array,
    query_ids: jax.Array | None = None,
    exclude_self: bool = True,
    exclude_zero: bool = True,
    zero_eps: float = 0.0,
    scale: jax.Array | None = None,
    keep: jax.Array | None = None,
) -> jax.Array:
    """Apply validity/exclusion masks to a (q, c) distance tile.

    - padding: candidates with id < 0 (sentinel rows from divisibility
      padding, SURVEY.md §8) are forced to +inf;
    - self-exclusion by id: exact leave-one-out (robust under fp, unlike the
      reference's value test);
    - zero-exclusion by value: the reference's actual rule ``sqrt(S) != 0``
      (``/root/reference/knn-serial.c:86``), which also drops exact duplicate
      points — kept for recall parity (SURVEY.md Q3). With the default
      ``zero_eps=0`` the threshold is *relative*: ``rtol · scale`` when a
      per-pair magnitude ``scale`` (q, c) — e.g. ``x_sq + y_sq`` — is given,
      else a strict ``d <= 0`` test;
    - a predicate's plane ``keep`` (q, c) bool, where a tagged index's
      batch brings one: a candidate the query row's predicate does not
      hold for is forced to +inf like the others.
    """
    q, c = dists.shape
    if cand_ids.ndim == 1:
        cand_ids = jnp.broadcast_to(cand_ids[None, :], (q, c))
    invalid = cand_ids < 0
    if exclude_zero:
        if zero_eps > 0.0:
            thresh = zero_eps
        elif scale is not None:
            rtol = _ZERO_RTOL.get(jnp.dtype(dists.dtype), _ZERO_RTOL_DEFAULT)
            thresh = rtol * scale
        else:
            thresh = 0.0
        invalid = invalid | (dists <= thresh)
    if exclude_self and query_ids is not None:
        invalid = invalid | (cand_ids == query_ids[:, None])
    if keep is not None:
        invalid = invalid | ~keep
    return jnp.where(invalid, _INF, dists)
