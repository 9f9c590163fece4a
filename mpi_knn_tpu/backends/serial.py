"""Serial (single-device) backend — the ground-truth execution path,
replacing the reference's serial driver (SURVEY.md C5,
``/root/reference/knn-serial.c:36-133``).

Same math as the distributed backends, unsharded: the (q × c) distance
problem is tiled into MXU-sized blocks; a ``lax.scan`` streams corpus tiles
through VMEM while a per-query top-k carry is merged tile by tile, and a
``lax.map`` walks query tiles so peak memory is
O(query_tile × corpus_tile + q × k) instead of the reference's full
m × NN neighbour matrix on the *stack* (~28.8 MB of VLAs,
``/root/reference/knn-serial.c:54-55``).

``serve_chunk`` is the one core: the plain serial path prepares its corpus
once (:class:`SerialCorpus`: the tile stack, ids and norms) and runs it
under a jit over all corpus tiles (``_search_stack``), as the serving
engine does over a resident index; ``knn_chunk_update`` is the same body
with the chunk's norms computed inside, which the resumable driver
(backends.resumable) calls per checkpoint round with the carry threaded
through; the ring backends run ``knn_tile_step`` against each rotating
block.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.ops.distance import (
    COSINE_SCOPE,
    IP_SCOPE,
    bf16_exact,
    byte_rows,
    center_corpus,
    cosine_inv_norms,
    onepass_applies,
    onepass_fact,
    pairwise_dist,
    pairwise_sq_l2,
    sq_norms,
    unit_rows,
    widen_rows,
)
from mpi_knn_tpu.ops.rerank import compress_rerank_tile, rerank_exact_topk
from mpi_knn_tpu.ops.topk import (
    cascade_smallest_k,
    fused_scan_engages,
    init_topk,
    init_topk_tiles,
    lane_bin_bound_rides,
    lane_bin_depth,
    mask_tile,
    merge_topk,
    smallest_k,
)
from mpi_knn_tpu.parallel.partition import (
    make_global_ids,
    pad_cols,
    pad_rows_any,
    pad_to_multiple,
)
from mpi_knn_tpu.types import INVALID_ID


# the branches of the one-pass rule, as the trace names them, nested in
# ``knn.dist`` (a program without the rule keeps plain ``knn.dist``). Full
# ``knn.*`` names, so that a reduction of the trace by innermost ``knn.*``
# scope (the ring cell's ``ring_scopes``) keeps the branches apart.
ONEPASS_SCOPE = "knn.dist_onepass"
MULTIPASS_SCOPE = "knn.dist_multipass"
# the one-pass branch where one kernel walks the whole stack
# (:func:`fused_rule`): the dot, the masks, the bound's test and *bins* of
# every tile step are inside it
FUSED_SCOPE = "knn.fused"
# the one-pass branch over a BYTE stack (``dtype="uint8"``,
# ``ops/distance.py widen_rows``), around whatever walks it — the kernel
# (``knn.scan_u8/knn.fused``) or the scan's tile steps (``knn.scan_u8/
# knn.dist/knn.dist_onepass``, ``knn.scan_u8/knn.select``): the device time
# under it in a trace is what a batch spends over the bytes at rest. The
# other branch, over the widened rows, keeps ``knn.dist_multipass``.
U8_SCOPE = "knn.scan_u8"
# query-tile height from which a tile program carries the rule's branch.
# The conditional costs one copy of the corpus tile a step (8 bytes an
# element at the HBM rate) and saves passes - 1 of the dot's 2·q FLOPs an
# element: on the v5e (197 TFLOP/s, 819 GB/s) it breaks even at q = 962 /
# (passes - 1) rows, 481 at ``high`` and 192 at ``highest``; below, a step
# is bound by reading its tile at any pass count and gains nothing. The
# branch also costs set-up: a program that carries it traces and lowers its
# step twice, ~0.4 s at every process start on the chip's host (PERF.md §6,
# PR 29). Twice the break-even of ``high`` pays for both.
ONEPASS_MIN_ROWS = 1024
# the same for a tagged index's filtered programs (:func:`serve_chunk_filtered`):
# a coalesced batch splits by regime (``serve/tags.py``), so its scan part
# is rarely a whole 1024-row bucket, and without the branch a 512-row part
# would run the float32 dot in six passes and cost what 1024 rows cost.
# From the break-even of ``highest`` up; only those programs pay the
# second trace.
FILTER_ONEPASS_MIN_ROWS = 256


def onepass_rule(cfg: KNNConfig, q_rows: int, filtered: bool = False) -> bool:
    """Whether a tile program of ``q_rows``-row query tiles carries the
    one-pass branch: ``ops.distance.onepass_applies`` and the height
    (``filtered``: the program masks by a predicate)."""
    return onepass_applies(cfg) and q_rows >= (
        FILTER_ONEPASS_MIN_ROWS if filtered else ONEPASS_MIN_ROWS)


# where a program without the one-pass branch counts its tile steps, by
# metric: the column of ``obs.metrics.DIST_PATHS``
_STATIC_PATH = {"l2": 1, "cosine": 2, "ip": 4}
# and where one whose screened steps run inside the kernel does
_FUSED_SCREEN_PATH = 6


def dist_steps(took, steps: int, metric: str = "l2", fused: bool = False,
               u8: bool = False, fused_screen: bool = False):
    """A dispatch's tile steps by the path of their distance dot, int32
    ``[one-pass, multi-pass]`` — from a cosine program ``[0, 0, cosine]``,
    from an inner-product program ``[0, 0, 0, 0, ip]``, from one whose
    one-pass steps run inside the fused kernel
    (:func:`fused_rule`) ``[0, multi-pass, 0, fused]``, from one whose
    one-pass steps walk a byte stack (kernel or tile steps)
    ``[0, multi-pass, 0, 0, 0, u8]``, from one with no branch whose
    screened steps run inside the fused kernel's three-pass form
    (:func:`fused_screen_rule`) ``[0, 0, 0, 0, 0, 0, fused_screen]``: what
    ``KNNResult.dist_steps`` and the counter ``knn_dist_tile_steps_total``
    hold. ``took`` is one verdict a query-tile merge (a bool vector, made
    inside a program that carries the branch) or, for a program without
    the branch, their number; each merge meets ``steps`` corpus tiles."""
    if isinstance(took, int):
        if metric not in _STATIC_PATH:
            raise ValueError(f"unknown metric {metric!r}")
        counts = np.zeros(
            _FUSED_SCREEN_PATH + 1 if fused_screen
            else _STATIC_PATH[metric] + 1, dtype=np.int32)
        counts[-1] = took * steps
        return counts
    one = jnp.sum(took, dtype=jnp.int32)
    multi = took.size - one
    if u8:
        return jnp.stack([0, multi, 0, 0, 0, one]) * steps
    if fused:
        return jnp.stack([0, multi, 0, one]) * steps
    return jnp.stack([one, multi]) * steps


class TileCounts(typing.NamedTuple):
    """What a tile program counts on the device and returns beside its
    answer, third of its outputs (a program that counts neither returns
    two); a field is None where the program does not count it."""

    # :func:`dist_steps`, from a program that carries the one-pass branch
    dist_steps: jax.Array | None = None
    # :func:`select_tiles`, from a program whose scans carry the lists
    select_tiles: jax.Array | None = None
    # int32 ``[inserted, skipped]``: the chunks of the distance tiles (16
    # rows x 1024 columns) by what became of them in *bins* under the row
    # bound (:func:`_merge_carried`), from a program whose scans carry one
    bins_chunks: jax.Array | None = None
    # int32 ``[probes, bucket_cap, live rows, distinct partitions, their
    # live rows, work items walked, those in one pass]``: what a clustered
    # batch probed
    # (``ivf/search.py probe_counts``), from a clustered index's program
    # alone
    ivf_probe: jax.Array | None = None
    # int32 ``[certified, flagged]``: the query rows by the verdict of the
    # SCREEN's certificate (:func:`screen_eps`; the lanes' flags count in
    # ``select_tiles``), from a program whose scans rank in three passes
    # (:func:`screen_rule`)
    screen_rows: jax.Array | None = None


def select_tiles(rescanned: jax.Array):
    """A dispatch's query-tile merges by what became of the carried
    selection, int32 ``[carried, rescanned]``: what
    ``KNNResult.select_tiles`` and the counter
    ``knn_select_query_tiles_total`` hold. ``rescanned`` is one verdict a
    merge (:func:`merge_tiles_into_carry`'s third output): some row failed
    the certificate and the flagged rows were answered again."""
    again = jnp.sum(rescanned, dtype=jnp.int32)
    return jnp.stack([rescanned.size - again, again])


def tile_counts(rest: tuple, merges: int, steps: int,
                metric: str) -> TileCounts:
    """A dispatch's :class:`TileCounts` from what its program returned
    beyond (dists, ids): in a program without the one-pass branch each of
    the ``merges`` query-tile merges met its ``steps`` corpus tiles on the
    one path the program holds, a static count."""
    counts = rest[0] if rest else TileCounts()
    if counts.dist_steps is None:
        counts = counts._replace(
            dist_steps=dist_steps(merges, steps, metric))
    return counts


def carried_depth(cfg: KNNConfig, q_rows: int, c_tile: int,
                  varying: bool = False) -> int | None:
    """The lane-bin depth at which a ``twolevel`` merge of (q_rows x c_tile)
    tile steps carries its lists through the scan, or None: the per-tile
    program. The test ``smallest_k`` makes of a tile with 1-D ids, where
    the exact policy and method put every tile of the stack through it.
    ``varying``: the operands vary over a checked ``shard_map``'s axes (the
    XLA ring), under which the kernels — *bins*, *finish* and the one that
    walks a whole stack (:func:`fused_rule`) — run on the TPU only
    (``ops/topk.py _lane_bin_smallest_k``)."""
    if (cfg.merge_schedule != "twolevel" or cfg.precision_policy != "exact"
            or cfg.topk_method != "exact"
            or (varying and jax.default_backend() != "tpu")):
        return None
    return lane_bin_depth(q_rows, c_tile, cfg.k)


def fused_rule(cfg: KNNConfig, q_rows: int, c_tile: int, dim: int,
               varying: bool = False, filtered: bool = False) -> int | None:
    """Whether the one-pass branch of an engaged merge of (q_rows x
    c_tile) tile steps at width ``dim`` is ONE kernel over the whole stack
    (``ops/fused_scan.py``) — the height of the row blocks it walks the
    query tile in, or None: the program carries the one-pass rule
    (:func:`onepass_rule`) and the lists (:func:`carried_depth`), and the
    shapes pass ``ops/topk.py fused_scan_engages`` (a block height at
    which the bound rides and the kernel's VMEM fits; a stack that rests
    in a form the kernel takes). ``varying`` (the operands vary over a
    checked ``shard_map``'s axes: the XLA ring's rounds, whose arriving
    block is the stack): the same answer on the TPU, where the kernel is
    typed for the check (``ops/lane_bin.py _out``), and None elsewhere —
    :func:`carried_depth`'s condition: jax's Pallas interpreter cannot run
    under the check, so the CPU ring keeps the per-tile program it has.
    ``filtered`` (a predicate's words ride the scan, :func:`filter_words`):
    the kernel takes them as one operand more and masks by them itself, at
    every bucket height that carries the one-pass branch of a filtered
    program and at which the bound rides (256, 512 and 1024 rows: 64 and
    128 keep the scan of tile steps), over a float32 stack whose tiles'
    words are whole lane-aligned vectors (``fused_scan_engages``)."""
    if not onepass_rule(cfg, q_rows, filtered):
        return None
    depth = carried_depth(cfg, q_rows, c_tile, varying)
    if depth is None:
        return None
    return fused_scan_engages(
        q_rows, c_tile, dim, depth, jnp.dtype(cfg.dtype).itemsize,
        filtered=filtered)


# --- the certified screen ---------------------------------------------------
# The pieces a bf16 pass multiplies are bfloat16 numbers, 8 significant
# bits: whichever way the hardware cuts them (truncation or rounding),
# |x - x1| <= 2^-7 |x| for the first piece x1 and |x - x1 - x2| <= 2^-14 |x|
# for the second, |x - x1 - x2 - x3| <= 2^-21 |x| for the third.
#
# c_P, the THREE-pass dot (``high``: x1 y1 + x1 y2 + x2 y1) against the real
# product, an element: x y - kept = x2 y2 + rx y + (x - rx) ry with rx, ry
# the residues after two pieces, so
#   |x2 y2|      <= (2^-7 + 2^-14)^2 |x y|  = 2^-14 (1 + 2^-7)^2 |x y|
#   |rx y|       <= 2^-14 |x y|
#   |(x - rx)ry| <= 2^-14 (1 + 2^-14) |x y|
# together under 3.02 * 2^-14 |x y|; summed over the width, sum |x_i y_i| <=
# |x| |y| (Cauchy-Schwarz): 3.02 * 2^-14 |x| |y|, rounded UP to 2^-12.
_SCREEN_SPLIT = 2.0 ** -12
# the SIX-pass dot (``highest``: the three above + x1 y3 + x3 y1 + x2 y2)
# drops x2 y3 + x3 y2 + x3 y3 + rx y + (x - rx) ry with three-piece
# residues: 2 * 2^-21 + 2^-28 + 2 * 2^-21 (1 + ...) < 4.1 * 2^-21 of |x| |y|,
# rounded UP to 2^-18
_FINISH_SPLIT = 2.0 ** -18
# float32 accumulation. A product of two bf16 pieces is exact in float32
# (16 significant bits). A P-pass dot is P one-pass dots — each adds its d
# products in float32, in an order the hardware chooses — whose P results
# are added in float32 (XLA's ``BF16_BF16_F32_X3`` / ``_X6``: "3 / 6
# BF16_BF16_F32 matmuls"): a product passes through at most d - 1 + P - 1
# <= d + 4 additions, each off by at most 2^-23 of its result (2^-24
# rounding to nearest; 2^-23 covers an adder that truncates), so a dot is
# off by at most (d + 4) 2^-23 sum |pieces' products| <= (d + 4) 2^-23
# (1 + 2^-5) |x| |y|. The same bound holds for a float32 multiply-and-add
# chain of d terms (each product rounded once, d - 1 additions).
#
# The screen INSIDE the kernel (``ops/fused_scan.py``, its three-pass form;
# :func:`fused_screen_rule`) is another program, and its term is its own.
# The pieces: x1 is CUT from the float32's bits (|x - x1| < 2^-7 |x|), x - x1
# is then exact in float32 and x2 is it rounded to nearest (|x - x1 - x2| <=
# 2^-8 |x - x1| < 2^-15 |x|): inside the 2^-7 and 2^-14 that c_P assumes, so
# :data:`_SCREEN_SPLIT` holds as it stands. The query side is -2 x: a power
# of two scales both pieces exactly and the dot is -2 times the dot of x,
# which the L2 form's ``2 K |q| R`` already says. The sum: ONE dot of K =
# 3 d, the pieces side by side — its 3 d products (exact in float32, as
# above) are added in float32 in whatever order the MXU's accumulator takes
# them; in ANY summation tree of n terms a term passes through at most
# n - 1 additions, so the dot is off by at most (3 d - 1) 2^-23 sum
# |pieces' products|. (Three d-long sums and two additions, the form XLA
# gives, put a product through d + 1: under the same bound.) Then the
# kernel adds ``x_sq`` and ``y_sq`` in ``pairwise_sq_l2``'s order, two
# additions and the clamp, which :data:`_ELEMENTWISE` counts as it counts
# the XLA screen's; the bound's test on the unclamped sum only MARKS
# chunks (a superset: a bound is never negative) and no value comes of it.
_ACC_UNIT = 2.0 ** -23
# the roundings outside the dot (scaling by the inverse norm, 1 - sim, the
# two additions of the L2 form) in the screen and in the finish, and the
# rounding of the certificate's own comparison: at most 8 of them, each
# 2^-23 of the value's scale
_ELEMENTWISE = 8 * _ACC_UNIT
# what the first-order terms above leave out, as one factor: the pieces'
# products add up to (1 + 2^-5) |x| |y| at most, (1 + u)^n - 1 <= n u
# (1 + 2^-7) while the width is at most 2^15, and |q|, R are themselves
# float32 sums (off by (d / 2 + 2) 2^-24 <= 2^-9 of their value)
_SCREEN_SLACK = 1.0 + 2.0 ** -4
_SCREEN_MAX_DIM = 1 << 15


def screen_width(k: int) -> int:
    """k', the candidates a screened scan keeps a row: ``3k + 2``, 32 at
    k = 10. The certificate needs a row's k-th and k'-th smallest values
    :func:`screen_eps` apart (and the screen's own error on top), so
    k' - k is how many neighbours a row may have inside that gap before
    it is flagged — a count that goes with the neighbours' density at the
    k-th, which grows about as k does. At the embedding cell's law (rows of
    a class at cosine distance 0.2 +- 7e-3, ~984 a class, eps 6.5e-4) the
    gap from the 10th to the 24th neighbour is 2.2e-3 on average and under
    7e-4 for one row in 10^4 (20 000 rows drawn from the law: every tenth
    batch would re-scan), to the 32nd 2.9e-3 and never under 1.1e-3: 32
    costs the lists a seventh column group (``ops/topk.py
    lane_bin_depth``: depth 6 at 24, 7 at 32) and buys four orders of
    magnitude. A constant of the rule, no setting."""
    return 3 * k + 2


def screen_rule(cfg: KNNConfig, q_rows: int, c_tile: int, dim: int, *,
                branch: bool = False, filtered: bool = False,
                varying: bool = False) -> int | None:
    """Whether an engaged merge of (q_rows x c_tile) tile steps at width
    ``dim`` SCREENS: ranks by a three-pass dot, keeps k' candidates a row
    and finishes them at the configured precision under a certificate
    (:func:`_merge_carried`). Returns k' (:func:`screen_width`) or None, by
    what the program and its operands are — no setting:

    - the lists are carried (:func:`carried_depth`, asked for k' too:
      ``twolevel``, the exact policy and method, k' <= 128);
    - float32 rows whose dot resolves to ``highest``, six passes: three of
      them are then what there is to save (``high``, ``default`` and bf16
      stacks keep their programs: nothing is screened below the stated
      precision);
    - ``branch`` is False: the program carries no one-pass branch — a
      whole-number corpus is ranked exactly in ONE pass already;
    - q_rows >= ``ONEPASS_MIN_ROWS``: below, a step is bound by reading
      its tile at any pass count and would only pay the finish;
    - no predicate's words ride the scan (``filtered``), the operands do
      not vary over a mesh (``varying``: the ring);
    - dim % 128 == 0, ``dim`` the width the stack RESTS at: the v5e rests
      such a stack row-major (``ops/topk.py fused_scan_engages``), so a
      candidate's row is one contiguous read; off the lane grid the stack
      rests rows-minor and a gathered row is ``dim`` scalars — which is
      why a build whose rows are off the grid asks this rule at the padded
      width and, where it engages, rests the stack there
      (``serve/index.py rest_width``). And dim <= 2^15, which
      :func:`screen_eps` assumes."""
    if (branch or filtered or varying or q_rows < ONEPASS_MIN_ROWS
            or dim % 128 or dim > _SCREEN_MAX_DIM
            or cfg.dtype != "float32"
            or cfg.matmul_precision not in (None, "highest")
            or cfg.metric not in _STATIC_PATH):
        return None
    wide = screen_width(cfg.k)
    if (carried_depth(cfg, q_rows, c_tile) is None
            or lane_bin_depth(q_rows, c_tile, wide) is None):
        return None
    return wide


def fused_screen_rule(cfg: KNNConfig, q_rows: int, c_tile: int, dim: int,
                      **facts) -> int | None:
    """Whether the SCREENED scan of an engaged merge is one call of the
    kernel that walks the whole stack, in its three-pass form
    (``ops/fused_scan.py``) — the height of the row blocks it walks the
    query tile in, or None: the XLA scan of three-pass tile steps, or no
    screen at all. By what the program is, no setting: :func:`screen_rule`
    engages (``facts``: its ``branch`` / ``filtered`` / ``varying``), the
    metric is L2 (the kernel's value is ``x_sq - 2 xy + y_sq``; cosine
    scales its dot by a plane and an inner product has no norm to test
    against) and the shapes pass ``ops/topk.py fused_scan_engages`` at the
    screen's depth with the three-pass form's own VMEM (1024 rows x 8192
    columns at d = 128: the streaming cell's bucket; d = 1536 passes at no
    height)."""
    wide = screen_rule(cfg, q_rows, c_tile, dim, **facts)
    if wide is None or cfg.metric != "l2":
        return None
    return fused_scan_engages(
        q_rows, c_tile, dim, lane_bin_depth(q_rows, c_tile, wide), 4,
        passes=3)


def screen_additions(dim: int, fused: bool = False) -> int:
    """The float32 additions a product passes through in the screen's dot
    and in the finish's, together (the certificate compares a value of the
    one with a value of the other), each worth :data:`_ACC_UNIT`: the
    six-pass finish ``dim + 4``; the XLA screen (three one-pass dots and
    two additions) ``dim + 4``; the kernel's (``fused``: one dot of K =
    3 dim in an order that is the hardware's) ``3 dim - 1``. Derived above
    :data:`_ACC_UNIT`; a constant of the program's form."""
    return (3 * dim - 1 if fused else dim + 4) + dim + 4


def screen_eps(metric: str, dim: int, q_x: jax.Array,
               q_sq: jax.Array | None, r_sq, fused: bool = False) -> jax.Array:
    """(q,) float32: for every query row a WORST-CASE bound on |value the
    three-pass screen ranked a corpus row by - value the six-pass finish
    would give that row|, for ANY row of the stack. The certificate of a
    screened merge: with ``s`` the k'-th smallest screen value of a row
    and ``tau`` the k-th smallest finished value among its k' candidates,
    every row that is NOT a candidate has a screen value >= ``s``, so its
    finished value would be >= ``s - eps``; if ``tau + eps <= s`` no such
    row can enter the first k, and the candidates' k smallest finished
    values are the stack's.

    ``eps = K |q| R' + E V`` with, against the real value of the same
    float32 operands (the query row, the corpus row, the norm planes'
    entries, which both dots read):

    - ``K = (c_P + c_6 + A 2^-23) (1 + 2^-4)``: the products the
      three-pass split drops (:data:`_SCREEN_SPLIT`, 2^-12), those the
      six-pass split drops (:data:`_FINISH_SPLIT`, 2^-18), the float32
      accumulation of BOTH dots (:data:`_ACC_UNIT`,
      :func:`screen_additions`: ``A = 2 (d + 4)`` for the XLA screen,
      ``4 d + 3`` where the screen ran inside the kernel, ``fused``) — the
      certificate compares a value of the one with a value of the other —
      and the second-order terms (:data:`_SCREEN_SLACK`); each derived
      where it is defined, above. At d = 1536: 6.5e-4; at d = 128, 3.0e-4
      and in the kernel 3.3e-4.
    - ``|q| R'``, the size of the dot: cosine ``|q|`` (the query's unit
      row; a corpus row's norm times its stored inverse norm is at most 1,
      the scaling cancels), inner product ``|q| R``, L2 ``2 |q| R`` (the
      form's ``-2 q.c``, centred rows), R the largest row norm of the
      stack (``r_sq`` its square).
    - ``E V``, the roundings outside the dots (:data:`_ELEMENTWISE`):
      cosine V = 1 (values in [0, 2]) — and 0 for an all-zero query row,
      whose values are exactly 1 in both dots: a batch's padding rows tie
      everywhere and must not be flagged —, inner product ``|q| R``, L2
      ``(|q| + R)^2``.

    On the CPU both dots are float32's own and the bound is merely loose.
    A bound that is too loose costs re-scans; one that is too tight is a
    wrong answer: nothing here is measured."""
    if dim > _SCREEN_MAX_DIM:
        raise ValueError(f"the screen's bound assumes dim <= 2^15: {dim}")
    acc = jnp.float32
    K = (_SCREEN_SPLIT + _FINISH_SPLIT
         + screen_additions(dim, fused) * _ACC_UNIT) * _SCREEN_SLACK
    q_norm = jnp.sqrt(sq_norms(q_x) if q_sq is None else q_sq).astype(acc)
    if metric == "cosine":
        return K * q_norm + _ELEMENTWISE * (q_norm > 0)
    r = jnp.sqrt(r_sq).astype(acc)
    if metric == "ip":
        return (K + _ELEMENTWISE) * q_norm * r
    if metric == "l2":
        return 2 * K * q_norm * r + _ELEMENTWISE * (q_norm + r) ** 2
    raise ValueError(f"unknown metric {metric!r}")


def largest_norm_sq(metric: str, tiles: jax.Array,
                    tile_sqs: jax.Array | None):
    """R^2 of :func:`screen_eps` for a stack: the largest squared row norm,
    from the norm plane where the metric keeps squared norms (L2), from
    the rows themselves where it keeps none (an inner product: one pass
    over the stack), None where the bound needs none (cosine). Every slot
    counts, live or not: a free slot reads 0 and a deleted row's slot keeps
    its norm until it is written again (a delete is the id plane's), so in
    an index that takes writes this is an UPPER bound on the live rows' —
    which is all the certificate asks of it."""
    if metric == "l2":
        return jnp.max(tile_sqs)
    if metric == "ip":
        return jnp.max(jax.vmap(sq_norms)(tiles))
    return None


@jax.named_scope("knn.dist")
def masked_dist_tile(
    q_x: jax.Array,
    q_ids: jax.Array,
    q_sq: jax.Array | None,
    blk: jax.Array,
    blk_ids: jax.Array,
    blk_sq: jax.Array | None,
    cfg: KNNConfig,
    onepass: bool | None = None,
    keep: jax.Array | None = None,
    screen: bool = False,
) -> jax.Array:
    """(q_tile × c_tile) masked distances: metric kernel → padding/self/zero
    exclusion masks. The compute half shared by both merge schedules and the
    ring backends.

    ``screen`` (static; :func:`screen_rule`): the tile RANKS and returns
    nothing — the dot runs at ``high``, three bf16 passes, under the same
    scope, every value within :func:`screen_eps` of what the configured
    precision gives; the masks by id (padding, self) are exact at any
    precision and stay, the zero test by VALUE waits for the finish's
    exact values (``ops/rerank.py``'s split: a true near neighbour must
    not be dropped on the evidence of a rounded distance).

    ``keep`` (a tagged index's batches: :func:`filter_words`) is the
    predicate's plane for this tile step, (q_tile, c_tile / 32) uint32
    words, a bit a (query row, corpus slot): the fourth mask, expanded and
    applied with the other three (``ops/topk.py mask_tile``).

    ``onepass`` (static) says which branch of the one-pass rule this step
    is traced for (:func:`merge_tiles_into_carry` holds the ``lax.cond``):
    True, both operands are bf16 numbers and the dot is one bf16 x bf16
    pass, which for them returns what the configured precision returns;
    False, the dot at ``cfg.matmul_precision``; None, the same in a program
    that carries no such branch. Norms and masks are the same in all.

    Cosine (scope ``knn.dist_cosine``): ``blk_sq`` given is the corpus
    side prepared — the rows' inverse norms (:func:`stack_norms`) — and
    ``q_x`` then holds unit rows (:func:`serve_chunk` makes them once a
    query tile): the step scales its dot and normalises nothing. None (the
    ring's rounds) normalises both operands here, every step.

    Inner product (scope ``knn.dist_ip``): the step is the dot, negated,
    and the masks; ``q_sq`` and ``blk_sq`` are None (neither side has a
    norm in a score), nothing is clamped and there is no zero test
    (``KNNConfig`` reads ``exclude_zero`` False under ``ip``)."""
    if onepass is not None:
        scope = jax.named_scope(ONEPASS_SCOPE if onepass else MULTIPASS_SCOPE)
    elif cfg.metric == "cosine":
        scope = jax.named_scope(COSINE_SCOPE)
    elif cfg.metric == "ip":
        scope = jax.named_scope(IP_SCOPE)
    else:
        scope = contextlib.nullcontext()
    with scope:
        return _masked_dist_tile(
            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, onepass, keep,
            screen)


def _masked_dist_tile(q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, onepass,
                      keep=None, screen=False):
    """:func:`masked_dist_tile` under no scope of its own (the re-scan of
    flagged rows sits in ``knn.select/fallback`` with all it runs)."""
    if onepass:
        d = pairwise_sq_l2(q_x, blk, x_sq=q_sq, y_sq=blk_sq, onepass=True)
    else:
        d = pairwise_dist(
            q_x,
            blk,
            metric=cfg.metric,
            x_sq=q_sq,
            y_sq=blk_sq,
            precision="high" if screen else cfg.matmul_precision,
        )
    if cfg.metric == "l2" and q_sq is not None and blk_sq is not None:
        pair_scale = q_sq[:, None] + blk_sq[None, :]
    elif cfg.metric in ("l2", "cosine"):
        # cosine distances live in [0, 2]; constant scale for the zero test
        pair_scale = jnp.asarray(2.0, dtype=d.dtype)
    elif cfg.metric == "ip":
        # an unbounded score has no scale, and no zero test to hand one to
        pair_scale = None
    else:
        raise ValueError(f"unknown metric {cfg.metric!r}")
    return mask_tile(
        d,
        blk_ids,
        query_ids=q_ids if cfg.exclude_self else None,
        exclude_self=cfg.exclude_self,
        exclude_zero=cfg.exclude_zero and not screen,
        zero_eps=cfg.zero_eps,
        scale=pair_scale,
        keep=None if keep is None else filter_keep(keep, blk.shape[0]),
    )


_FILTER_NEEDS_EXACT = (
    "a filtered batch needs precision_policy='exact': the compress pass of "
    "'mixed' overfetches by value and knows no predicate")

# the predicate's part of a tile step, as the trace names it: the words'
# expansion to the (q_tile, c_tile) plane (the gather of a batch's words
# from the index's bitsets sits ahead of the scan, under the same name)
FILTER_MASK_SCOPE = "knn.filter_mask"


def filter_words(tag_bits: jax.Array, q_tags: jax.Array) -> jax.Array:
    """(T, q_tile, c_tile / 32) uint32: for every corpus tile the words of
    a query tile's predicate, a bit a (query row, slot of the tile), set
    where the slot's row holds EVERY tag of the query row. ``tag_bits``
    (F + 1, T, c_tile / 32) is a tagged index's bitsets
    (``serve/tags.py``: one row a frequent tag, the last row all ones),
    ``q_tags`` (q_tile, W) int32 the query rows' bitset rows (F: no
    constraint). Made once a query tile, ahead of its scan over the
    stack, whose steps each take one tile's (q_tile, c_tile / 32) slice."""
    with jax.named_scope(FILTER_MASK_SCOPE):
        words = tag_bits[q_tags[:, 0]]
        for w in range(1, q_tags.shape[1]):
            words = words & tag_bits[q_tags[:, w]]
        return jnp.swapaxes(words, 0, 1)


def filter_keep(words: jax.Array, c_tile: int) -> jax.Array:
    """The (q_tile, c_tile) bool plane of a tile step's predicate from its
    words (:func:`filter_words`): slot ``c`` of the tile is bit ``c //
    (c_tile / 32)`` of word ``c % (c_tile / 32)``, so that the plane is the
    words shifted 32 times, whole, laid side by side: no lane moves. (The
    v5e compiler lays the broadcast words out anew, one copy a step in
    fast memory, and shifts and tests inside the step's distance fusion;
    written as 32 shifted pieces concatenated it runs 32 small fusions a
    step: read in the program compiled for the chip.)"""
    with jax.named_scope(FILTER_MASK_SCOPE):
        shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
        bits = (words[:, None, :] >> shifts) & jnp.uint32(1)
        return bits.reshape(words.shape[0], c_tile) != 0


def local_tile_topk(
    q_x: jax.Array,
    q_ids: jax.Array,
    q_sq: jax.Array | None,
    blk: jax.Array,
    blk_ids: jax.Array,
    blk_sq: jax.Array | None,
    cfg: KNNConfig,
    out_dtype,
    onepass: bool | None = None,
    keep: jax.Array | None = None,
):
    """One corpus tile's (q, k) survivors — the per-tile reduction both
    merge schedules share, switched on ``cfg.precision_policy``:

    - "exact": one distance pass at ``cfg.matmul_precision`` (HIGHEST by
      default for f32), then ``smallest_k`` per ``cfg.topk_method`` — with
      the 1-D tile id vector, so "exact" can take the lane-bin selection
      (ops/topk.py) instead of sorting the tile;
    - "mixed": the compress-and-rerank two-pass pipeline (ops/rerank.py) —
      a DEFAULT-precision bf16 compress dot overfetches 4k candidates, a
      HIGHEST rerank of the gathered survivors finishes exactly. The tile's
      contribution to any downstream merge is exact-f32 either way, so the
      carry/checkpoint algebra is policy-independent.
    """
    if cfg.precision_policy == "mixed":
        if keep is not None:
            raise ValueError(_FILTER_NEEDS_EXACT)
        ld, li = compress_rerank_tile(
            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg
        )
        return ld.astype(out_dtype), li
    d = masked_dist_tile(
        q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, onepass, keep)
    # under the one-pass rule both branches select the same way from tiles
    # of one type: a nested jit is traced and lowered once for the two
    select = _select_tile if onepass is None else _select_tile_once
    return select(
        d.astype(out_dtype), blk_ids, cfg.k, cfg.topk_method,
        cfg.recall_target, cfg.topk_block,
    )


@jax.named_scope("knn.select")
def _select_tile(d, blk_ids, k, method, recall_target, block):
    return smallest_k(
        d, blk_ids, k, method=method, recall_target=recall_target, block=block)


# The selection kernels' bodies are the costly part of a tile program's
# tracing and lowering, which run at every process start (PERF.md §6, PR 27):
# traced twice, a program that carries the rule cost 1.7 s of `setup_s` in
# the all-kNN cell. Programs without the rule do NOT go through the nested
# jit: a server warms its buckets on ten threads, and all of them tracing
# through one jitted function cost its warm-up 1.5 s (PERF.md §6, PR 29).
_select_tile_once = jax.jit(
    _select_tile, static_argnames=("k", "method", "recall_target", "block"))


@jax.named_scope("knn.select")
def _insert_tile(lists_d, lists_i, bound, d, blk_ids, depth):
    # imported where a program first carries the lists, as ``ops/topk.py``
    # imports it where one first selects from a wide tile (pallas: ~0.8 s)
    from mpi_knn_tpu.ops.lane_bin import lane_bin_insert

    return lane_bin_insert((lists_d, lists_i), d, blk_ids, depth, bound)


# as :data:`_select_tile_once`: one trace of the bins kernel for the two
# branches of the one-pass rule
_insert_tile_once = jax.jit(_insert_tile, static_argnames=("depth",))


# the most tile steps between two refreshes of the row bound
_REFRESH_EVERY = 16


def bound_refreshes(n_tiles: int) -> np.ndarray:
    """(n_tiles,) bool: the tile steps of a carried scan that take the row
    bound anew from the lists before they insert (``ops/lane_bin.py
    lane_bin_bound``). After t tiles of rows in any exchangeable order a
    new value stays under a bound taken at tile t' with probability
    k / (width · t'), so what a stale bound lets through grows with t / t'
    and not with t - t': the steps lie a quarter of their index apart —
    and never more than 16 tiles, because a corpus ordered by cluster is not
    exchangeable: when the first tile of a cluster nearer than any before
    comes, every chunk of it passes until the next refresh (the streaming
    cell, on a seed that brings a query's own cluster late: 4780 rows/s
    without the cap, 5172 with it; a refresh is 15 us of a 1024-row scan's
    55 us steps, 92 a 1221-tile scan: 2 % of it, 1.3 % of the bulk cell's
    rate: PERF.md §6, PR 35)."""
    due = np.zeros(n_tiles, dtype=bool)
    t = 1
    while t < n_tiles:
        due[t] = True
        t += max(1, min(t // 4, _REFRESH_EVERY))
    return due


def knn_tile_step(
    q_x: jax.Array,
    q_ids: jax.Array,
    q_sq: jax.Array | None,
    blk: jax.Array,
    blk_ids: jax.Array,
    blk_sq: jax.Array | None,
    carry_d: jax.Array,
    carry_i: jax.Array,
    cfg: KNNConfig,
    onepass: bool | None = None,
    keep: jax.Array | None = None,
):
    """One fused (query_tile × corpus_tile) step: distances → masks → merged
    top-k, streamed into the carry. The ring backends' per-round body (a
    rotating block is inherently stream-merged)."""
    if cfg.precision_policy == "mixed":
        # two-pass tile reduction to k exact survivors first, then a narrow
        # (2k-wide) merge into the carry — the carry itself stays exact
        ld, li = local_tile_topk(
            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, carry_d.dtype,
            keep=keep,
        )
        all_d = jnp.concatenate([carry_d, ld], axis=-1)
        all_i = jnp.concatenate([carry_i, li], axis=-1)
    else:
        d = masked_dist_tile(
            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg, onepass, keep)
        all_d = jnp.concatenate([carry_d, d.astype(carry_d.dtype)], axis=-1)
        with jax.named_scope("knn.ids"):
            tile_ids = jnp.broadcast_to(blk_ids[None, :], d.shape)
        all_i = jnp.concatenate([carry_i, tile_ids], axis=-1)
    with jax.named_scope("knn.merge"):
        return smallest_k(
            all_d,
            all_i,
            cfg.k,
            method=cfg.topk_method,
            recall_target=cfg.recall_target,
            block=cfg.topk_block,
        )


@functools.partial(jax.jit, static_argnames=("cfg",))
def knn_chunk_update(
    q_tiles: jax.Array,  # (QT, q_tile, d)
    qid_tiles: jax.Array,  # (QT, q_tile)
    chunk_tiles: jax.Array,  # (T, c_tile, d) corpus tiles to merge in
    chunk_ids: jax.Array,  # (T, c_tile)
    carry_d: jax.Array,  # (QT, q_tile, k)
    carry_i: jax.Array,
    cfg: KNNConfig,
    onepass: jax.Array | None = None,
    offset: jax.Array | None = None,
):
    """Merge a chunk of corpus tiles into the per-query top-k carry: scan
    over corpus tiles inside a map over query tiles. The one compiled core
    behind both the serial backend and the resumable driver — the serving
    path's :func:`serve_chunk` IS this body with the chunk norms hoisted
    to index state, so the two can never drift. ``offset``: a byte
    stack's (:func:`serve_chunk`)."""
    return serve_chunk(
        q_tiles, qid_tiles, carry_d, carry_i,
        chunk_tiles, chunk_ids, stack_norms(chunk_tiles, cfg.metric, offset),
        onepass, offset, cfg=cfg,
    )


def stack_norms(tiles: jax.Array, metric: str,
                offset: jax.Array | None = None) -> jax.Array | None:
    """The (T, c_tile) per-row state a metric's tile step wants from a
    (T, c_tile, d) tile stack, made once a corpus: squared row norms for
    L2, inverse row norms for cosine (``ops.distance.cosine_inv_norms``:
    the step then scales its dot and never normalises a corpus tile),
    NOTHING for an inner product (None: an empty node of the stack's
    pytree, no operand of any program). One reduction over the stack, no
    copy of it. Always traced (inside :func:`knn_chunk_update`, or under
    :data:`_stack_norms`): the eager reduction gives other bits than the
    traced one on the CPU. A byte stack's plane holds the norms of the
    rows its tile steps see (``ops/distance.py widen_rows`` by ``offset``:
    what a float32 stack of the same rows keeps), widened inside the
    reduction."""
    if metric == "ip":
        return None
    if tiles.dtype == jnp.uint8:
        return jax.vmap(lambda tile: sq_norms(widen_rows(tile, offset)))(tiles)
    if metric == "l2":
        return jax.vmap(sq_norms)(tiles)
    if metric == "cosine":
        return jax.vmap(cosine_inv_norms)(tiles)
    raise ValueError(f"unknown metric {metric!r}")


_stack_norms = jax.jit(stack_norms, static_argnames=("metric",))


def resident_norms(tiles: jax.Array, metric: str,
                   offset: jax.Array | None = None) -> jax.Array | None:
    """:func:`stack_norms` of a stack that stays (a prepared corpus, a
    served index), under its jit; for an inner product no program runs
    and no plane is built."""
    if metric == "ip":
        return None
    # (two arguments for a float stack, as ever: benchmark/tests plant
    # faults by replacing ``_stack_norms`` with a function of two)
    if offset is None:
        return _stack_norms(tiles, metric)
    return _stack_norms(tiles, metric, offset)


def serve_chunk(
    q_tiles: jax.Array,  # (QT, q_tile, d) one padded query batch
    qid_tiles: jax.Array,  # (QT, q_tile)
    carry_d: jax.Array,  # (QT, q_tile, k) per-batch scratch (donatable)
    carry_i: jax.Array,
    tiles: jax.Array,  # (T, c_tile, d) RESIDENT corpus tiles
    tile_ids: jax.Array,  # (T, c_tile)
    tile_sqs: jax.Array | None,  # (T, c_tile) stack_norms, made at index
    # build; None under "ip"
    onepass: jax.Array | None = None,  # the corpus side of the one-pass rule
    offset: jax.Array | None = None,  # (d,) what a BYTE stack is centred by
    *,
    cfg: KNNConfig,
    filt: tuple | None = None,
):
    """One serving batch against a device-resident corpus index: the
    queries-vs-corpus generalization of :func:`knn_chunk_update` with the
    corpus-side work hoisted out of the batch entirely — tiles, global ids
    AND squared norms arrive precomputed (``serve.CorpusIndex`` builds them
    once), so the per-batch program is only the distance matmuls, masks and
    the top-k merge. The serving engine (``serve.engine``) AOT-compiles
    this per row bucket with ``carry_d``/``carry_i`` donated; argument
    order therefore keeps the batch-owned buffers first and the resident
    index last.

    ``onepass`` (a bool scalar on the device: every centred corpus element
    is a bf16 number) puts both branches of :func:`masked_dist_tile` into
    the program; each query tile adds its own half of the verdict. None:
    the program without the branch; so too where the rule does not apply
    (:func:`onepass_rule`: a small bucket, ``precision_policy="mixed"`` as
    a serving rung).

    A program that counts on the device returns a third output,
    :class:`TileCounts`: the tile steps by the branch they took (the
    one-pass rule's: :func:`dist_steps`) and the query tiles by what became
    of the selection their scans carried (:func:`select_tiles`: every
    program :func:`carried_depth` engages) and, where a row bound rides
    those scans, the chunks *bins* inserted and skipped under it
    (``bins_chunks``), and, where the scans screen (:func:`screen_rule`),
    the query rows by the screen's certificate (``screen_rows``). A
    program that counts none of them returns two, as it always did.

    Cosine: the query side's unit rows are made HERE, once a query tile
    and ahead of its scan (scope ``knn.qunit``), where L2 norms its query
    tile — inside the batch program, so a served batch, a one-shot call
    and a resumable round run one arithmetic and no host pass or extra
    dispatch prepares a batch; ``tile_sqs`` holds the corpus rows' inverse
    norms, so no tile step normalises anything.

    ``filt`` (:func:`serve_chunk_filtered`, a tagged index's batches):
    ``(q_tags (QT, q_tile, W), tag_bits)``, a predicate a query row; its
    words ride the scan beside the stack (:func:`filter_words`) and every
    tile step — or, where :func:`fused_rule` engages with them, the kernel
    that walks the stack — masks by them. None: the program as it always
    was.

    A byte stack (``tiles`` uint8, ``dtype="uint8"``): every tile step
    widens its tile to float32 and takes ``offset`` off
    (``ops/distance.py widen_rows``) ahead of the dot it would run over a
    float32 stack of the same rows — in the one-pass branch inside the
    dot's own fusion or the kernel, so nothing but bytes crosses HBM; its
    one-pass steps count in the sixth column (:func:`dist_steps`) and sit
    under the scope :data:`U8_SCOPE`."""
    if not onepass_rule(cfg, q_tiles.shape[1], filtered=filt is not None):
        onepass = None
    # the batch meets the stack at the width it rests at (``serve/index.py
    # rest_width``: zero columns, exact zeros in every dot and norm), and
    # crosses to the device at its own
    q_tiles = pad_cols(q_tiles, tiles.shape[-1])

    def per_query_tile(args):
        q_x, q_ids, cd, ci, *q_tags = args
        q_sq = None
        if cfg.metric == "l2":
            q_sq = sq_norms(q_x)
        elif cfg.metric == "cosine":
            q_x = unit_rows(q_x)
        elif cfg.metric != "ip":  # an inner product prepares nothing
            raise ValueError(f"unknown metric {cfg.metric!r}")
        one = None if onepass is None else onepass & bf16_exact(q_x)
        words = filter_words(filt[1], *q_tags) if q_tags else None
        return *merge_tiles_into_carry(
            q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs, cd, ci, cfg, one,
            words, offset,
        ), one

    best_d, best_i, rescanned, chunks, screened, took = jax.lax.map(
        per_query_tile,
        (q_tiles, qid_tiles, carry_d, carry_i) + (
            () if filt is None else (filt[0],)))
    varying = bool(jax.typeof(q_tiles).vma | jax.typeof(tiles).vma)
    if took is not None:
        steps = dist_steps(
            took, tiles.shape[0], fused=bool(fused_rule(
                cfg, q_tiles.shape[1], *tiles.shape[1:], varying,
                filtered=filt is not None)),
            u8=tiles.dtype == jnp.uint8)
    elif fused_screen_rule(cfg, q_tiles.shape[1], *tiles.shape[1:],
                           filtered=filt is not None, varying=varying):
        # the screened steps that ran inside the kernel: their own column,
        # a static count (the program holds no other path)
        steps = dist_steps(q_tiles.shape[0], tiles.shape[0], cfg.metric,
                           fused_screen=True)
    else:
        steps = None
    counts = TileCounts(
        steps,
        None if rescanned is None else select_tiles(rescanned),
        None if chunks is None else jnp.sum(chunks, axis=0, dtype=jnp.int32),
        screen_rows=None if screened is None else jnp.sum(
            screened, axis=0, dtype=jnp.int32),
    )
    if all(c is None for c in counts):
        return best_d, best_i
    return best_d, best_i, counts


def serve_chunk_filtered(
    q_tiles, qid_tiles, carry_d, carry_i,
    q_tags: jax.Array,  # (QT, q_tile, W) int32 bitset rows, F = none
    tiles, tile_ids, tile_sqs, onepass,
    tag_bits: jax.Array,  # (F + 1, T, c_tile / 32) uint32, RESIDENT
    *,
    cfg: KNNConfig,
):
    """:func:`serve_chunk` for a tagged index (``serve/tags.py``): every
    query row brings a predicate, the conjunction of at most W frequent
    tags, and a corpus row is a candidate for it only where its bag holds
    them all. The batch-owned operand sits with the batch-owned buffers,
    the bitsets last with the resident index."""
    return serve_chunk(
        q_tiles, qid_tiles, carry_d, carry_i, tiles, tile_ids, tile_sqs,
        onepass, cfg=cfg, filt=(q_tags, tag_bits))


def merge_tiles_into_carry(
    q_x: jax.Array,  # (q_tile, d)
    q_ids: jax.Array,  # (q_tile,)
    q_sq: jax.Array | None,
    tiles: jax.Array,  # (T, c_tile, d)
    tile_ids: jax.Array,  # (T, c_tile)
    tile_sqs: jax.Array | None,  # (T, c_tile); None under "ip"
    carry_d: jax.Array,  # (q_tile, k)
    carry_i: jax.Array,
    cfg: KNNConfig,
    onepass: jax.Array | None = None,
    words: jax.Array | None = None,  # (T, q_tile, c_tile / 32)
    offset: jax.Array | None = None,  # (d,) of a byte stack
):
    """Merge a stack of corpus tiles into one query tile's top-k carry, per
    ``cfg.merge_schedule``. The single implementation behind the serial
    chunk scan and the ring backends' per-round block loop (the schedules
    must match or the ring's per-round cost diverges from serial's).
    Returns ``(dists, ids, rescanned, chunks, screened)``: the merged
    carry and, from an engaged ``twolevel`` program, a bool scalar — some
    row failed a certificate (the lanes' or the screen's) and the flagged
    rows were answered again — and, where the row bound rides the scan
    too, int32 ``[inserted, skipped]``, the chunks of the distance tiles
    by what became of them in *bins*, and, where the scan screens
    (:func:`screen_rule`), int32 ``[certified, flagged]``, the query rows
    by the screen's certificate; else None.

    - "twolevel", where the lane-bin rule engages for the stack's tiles
      (:func:`carried_depth`: the exact policy and method, k <= 128, tiles
      >= 1024 wide — every cell's program): the scan over the tiles
      carries the per-(row, lane) lists of the selection, a step is the
      distance tile and *bins* into them, and after the scan ONE *finish*
      turns them into the stack's k survivors, one certificate says
      whether anything dropped anywhere could have belonged, the rows it
      flags are scanned again exactly (:func:`_rescan_flagged`), and a
      2k-column exact merge joins the survivors to the incoming carry
      (:func:`_merge_carried`). No per-tile top-k, no (T, q, k) stack of
      survivors and no cascade exist in such a program; its third output
      says whether the query tile was re-scanned. Where the tile's shape
      allows (``ops/topk.py lane_bin_bound_rides``: 256 to 2048 rows at
      8192 columns) a row bound rides the scan beside the lists and *bins*
      inserts only the chunks that hold a value at or under it: the same
      answer, bit for bit, and a fourth output that counts the chunks.
      Where :func:`screen_rule` says so (float32 rows at ``highest``, no
      one-pass branch, 1024 rows or more, a row-major stack) the scan's
      dot runs in three passes and keeps k' candidates a row, their rows
      are gathered and finished at the configured precision, and a second
      certificate (:func:`screen_eps`) sends what it cannot vouch for to
      the same re-scan: the six-pass answer, the six passes spent on k'
      rows a query and not on the stack.
    - "twolevel" elsewhere (k > 128, ``mixed``, another ``topk_method``,
      narrow tiles, the ring's interpreted form off the TPU): level 1 —
      independent local top-k per corpus tile (no carry dependence between
      scan steps, so XLA can pipeline the sort of tile t with the matmul
      of tile t+1); level 2 — ONE narrow cascade merge over the incoming
      carry plus every tile's k survivors, (n_tiles+1)·k columns instead of
      a (carry ‖ c_tile)-wide reduction per tile. Measured faster on v5e
      (BASELINE.md r3), now the default.
    - "stream": carry threaded through the tile scan — the reference's
      accumulate-as-you-go shape (``knn-serial.c:86-91``), batched.

    Under ``cfg.precision_policy="mixed"`` the per-tile reduction in BOTH
    schedules is the compress-and-rerank pipeline (ops/rerank.py): the wide
    DEFAULT-precision dot and the 4k overfetch happen inside the tile, the
    HIGHEST rerank finishes it, and what reaches the merges here is already
    exact — the schedules, the cascade, and the ring's per-round streaming
    merge are untouched by the policy.

    ``onepass`` is the one-pass rule's verdict on this merge's operands, a
    bool scalar made on the device (``ops.distance.bf16_exact`` of the
    centred corpus AND of the query tile), the same for every tile of the
    stack; None — the rule does not apply (:func:`onepass_rule`), or the
    corpus is known not to qualify — is the program as it always was.
    Given, every tile step is a ``lax.cond`` over two whole steps —
    distances AND their selection (the reduction to k survivors; in an
    engaged program *bins* into the carried lists, which the conditional
    takes and returns: the v5e compiler updates them in place through it,
    ``tests/test_pallas.py`` reads that in the compiled program) — that
    differ in the distance dot alone (:func:`masked_dist_tile`). Where the
    conditional sits was decided by what the v5e compiler does with it
    (PERF.md §6, PR 29): around the dot alone, a tile small enough for fast
    memory leaves it, because the conditional's output does not live there;
    around the whole scan, the f32 -> bf16 narrowing of the WHOLE stack is
    hoisted out of the loop, 2.3 GiB and 28 ms at every call of the
    all-kNN cell. Around the step, the tile is laid out ahead of the
    conditional (one copy of the tile a step, which :func:`onepass_rule`
    weighs) and the narrowing stays in the dot's own fusion.

    A byte stack (``tiles`` uint8, with its ``offset``): each step's tile
    is widened (``ops/distance.py widen_rows``) where the step is traced,
    ahead of the same distance code; the one-pass branch sits under
    :data:`U8_SCOPE`.
    """
    u8 = tiles.dtype == jnp.uint8
    if u8 and words is not None:
        raise ValueError("a byte stack takes no predicate's words")

    def under_u8(fn):
        """``fn`` under the byte stack's scope, where the stack is one."""
        if not u8:
            return fn
        return lambda *o: jax.named_scope(U8_SCOPE)(fn)(*o)

    def either(step, *operands):
        """``step(*operands, onepass)``: under the rule, a conditional over
        the step traced for each branch."""
        if onepass is None:
            return step(*operands, None)
        return jax.lax.cond(
            onepass,
            under_u8(lambda *o: step(*o, True)),
            lambda *o: step(*o, False),
            *operands,
        )

    # a predicate's words ride the scan as a fourth plane of the stack
    # (:func:`filter_words`): a tile's slice a step, or the kernel's operand
    stack = (tiles, tile_ids, tile_sqs) + (() if words is None else (words,))
    if cfg.merge_schedule == "twolevel":
        varying = bool(jax.typeof(q_x).vma | jax.typeof(tiles).vma)
        depth = carried_depth(cfg, carry_d.shape[0], tiles.shape[1], varying)
        if depth is not None:
            block = None
            if onepass is not None:
                block = fused_rule(
                    cfg, carry_d.shape[0], *tiles.shape[1:], varying,
                    filtered=words is not None)
            facts = dict(branch=onepass is not None,
                         filtered=words is not None, varying=varying)
            screen = screen_rule(
                cfg, carry_d.shape[0], *tiles.shape[1:], **facts)
            if screen is not None:
                block = fused_screen_rule(
                    cfg, carry_d.shape[0], *tiles.shape[1:], **facts)
            return _merge_carried(
                q_x, q_ids, q_sq, stack, carry_d, carry_i, cfg, depth,
                either, onepass if block else None, block,
                nested=onepass is not None, offset=offset, screen=screen)

        def local(_, tile):
            # per-tile reduction honors cfg.precision_policy (exact single
            # pass vs compress-and-rerank); either way k exact-f32
            # survivors per tile feed the level-2 cascade
            return None, either(
                lambda blk, blk_ids, blk_sq, *keep_one: local_tile_topk(
                    q_x, q_ids, q_sq, widen_rows(blk, offset), blk_ids,
                    blk_sq, cfg,
                    carry_d.dtype, keep_one[-1], *keep_one[:-1],
                ),
                *tile,
            )

        _, (ld, li) = jax.lax.scan(local, None, stack)
        n_tiles = ld.shape[0]
        q_rows = carry_d.shape[0]
        with jax.named_scope("knn.merge"):
            ld = jnp.moveaxis(ld, 0, 1).reshape(q_rows, n_tiles * cfg.k)
            li = jnp.moveaxis(li, 0, 1).reshape(q_rows, n_tiles * cfg.k)
            return *cascade_smallest_k(
                jnp.concatenate([carry_d, ld], axis=-1),
                jnp.concatenate([carry_i, li], axis=-1),
                cfg.k,
                # survivors-of-survivors must merge exactly or recall
                # decays multiplicatively; "block" is exact,
                # "approx"/"bf16" are not
                method=(
                    cfg.topk_method
                    if cfg.topk_method in ("exact", "block")
                    else "exact"
                ),
                block=cfg.topk_block,
            ), None, None, None

    n_stack = len(stack)

    def step(carry, tile):
        return (
            either(
                lambda *o: knn_tile_step(
                    q_x, q_ids, q_sq, widen_rows(o[0], offset), *o[1:3],
                    *o[n_stack:-1], cfg, o[-1],
                    *o[3:n_stack],
                ),
                *tile, *carry,
            ),
            None,
        )

    out, _ = jax.lax.scan(step, (carry_d, carry_i), stack)
    return *out, None, None, None


def _varying_like(x: jax.Array, *operands):
    """``x``, a constant, typed as varying over every mesh axis that an
    operand varies over (a checked ``shard_map``: a loop's carry has one
    type from its first step); elsewhere ``x`` as it is."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return jax.lax.pcast(x, tuple(vma), to="varying") if vma else x


def _merge_carried(q_x, q_ids, q_sq, stack, carry_d, carry_i, cfg, depth,
                   either, fused, block, nested, screen=None, offset=None):
    """The engaged ``twolevel`` merge (:func:`merge_tiles_into_carry`): the
    scan over the stack's tiles carries the lane-bin lists — a step is the
    distance tile and *bins* into them, under the one-pass rule's
    conditional, which takes and returns the lists — and after it ONE
    *finish* gives the stack's k survivors and the certificate, and the
    narrow exact merge joins them to the incoming carry. ``nested``: the
    step is traced twice, so *bins* goes through its nested jit.

    Where ``lane_bin_bound_rides`` the scan also carries a ROW BOUND, an
    upper bound on every row's final k-th smallest value of THIS stack,
    taken from the lists themselves (``lane_bin_bound``: the finish kernel
    over their first column group, under a ``lax.cond`` at
    :func:`bound_refreshes`' steps; it only falls), and the count of the
    chunks *bins* inserted under it.

    ``fused`` (the one-pass verdict where :func:`fused_rule` gives a
    ``block`` height, else None): the conditional sits ONCE around the
    scan and its one-pass branch is one kernel that walks the stack
    (``ops/fused_scan.py``, scope ``knn.fused``), the query tile in blocks
    of ``block`` rows: the same lists and the same count, with no slice of
    a tile, no distance tile and no list crossing HBM in a step. The bound
    rides at the block's height, so the rule is asked ahead of
    ``lane_bin_bound_rides``: a 4096-row tile takes the kernel in four
    blocks, and its other branch, the scan of multi-pass steps, stays the
    unbounded one it was and counts every chunk as inserted. (Around the
    whole scan XLA hoisted the one-pass branch's narrowing of the stack
    out of the loop, PERF.md §6, PR 29; a narrowing inside a kernel cannot
    be.)

    ``screen`` (k' where :func:`screen_rule` says so, else None): the same
    scan with everything that says k saying k' — the lists' depth, the
    bound, the finish — over a distance tile whose dot runs in THREE
    passes (:func:`masked_dist_tile`'s ``screen``), and the lists keep a
    candidate's SLOT in the stack, not its id. Then the k' candidates'
    rows are gathered and finished at the configured precision
    (:func:`_finish_screened`: every returned distance is that code's or
    the re-scan's, none the screen's), the screen's certificate
    (:func:`screen_eps`) joins the lanes', and a row flagged by either is
    answered again by the same re-scan, whose distance tile is the
    configured precision's. A fifth output counts the rows by the
    screen's verdict. With a ``block`` height (:func:`fused_screen_rule`;
    ``fused`` is None: such a program carries no one-pass branch) the
    screened scan is ONE call of the kernel in its three-pass form, no
    conditional around it: the same lists within the kernel's own error
    (:func:`screen_eps` with ``fused``), slots made in the kernel, and
    everything after the scan as it is.

    ``offset``: a byte stack's (:func:`merge_tiles_into_carry`); every
    distance tile is made from the widened tile, and the kernel widens its
    own pieces."""
    from mpi_knn_tpu.ops.lane_bin import (
        lane_bin_bound,
        lane_bin_chunks,
        lane_bin_lists,
        lane_bin_no_bound,
        lane_bin_result,
    )

    q_rows, k = carry_d.shape
    n_tiles, c_tile = stack[0].shape[:2]
    insert = _insert_tile_once if nested else _insert_tile

    n_stack = len(stack)  # 3, and a predicate's words where a batch has one
    # what the lists answer for, and what the scan walks: under the screen
    # k' values a row, and one plane more, every row's slot in the stack
    # (an iota: the finish gathers by it, whatever ids a mutable index
    # keeps there)
    wide, walked, ids_at = k, stack, 1
    in_kernel = screen is not None and block is not None
    if screen is not None:
        wide, ids_at = screen, n_stack
        depth = lane_bin_depth(q_rows, c_tile, wide)
        if not in_kernel:
            walked = (*stack, jnp.arange(
                n_tiles * c_tile, dtype=jnp.int32).reshape(n_tiles, c_tile))
    n_walked = len(walked)

    def dist_tile(*tile_one, scoped=True, screened=screen is not None):
        *tile, one = tile_one
        dist = masked_dist_tile if scoped else _masked_dist_tile
        return dist(
            q_x, q_ids, q_sq, widen_rows(tile[0], offset), *tile[1:3], cfg,
            one, *tile[3:n_stack],
            screen=screened,
        ).astype(carry_d.dtype)

    def varying(x):
        return _varying_like(x, q_x, stack[0])

    lists = tuple(map(varying, lane_bin_lists(q_rows, depth, carry_d.dtype)))
    every_chunk = n_tiles * lane_bin_chunks(q_rows, c_tile)

    def unbounded(either):
        def step(lists, tile):
            return either(
                lambda *o: insert(
                    *o[n_walked:-1], None,
                    dist_tile(*o[:n_stack], o[-1]), o[ids_at], depth=depth),
                *tile, *lists,
            ), None

        return jax.lax.scan(step, lists, walked)[0]

    def bounded(either):
        def step(state, tile):
            *lists, bound, inserted = state
            *tile, due = tile
            with jax.named_scope("knn.select"):
                bound = jax.lax.cond(
                    due,
                    lambda: jnp.minimum(bound, lane_bin_bound(lists, wide)),
                    lambda: bound)
            *lists, n = either(
                lambda *o: insert(
                    *o[n_walked:-1],
                    dist_tile(*o[:n_stack], o[-1]), o[ids_at], depth=depth),
                *tile, *lists, bound,
            )
            return (*lists, bound, inserted + n), None

        # the bound starts at +inf and not at the incoming carry's k-th
        # column: that bounds the MERGED answer, while the lists answer
        # for this stack alone, and a stack with fewer than k values
        # under it would leave its rows short of k candidates, flagged
        # one and all
        (*out, _, inserted), _ = jax.lax.scan(
            step,
            (*lists,
             varying(lane_bin_no_bound(q_rows, carry_d.dtype)),
             varying(jnp.int32(0))),
            (*walked, bound_refreshes(n_tiles)),
        )
        return (*out, inserted)

    rides = lane_bin_bound_rides(q_rows, c_tile, carry_d.dtype.itemsize)
    if in_kernel:
        *lists, inserted = _fused_scan(
            q_x, q_ids, q_sq, *stack, cfg=cfg, depth=depth, block=block,
            screen=wide)
    elif fused is not None:
        multipass = lambda step, *o: step(*o, False)  # noqa: E731
        *lists, inserted = jax.lax.cond(
            fused,
            lambda: _fused_scan(
                q_x, q_ids, q_sq, *stack, cfg=cfg, depth=depth, block=block,
                offset=offset),
            lambda: bounded(multipass) if rides else (
                *unbounded(multipass), varying(jnp.int32(every_chunk))))
    elif rides:
        *lists, inserted = bounded(either)
    else:
        lists, inserted = unbounded(either), None
    chunks = None if inserted is None else jnp.stack(
        [inserted, every_chunk - inserted])
    with jax.named_scope("knn.select"):
        vals, ids, flagged = lane_bin_result(lists, q_rows, wide)
    screened = None
    if screen is not None:
        vals, ids, certified = _finish_screened(
            q_x, q_ids, q_sq, stack, ids, vals[:, -1], cfg, in_kernel)
        flagged = flagged | ~certified
        passed = jnp.sum(certified, dtype=jnp.int32)
        screened = jnp.stack([passed, q_rows - passed])
    with jax.named_scope("knn.select"):
        with jax.named_scope("finish"):
            rescanned = jnp.any(flagged)
        # the scope holds the re-scan and nothing else: its device time in
        # a trace is what failed certificates cost
        with jax.named_scope("fallback"):
            vals, ids = _rescan_flagged(
                flagged, vals, ids,
                functools.partial(dist_tile, scoped=False, screened=False),
                stack, either)
    with jax.named_scope("knn.merge"):
        return (*merge_topk(carry_d, carry_i, vals, ids), rescanned, chunks,
                screened)


def _finish_screened(q_x, q_ids, q_sq, stack, slots, s, cfg, fused=False):
    """What follows a screened scan (:func:`_merge_carried`): ``slots`` (q,
    k') are each row's candidates, slot numbers of the stack viewed flat
    (-1: the row had fewer), ``s`` (q,) its k'-th smallest SCREEN value,
    ``fused`` whether the kernel made them (:func:`fused_screen_rule`: the
    certificate then holds the kernel's own error).
    Returns ``((q, k) dists, (q, k) ids, (q,) certified)``.

    Scope ``knn.rerank``: the candidates' rows are read where the stack
    rests (flat, a bitcast of a row-major stack: :func:`screen_rule`) with
    their ids and their entries of the norm plane, and
    ``ops/rerank.py rerank_exact_topk`` computes every distance anew at
    HIGHEST — the tile step's own form on the same numbers — applies
    ``mask_tile``'s masks to those values, the zero test among them, and
    takes the k smallest. Scope ``knn.select/screen``: a row is certified
    iff ``tau + eps <= s`` (``tau`` its k-th finished value,
    :func:`screen_eps`) or ``s`` is +inf, nothing finite left out."""
    tiles, tile_ids, tile_sqs = stack[:3]
    dim = tiles.shape[-1]
    with jax.named_scope("knn.rerank"):
        at = jnp.maximum(slots, 0)
        rows = jnp.take(tiles.reshape(-1, dim), at, axis=0)
        cand_ids = jnp.where(
            slots < 0, INVALID_ID, jnp.take(tile_ids.reshape(-1), at, axis=0))
        cand_sq = None if tile_sqs is None else jnp.take(
            tile_sqs.reshape(-1), at, axis=0)
    vals, ids = rerank_exact_topk(
        q_x, q_ids, q_sq, rows, cand_ids, cand_sq, cfg.k, metric=cfg.metric,
        exclude_self=cfg.exclude_self, exclude_zero=cfg.exclude_zero,
        zero_eps=cfg.zero_eps)
    with jax.named_scope("knn.select"), jax.named_scope("screen"):
        eps = screen_eps(cfg.metric, dim, q_x, q_sq,
                         largest_norm_sq(cfg.metric, tiles, tile_sqs), fused)
        certified = (vals[:, -1] + eps <= s) | (s == jnp.inf)
    return vals, ids, certified


def _fused_scan(q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs, words=None, *,
                cfg, depth, block, offset=None, screen=None):
    """``ops/fused_scan.py fused_scan`` for ``cfg``: the engaged scan's
    one-pass branch over the whole stack as one kernel, the query tile in
    blocks of ``block`` rows, ``(lists_d, lists_i, chunks inserted)``,
    under the scope ``knn.fused``. ``offset``: a uint8 stack's, whose
    scope the kernel then sits in (``knn.scan_u8/knn.fused``). ``screen``
    (k'): the screened scan in the kernel's three-pass form — k' where
    the one-pass form says k, slots for ids, and no zero test by value
    (that waits for the finish's exact values, :func:`masked_dist_tile`).
    ``words``: a predicate's (:func:`filter_words`), the stack's fourth
    plane, which the kernel masks by as the scan's steps do."""
    from mpi_knn_tpu.ops.fused_scan import fused_scan

    if words is not None:
        # the kernel's operand is row-major int32; what that costs (the
        # compiler rests the gathered words a query row a slab and copies
        # them once a query tile) is the predicate's, and is named so
        with jax.named_scope(FILTER_MASK_SCOPE):
            words = jax.lax.bitcast_convert_type(words, jnp.int32)
    with contextlib.ExitStack() as scopes:
        if offset is not None:
            scopes.enter_context(jax.named_scope(U8_SCOPE))
        scopes.enter_context(jax.named_scope(FUSED_SCOPE))
        return fused_scan(
            q_x, q_ids, q_sq, tiles, tile_ids, tile_sqs,
            bound_refreshes(tiles.shape[0]), k=screen or cfg.k, depth=depth,
            exclude_self=cfg.exclude_self,
            exclude_zero=cfg.exclude_zero and screen is None,
            zero_eps=cfg.zero_eps, block=block, offset=offset,
            screen=screen is not None, words=words)


# rows a pass of the re-scan answers: one sublane tile of a float32 vreg
_RESCAN_ROWS = 8


def _rescan_flagged(flagged, vals, ids, dist_tile, stack, either):
    """``(vals, ids)`` with every ``flagged`` row answered again, exactly:
    the certificate's way out of a carried scan. While rows are flagged,
    the stack is walked once more for a handful of them: the step's own
    distance tile (``dist_tile``, under ``either``: the values the lists
    were filled from), the handful's rows of it, and the full-width
    ``lax.top_k`` of (survivors ‖ rows), as the ``stream`` schedule merges.
    No kernel in it, so it adds a few XLA operations to a program's set-up;
    it runs for a few query tiles in a thousand (``ops/topk.py
    lane_bin_depth``) and then costs the stack's distance tiles once more a
    pass, whatever flagged the rows (a collision in one lane; fewer than k
    finite candidates, where the full-width selection has always decided
    the answer) and however many there are.

    Its form is what the v5e compiler left room for (PERF.md §6, PR 33;
    ``tests/test_pallas.py -k one_pass_rule`` holds it): a second loop over the
    stack beside the scan, or passes as a loop around a scan, and the
    compiler lays the WHOLE stack out anew ahead of both (at d = 784 a
    copy of 4.6 GiB, which the all-kNN cell has no room for); one flat
    loop inside a conditional keeps the scan's own lay-out of a tile a
    step. So: one loop over (pass, tile), which commits a pass's answers
    and picks the next rows at the pass's last tile. The whole query
    tile's distances are computed though a few rows are wanted: the dot
    the scan runs."""
    q_rows, k = vals.shape
    g = min(_RESCAN_ROWS, q_rows)
    n_tiles = stack[0].shape[0]

    def pick(todo):
        # flagged rows first, the lowest first; where fewer than g are
        # left, rows that passed are answered again with them: the same
        # values
        return jax.lax.top_k(todo.astype(jnp.int32), g)[1]

    def fresh():
        return tuple(_varying_like(x, vals, stack[0])
                     for x in init_topk(g, k, vals.dtype))

    def step(state):
        i, todo, rows, best, vals, ids = state
        t = i % n_tiles
        tile = tuple(  # None: the norms' slot of an inner-product stack
            x if x is None else jax.lax.dynamic_index_in_dim(
                x, t, keepdims=False) for x in stack)
        d = either(dist_tile, *tile)[rows]
        best = merge_topk(
            *best, d, jnp.broadcast_to(tile[1][None, :], d.shape))
        last = t == n_tiles - 1
        done = jnp.where(last, rows, q_rows)  # out of range: nothing written
        todo = todo.at[done].set(False, mode="drop")
        vals = vals.at[done].set(best[0], mode="drop")
        ids = ids.at[done].set(best[1], mode="drop")
        rows = jnp.where(last, pick(todo), rows)
        best = tuple(jnp.where(last, a, b) for a, b in zip(fresh(), best))
        return i + 1, todo, rows, best, vals, ids

    return jax.lax.cond(
        jnp.any(flagged),
        lambda: jax.lax.while_loop(
            lambda state: jnp.any(state[1]) | (state[0] % n_tiles != 0),
            step,
            (jnp.int32(0), flagged, pick(flagged), fresh(), vals, ids),
        )[4:],
        lambda: (vals, ids),
    )


def cap_corpus_tile(q_tile: int, c_tile: int, max_tile_elems: int) -> int:
    """Shrink c_tile until q_tile × c_tile <= max_tile_elems — the hard
    bound on the per-step distance block a backend may materialize. The cap
    is rounded down to a 128 multiple while that keeps it >= 128 (MXU lane
    alignment); rounding down only ever shrinks, so the bound stays hard.
    Shared by the serial and ring backends so the memory plan is one policy."""
    cap = max(1, max_tile_elems // max(q_tile, 1))
    if cap >= 128:
        cap = cap // 128 * 128
    return min(c_tile, cap)


def effective_tiles(cfg: KNNConfig, m: int, nq: int) -> tuple[int, int]:
    """Clamp configured tiles to the (aligned) problem size so small inputs
    don't pay full-tile padding compute, and to ``cfg.max_tile_elems`` so a
    "whole corpus per tile" request can't materialize an HBM-busting
    (q_tile × c_tile) distance block at SIFT1M scale."""
    q_tile = min(cfg.query_tile, pad_to_multiple(nq, 8))
    c_tile = min(cfg.corpus_tile, pad_to_multiple(m, 128))
    return q_tile, cap_corpus_tile(q_tile, c_tile, cfg.max_tile_elems)


@jax.named_scope("knn.retile")
def tile_corpus(corpus, cfg: KNNConfig, c_tile: int):
    """``(tiles, tile ids)``: the corpus padded to whole tiles and reshaped
    into the device tile stack, with its global id rows. A host array is
    padded on the host and transferred once; a device array is padded with
    on-device ops (no device -> host round trip)."""
    m, dim = corpus.shape
    c_pad = pad_to_multiple(m, c_tile)
    tiles = pad_rows_any(corpus, c_pad, dtype=jnp.dtype(cfg.dtype)).reshape(
        -1, c_tile, dim)
    return tiles, jnp.asarray(make_global_ids(m, c_pad).reshape(-1, c_tile))


@jax.named_scope("knn.retile")
def tile_queries(queries, query_ids, cfg: KNNConfig, q_tile: int):
    """``(query tiles, query id tiles, padded rows)``: :func:`tile_corpus`
    for the query side (padding rows carry id -1)."""
    nq, dim = queries.shape
    q_pad = pad_to_multiple(nq, q_tile)
    q_tiles = pad_rows_any(
        queries, q_pad, dtype=jnp.dtype(cfg.compute_dtype)).reshape(
            -1, q_tile, dim)
    qid_tiles = pad_rows_any(query_ids, q_pad, fill=-1, dtype=jnp.int32).reshape(
        -1, q_tile
    )
    return q_tiles, qid_tiles, q_pad


def prepare_tiles(corpus, queries, query_ids, cfg: KNNConfig, q_tile, c_tile):
    """Pad + reshape corpus/query arrays into device tile stacks:
    :func:`tile_corpus` and :func:`tile_queries` for callers that tile both
    sides at once (the resumable driver, the lowering)."""
    corpus_tiles, corpus_tile_ids = tile_corpus(corpus, cfg, c_tile)
    q_tiles, qid_tiles, q_pad = tile_queries(queries, query_ids, cfg, q_tile)
    return q_tiles, qid_tiles, corpus_tiles, corpus_tile_ids, q_pad


@dataclasses.dataclass(frozen=True, eq=False)
class PreparedCorpus:
    """What a call derives from its corpus and nothing else: the corpus
    side of ``api.all_knn``, made once (``api.prepare_corpus``) and
    searched by any number of calls. The serial form is
    :class:`SerialCorpus`, the ring's ``backends.ring.RingCorpus``; the
    arrays are what a call has always made, by the same functions in the
    same order, so a search over a prepared corpus answers bit for bit
    what a call that prepares its own answers."""

    # every fact that shaped the arrays, by name (:func:`serial_form`,
    # ``backends.ring.ring_form``): a call whose own form differs cannot
    # use them
    form: dict
    m: int
    dim: int
    c_tile: int
    # the centring offset (``ops.distance.center_corpus``); None: not centred
    mu: object
    # the corpus side of the one-pass rule, READ ON THE HOST at the
    # preparation (``onepass_fact``): a TRUE device scalar, or None
    onepass: jax.Array | None

    @property
    def shape(self) -> tuple[int, int]:
        """The corpus's own (rows, dim)."""
        return self.m, self.dim

    def search(self, queries, query_ids, cfg: KNNConfig):
        """``((q, k) dists, (q, k) ids, TileCounts)`` of ``queries``
        (uncentred, as the caller has them) against this corpus. The query
        side and the tile program only: no pass over the corpus, no host
        read of a device value."""
        raise NotImplementedError


def serial_form(cfg: KNNConfig, m: int, dim: int, nq: int) -> dict:
    """The form of a :class:`SerialCorpus` for ``nq``-row calls: every fact
    that shapes its arrays. The corpus tile follows the query rows too
    (:func:`effective_tiles` caps the tile's elements)."""
    return dict(
        backend="serial", m=m, dim=dim, dtype=cfg.dtype, metric=cfg.metric,
        center=cfg.center, onepass_applies=onepass_applies(cfg),
        c_tile=effective_tiles(cfg, m, nq)[1],
    )


@functools.partial(jax.jit, static_argnames=("cfg", "q_tile"))
def _search_stack(queries, query_ids, tiles, tile_ids, tile_sqs, onepass,
                  offset=None, *, cfg: KNNConfig, q_tile: int):
    """The one per-call program of a search over a prepared stack:
    :func:`serve_chunk` under a jit with ``cfg`` static, as
    :func:`knn_chunk_update` is, with the query side's tiling, the carry's
    start and the answers' untiling inside it. They were eager dispatches
    under the corpus passes; with those gone the device would wait for
    each of them (a call's host gap read 4.9 ms with them outside, PERF.md
    §6, PR 31)."""
    nq = queries.shape[0]
    q_tiles, qid_tiles, q_pad = tile_queries(queries, query_ids, cfg, q_tile)
    acc = jnp.float64 if q_tiles.dtype == jnp.float64 else jnp.float32
    carry_d, carry_i = init_topk_tiles(q_pad // q_tile, q_tile, cfg.k,
                                       dtype=acc)
    best_d, best_i, *counts = serve_chunk(
        q_tiles, qid_tiles, carry_d, carry_i, tiles, tile_ids, tile_sqs,
        onepass, offset, cfg=cfg,
    )
    return (best_d.reshape(q_pad, cfg.k)[:nq],
            best_i.reshape(q_pad, cfg.k)[:nq], *counts)


@dataclasses.dataclass(frozen=True, eq=False)
class SerialCorpus(PreparedCorpus):
    """The serial form: the centred tile stack, its id tiles and its norms
    (:func:`stack_norms`: squared for L2, inverse for cosine), what
    ``serve.CorpusIndex`` keeps for a served corpus. No reference to a
    centred (m, d) copy: the stack is the only one."""

    tiles: jax.Array  # (T, c_tile, d)
    tile_ids: jax.Array  # (T, c_tile)
    tile_sqs: jax.Array | None  # (T, c_tile); None under "ip"
    # (d,) what a BYTE stack's tile steps widen by (``dtype="uint8"``:
    # ``tiles`` is uint8, ``mu`` holds the same for the query side)
    offset: jax.Array | None = None

    def search(self, queries, query_ids, cfg: KNNConfig):
        nq = queries.shape[0]
        q_tile = effective_tiles(cfg, self.m, nq)[0]
        if self.tiles.dtype == jnp.uint8:
            # (an all-pairs call brings the corpus's own bytes)
            queries = queries.astype(np.float32)
        if self.mu is not None:
            queries = queries - self.mu  # center_for_l2's own subtraction
        best_d, best_i, *counts = _search_stack(
            queries, query_ids, self.tiles, self.tile_ids, self.tile_sqs,
            self.onepass if onepass_rule(cfg, q_tile) else None, self.offset,
            cfg=cfg, q_tile=q_tile,
        )
        return best_d, best_i, tile_counts(
            counts, pad_to_multiple(nq, q_tile) // q_tile,
            self.tiles.shape[0], cfg.metric)


def prepare_serial(corpus, cfg: KNNConfig, form: dict) -> SerialCorpus:
    """Every pass a call makes over its corpus, once: centre (the offset
    and the one-pass fact with it), pad and tile, norm. ``form`` is
    :func:`serial_form`'s for the calls to come. The first call's memory
    peak is here — the caller's array, the centred copy and the stack —
    and the centred copy goes before the read of the fact waits."""
    m, dim = corpus.shape
    c_tile = form["c_tile"]
    mu = fact = None
    if cfg.dtype == "uint8":
        # a byte stack: the rows' own bytes rest, checked and never
        # rounded; the offset is kept aside for the tile steps
        corpus, mu = byte_rows(corpus, cfg.center)
        tiles, tile_ids = tile_corpus(corpus, cfg, c_tile)
        del corpus
        offset = None if mu is None else jnp.asarray(mu)
        return SerialCorpus(
            form, m, dim, c_tile, mu,
            onepass_fact(cfg, None if mu is None else True),
            tiles, tile_ids, resident_norms(tiles, cfg.metric, offset),
            offset)
    if cfg.center and cfg.metric == "l2":
        corpus, mu, fact = center_corpus(corpus)
    tiles, tile_ids = tile_corpus(corpus, cfg, c_tile)
    del corpus
    tile_sqs = resident_norms(tiles, cfg.metric)
    # read last: the stack's copy and its norms are queued behind the
    # centring pass that the read waits for
    return SerialCorpus(
        form, m, dim, c_tile, mu, onepass_fact(cfg, fact),
        tiles, tile_ids, tile_sqs,
    )


def all_knn_serial(corpus, queries, query_ids, cfg: KNNConfig):
    """One whole call on arrays as the caller has them: prepare the corpus,
    search it, drop it. Returns ((q, k) dists, (q, k) ids,
    :class:`TileCounts`), the first two device arrays."""
    form = serial_form(cfg, *corpus.shape, queries.shape[0])
    return prepare_serial(corpus, cfg, form).search(queries, query_ids, cfg)
