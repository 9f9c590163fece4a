"""TPU-native Lloyd's k-means — the partitioner behind the clustered (IVF)
index (``mpi_knn_tpu.ivf``).

The whole trainer is ONE jitted program: init (k-means++ D²-sampling or a
seeded random row draw), a fixed-``iters`` ``lax.scan`` of Lloyd rounds,
and a final assignment pass — so training lowers to a single executable
(no per-iteration dispatch, no host round trips for convergence checks;
a fixed iteration budget is the shape-static analogue of "until
converged", and the bench row measures what the budget buys).

Per round:

- **assignment** reuses ``ops.distance.pairwise_sq_l2`` in row blocks (a
  ``lax.map`` over (block × k) distance tiles, same memory discipline as
  the serial backend's query tiling — the full (m × k) distance matrix is
  never materialized when m is large);
- **update** is a segment-sum: per-cluster coordinate sums and counts via
  ``jax.ops.segment_sum`` on the assignment vector, then a masked divide;
- **empty-cluster re-seeding** is deterministic: the j-th empty cluster
  is re-seeded to the j-th farthest point from its current centroid
  (``lax.top_k`` over the assignment distances). A cluster can only stay
  empty if the data has fewer distinct rows than k — real corpora
  re-populate on the next assignment, and the property is tested
  (tests/test_ivf.py).

Everything is keyed by one PRNG seed (``KNNConfig.ivf_seed``) threaded
through init; same (data, k, seed, init, iters) → bit-identical
centroids.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.ops.distance import pairwise_sq_l2, sq_norms

# Row-block width of the assignment pass: bounds the per-step distance
# tile at (block × k) like the serial backend's query tiling bounds its
# (q_tile × c_tile) tile. 2048 × k ≤ 2048 · m elements — far inside every
# configured tile budget at realistic partition counts.
ASSIGN_BLOCK = 2048
# how far to either side of a split cluster's mean its two centres start,
# as a share of the way to its farthest member (_split_largest)
SPLIT_STEP = 1.0 / 16
# the share of a stated store height (KNNConfig.bucket_cap) over which the
# training rounds split a cluster: the sample's largest then reads up to
# 1.03 x the threshold over all rows (the rounds after the last split, the
# sample's noise), and the height leaves 1.2 x
SPLIT_AT = 5.0 / 6


@dataclasses.dataclass
class KMeansResult:
    """Trained partitioner state: (k, d) centroids, per-point assignments,
    per-cluster counts, and the mean squared assignment distance
    (inertia/m — the number a training-quality trajectory tracks)."""

    centroids: jax.Array  # (k, d) f32
    assignments: jax.Array  # (m,) int32
    counts: jax.Array  # (k,) int32
    inertia: jax.Array  # () f32, mean of per-point min squared distances


def _assign_blocks(data, data_sq, centroids, block: int):
    """(m,) argmin cluster + (m,) min squared distance, computed in row
    blocks so only a (block × k) distance tile is live at once."""
    m, d = data.shape
    cent_sq = sq_norms(centroids)
    nb = -(-m // block)
    pad = nb * block - m
    if pad:
        data = jnp.pad(data, ((0, pad), (0, 0)))
        data_sq = jnp.pad(data_sq, (0, pad))
    data_b = data.reshape(nb, block, d)
    sq_b = data_sq.reshape(nb, block)

    def one(args):
        rows, rows_sq = args
        dist = pairwise_sq_l2(
            rows, centroids, x_sq=rows_sq, y_sq=cent_sq,
            precision=jax.lax.Precision.HIGHEST,
        )
        return jnp.argmin(dist, axis=-1).astype(jnp.int32), jnp.min(
            dist, axis=-1
        )

    assign, min_d2 = jax.lax.map(one, (data_b, sq_b))
    assign = assign.reshape(nb * block)[:m]
    min_d2 = min_d2.reshape(nb * block)[:m]
    return assign, min_d2


def sample_rows(m: int, n: int | None, seed: int) -> np.ndarray | None:
    """The training sample of a clustered build (``KNNConfig.
    kmeans_sample``): ``n`` distinct row numbers of ``m``, ascending, from
    the seed alone (numpy's generator: the same rows on every platform).
    None where the sample is every row (``n`` None or >= ``m``)."""
    if n is None or n >= m:
        return None
    rng = np.random.default_rng([int(seed), 0x1F])
    return np.sort(rng.choice(m, size=int(n), replace=False)).astype(np.int32)


def _over_blocks(rows, block: int, one):
    """``one(block of rows)`` over the whole ``block``-row blocks of
    ``rows``, each sliced where it lies (a block's own temporaries, never
    a padded or re-laid copy of the array), then over the rows left:
    ``(stacked results or None, the tail's result or None)``."""
    m = rows.shape[0]
    block = min(block, m)
    full = m // block
    head = tail = None
    if full:
        head = jax.lax.map(
            lambda b: one(jax.lax.dynamic_slice_in_dim(
                rows, b * block, block)),
            jnp.arange(full, dtype=jnp.int32))
    if m % block:
        tail = one(rows[full * block:])
    return head, tail


@functools.partial(jax.jit, static_argnames=("block", "whole"))
def column_sums(rows, block: int = ASSIGN_BLOCK, whole: bool = False):
    """(blocks, d) float32 sums of ``block`` rows each, the last block the
    rows left over: the chunked half of a mean that crosses the host as a
    few KB — the caller adds the blocks up in float64. Whole numbers up
    to 255 add up exactly in a block of 65536 rows or fewer. ``whole``:
    also (blocks,) bool, whether a block holds whole numbers alone (NaN
    is none) — the test that decides a whole-number mean, beside the sums
    so that it too needs no corpus-sized temporary."""

    def one(blk):
        blk = blk.astype(jnp.float32)
        sums = jnp.sum(blk, axis=0)
        return (sums, jnp.all(blk == jnp.rint(blk))) if whole else sums

    head, tail = _over_blocks(rows, block, one)
    parts = ([head] if head is not None else []) + (
        [jax.tree.map(lambda x: x[None], tail)] if tail is not None else [])
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


@functools.partial(jax.jit, static_argnames=("block",))
def assign_rows(rows, mu, centroids, block: int = ASSIGN_BLOCK):
    """((m,) int32 nearest centroid of every row of ``rows - mu``, (k,)
    int32 rows a centroid), block by block: :func:`_assign_blocks`'
    distance tile and argmin with the centring inside the block, so that
    no centred copy of ``rows`` stands beside it."""
    k = centroids.shape[0]
    cent_sq = sq_norms(centroids)

    def one(blk):
        blk = blk.astype(jnp.float32) - mu
        dist = pairwise_sq_l2(
            blk, centroids, x_sq=sq_norms(blk), y_sq=cent_sq,
            precision=jax.lax.Precision.HIGHEST,
        )
        return jnp.argmin(dist, axis=-1).astype(jnp.int32)

    head, tail = _over_blocks(rows, block, one)
    parts = ([head.reshape(-1)] if head is not None else []) + (
        [tail] if tail is not None else [])
    assign = jnp.concatenate(parts)
    counts = jax.ops.segment_sum(
        jnp.ones_like(assign), assign, num_segments=k)
    return assign, counts


def _init_random(key, data, k: int):
    """k distinct data rows by a seeded permutation draw."""
    m = data.shape[0]
    perm = jax.random.permutation(key, m)[:k]
    return data[perm]


def _init_kmeanspp(key, data, data_sq, k: int):
    """k-means++ D² sampling: first centroid uniform, each next sampled
    with probability proportional to the squared distance to the nearest
    chosen centroid. O(k·m·d) — one pairwise row per step, under a
    ``fori_loop`` with a (k, d) centroid buffer (shape-static)."""
    m, d = data.shape
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, m)
    cents = jnp.zeros((k, d), data.dtype).at[0].set(data[first])
    min_d2 = pairwise_sq_l2(
        data, data[first][None, :], x_sq=data_sq,
        precision=jax.lax.Precision.HIGHEST,
    )[:, 0]

    def step(i, carry):
        cents, min_d2, key = carry
        key, kc = jax.random.split(key)
        # D² sampling; a floor keeps the categorical defined when every
        # remaining point coincides with a chosen centroid (all-zero mass)
        logits = jnp.log(jnp.maximum(min_d2, 1e-30))
        idx = jax.random.categorical(kc, logits)
        cents = cents.at[i].set(data[idx])
        d2 = pairwise_sq_l2(
            data, data[idx][None, :], x_sq=data_sq,
            precision=jax.lax.Precision.HIGHEST,
        )[:, 0]
        return cents, jnp.minimum(min_d2, d2), key

    cents, _, _ = jax.lax.fori_loop(1, k, step, (cents, min_d2, key))
    return cents


def _split_largest(data, assign, min_d2, counts, new, over: float,
                   most: int):
    """Size balancing of one Lloyd round: each of the ``most`` largest
    clusters that holds more than ``over`` times the mean is split — its
    centre and that of one of the ``most`` smallest clusters (the j-th
    largest pairs with the j-th smallest, which is given up) are put a
    little to either side of its mean, along the line to its farthest
    member, so the next round halves it and later rounds move the two to
    their halves' means. (A centre planted ON a member would keep that
    member alone: in many dimensions every other row is nearer the mean.)
    Lloyd's objective does not see sizes — in a mixture of isotropic
    classes a class seeded with few centres keeps them — while a store
    padded to the largest cluster pays for the largest."""
    m = data.shape[0]
    k = counts.shape[0]
    big_n, big = jax.lax.top_k(counts, most)
    _, small = jax.lax.top_k(-counts, most)
    far_d2 = jax.ops.segment_max(min_d2, assign, num_segments=k)
    rows = jnp.arange(m, dtype=jnp.int32)
    far_row = jax.ops.segment_min(
        jnp.where(min_d2 >= far_d2[assign], rows, m), assign,
        num_segments=k)
    split = ((big_n.astype(jnp.float32) > over * (m / k))
             & (counts[small] < big_n // 2))[:, None]
    mean = new[big]
    step = SPLIT_STEP * (data[jnp.minimum(far_row[big], m - 1)] - mean)
    new = new.at[big].set(jnp.where(split, mean - step, mean))
    return new.at[small].set(jnp.where(split, mean + step, new[small]))


@functools.partial(
    jax.jit, static_argnames=("k", "iters", "init", "block", "balance")
)
def _kmeans_jit(data, seed, k: int, iters: int, init: str, block: int,
                balance: float | None = None):
    data = data.astype(jnp.float32)
    data_sq = sq_norms(data)
    key = jax.random.PRNGKey(seed)
    if init == "kmeans++":
        centroids = _init_kmeanspp(key, data, data_sq, k)
    else:
        centroids = _init_random(key, data, k)

    def lloyd(centroids, left):
        assign, min_d2 = _assign_blocks(data, data_sq, centroids, block)
        counts = jax.ops.segment_sum(
            jnp.ones_like(assign, dtype=jnp.int32), assign, num_segments=k
        )
        sums = jax.ops.segment_sum(data, assign, num_segments=k)
        new = sums / jnp.maximum(counts, 1)[:, None].astype(data.dtype)
        # deterministic empty-cluster re-seed: the j-th empty cluster gets
        # the j-th farthest point from its current centroid — the standard
        # split-the-worst-fit move, with no data-dependent shapes
        empty = counts == 0
        _, far_idx = jax.lax.top_k(min_d2, k)
        erank = jnp.clip(jnp.cumsum(empty) - 1, 0, k - 1)
        new = jnp.where(empty[:, None], data[far_idx[erank]], new)
        if balance is not None:
            # not in the last two rounds: a centre just planted is a
            # member's own row until a round has moved it to a mean
            new = jnp.where(
                left > 2,
                _split_largest(data, assign, min_d2, counts, new, balance,
                               max(1, k // 64)),
                new)
        return new, None

    centroids, _ = jax.lax.scan(
        lloyd, centroids,
        None if balance is None else jnp.arange(iters, 0, -1),
        length=iters)
    assign, min_d2 = _assign_blocks(data, data_sq, centroids, block)
    counts = jax.ops.segment_sum(
        jnp.ones_like(assign, dtype=jnp.int32), assign, num_segments=k
    )
    return centroids, assign, counts, jnp.mean(min_d2)


def kmeans(
    data,
    k: int,
    *,
    iters: int = 25,
    seed: int = 0,
    init: str = "kmeans++",
    block: int = ASSIGN_BLOCK,
    balance: float | None = None,
) -> KMeansResult:
    """Train a k-partition Lloyd's k-means on (m, d) data (host numpy or
    device array), single compiled executable, bit-deterministic per
    ``seed``. ``balance``: split, in every round but the last two, a
    cluster over that multiple of the mean rows (:func:`_split_largest`;
    None = plain Lloyd rounds). Returns :class:`KMeansResult`."""
    if init not in ("kmeans++", "random"):
        raise ValueError(f"unknown kmeans init {init!r}")
    m = int(np.shape(data)[0])
    if not 1 <= k <= m:
        raise ValueError(f"k must be in [1, m={m}], got {k}")
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    if not isinstance(data, jax.Array):
        data = jnp.asarray(np.asarray(data, dtype=np.float32))
    centroids, assign, counts, inertia = _kmeans_jit(
        data, jnp.int32(seed), k, iters, init, min(block, m),
        balance=balance,
    )
    return KMeansResult(
        centroids=centroids, assignments=assign, counts=counts,
        inertia=inertia,
    )
