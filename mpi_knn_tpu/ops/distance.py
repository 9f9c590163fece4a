"""Pairwise distance kernels — the framework's replacement for the reference's
hot loop (SURVEY.md C4).

The reference computes ``S = Σ_j pow(Da−Db, 2)`` in a scalar triple loop
(``/root/reference/knn-serial.c:72-93``) and compares ``sqrt(S)``. On TPU the
FLOPs belong on the MXU, so squared L2 is computed in matmul form::

    ‖x − y‖² = ‖x‖² + ‖y‖² − 2·x·yᵀ

and comparisons stay in *squared* space — sqrt is monotone, so the top-k order
is identical up to floating-point rounding (SURVEY.md §5 Q10). A float64 mode
is kept for adjudicating near-tie mismatches against the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.named_scope("knn.center")
def center_for_l2(corpus, queries, all_pairs: bool):
    """Mean-center corpus (and queries consistently) before L2 distances.

    Translation leaves L2 distances unchanged, but cancellation error in the
    ‖x‖²+‖y‖²−2xy matmul form scales with the *centered* norms — centering
    keeps fp noise (and the relative zero-distance threshold, ops.topk) tight
    even when the data sits far from the origin. One shared implementation
    for api.all_knn and both resumable drivers: device-resident inputs are
    centered on device (no host bounce; f64 stays f64 when x64 is on), host
    inputs keep the f64 mean for the debug mode.

    The two paths accumulate the mean at different precisions, so centered
    values for the SAME data differ by fp noise across residencies —
    bit-identical checkpoint resume holds per-residency only, and
    ring_resumable folds the residency into the run fingerprint so a
    cross-residency resume restarts rather than merging mixed carries.
    """
    if isinstance(corpus, jax.Array):
        acc = jnp.float64 if corpus.dtype == jnp.float64 else jnp.float32
        mu = jnp.mean(corpus, axis=0, dtype=acc)
    else:
        mu = np.asarray(corpus, dtype=np.float64).mean(axis=0)
    corpus = corpus - mu
    queries = corpus if all_pairs else queries - mu
    return corpus, queries


def _acc_dtype(x: jax.Array) -> jnp.dtype:
    """Accumulation dtype: f64 inputs accumulate in f64 (debug mode), anything
    else in f32 (bf16 inputs still get full-precision MXU accumulation)."""
    return jnp.float64 if x.dtype == jnp.float64 else jnp.float32


def _dot_precision(x: jax.Array, precision: str | None):
    """Matmul precision for the −2·X·Yᵀ term.

    TPU's MXU default truncates f32 operands to bf16, which was measured to
    cost ~0.3% recall@10 and to move self-distances from ~0 to O(1) on
    MNIST-scale data (see .claude/skills/verify/SKILL.md). Correctness is the
    anchor (recall parity vs the serial reference), so f32 inputs default to
    HIGHEST (multi-pass f32-accurate MXU); bf16 inputs keep DEFAULT — the
    caller already chose throughput over precision.
    """
    if precision is not None:
        return precision
    if x.dtype == jnp.bfloat16:
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


@jax.named_scope("knn.norms")
def sq_norms(x: jax.Array) -> jax.Array:
    """Row squared norms, accumulated at full precision. (r, d) -> (r,)."""
    acc = _acc_dtype(x)
    return jnp.sum(x.astype(acc) * x.astype(acc), axis=-1)


def pairwise_sq_l2(
    x: jax.Array,
    y: jax.Array,
    x_sq: jax.Array | None = None,
    y_sq: jax.Array | None = None,
    precision: str | None = None,
) -> jax.Array:
    """Squared L2 distances between all rows of x (q, d) and y (c, d) -> (q, c).

    The −2·X·Yᵀ term is a single MXU matmul (``preferred_element_type`` forces
    f32/f64 accumulation even for bf16 inputs). Precomputed squared norms may
    be passed in so tiled callers hoist them out of the tile loop.
    """
    acc = _acc_dtype(x)
    if x_sq is None:
        x_sq = sq_norms(x)
    if y_sq is None:
        y_sq = sq_norms(y)
    xy = jax.lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc,
        precision=_dot_precision(x, precision),
    )
    d = x_sq[:, None] - 2.0 * xy + y_sq[None, :]
    # fp cancellation can produce tiny negatives for near-identical rows
    return jnp.maximum(d, 0.0)


# Norm-squared clamp used by _l2_normalize. Any row with sq_norm <= this is
# NOT normalized to unit length (the clamp wins), so callers relying on the
# unit-row identity (pallas cosine's d² = 2·d_cos) must treat such rows as
# degenerate — guard with `sq_norms(x) <= _NORM_EPS`, not `== 0`.
_NORM_EPS = 1e-30


def _l2_normalize(x: jax.Array, eps: float = _NORM_EPS) -> jax.Array:
    acc = _acc_dtype(x)
    n = jnp.sqrt(jnp.maximum(sq_norms(x), eps)).astype(acc)
    return x.astype(acc) / n[:, None]


def pairwise_cosine(
    x: jax.Array, y: jax.Array, precision: str | None = None
) -> jax.Array:
    """Cosine *distance* (1 − cosine similarity), (q, d) × (c, d) -> (q, c).

    Normalization happens on device; the inner product is one MXU matmul.
    Range [0, 2]; smaller = more similar, so the same top-k machinery applies.
    """
    acc = _acc_dtype(x)
    xn = _l2_normalize(x)
    yn = _l2_normalize(y)
    sim = jax.lax.dot_general(
        xn,
        yn,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc,
        precision=_dot_precision(x, precision),
    )
    return jnp.maximum(1.0 - sim, 0.0)


def pairwise_dist(
    x: jax.Array,
    y: jax.Array,
    metric: str = "l2",
    x_sq: jax.Array | None = None,
    y_sq: jax.Array | None = None,
    precision: str | None = None,
) -> jax.Array:
    """Dispatch on metric; returns distances in sortable space (see KNNResult)."""
    if metric == "l2":
        return pairwise_sq_l2(x, y, x_sq=x_sq, y_sq=y_sq, precision=precision)
    if metric == "cosine":
        return pairwise_cosine(x, y, precision=precision)
    raise ValueError(f"unknown metric {metric!r}")
