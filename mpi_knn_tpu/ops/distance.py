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


def bf16_exact(x):
    """Whether every element of ``x`` is a bfloat16 number (whole numbers
    of magnitude <= 256 all are; NaN never is): a bool scalar on the device for a device
    array (or a tracer), a Python bool for a host one. For such operands
    the hi + lo [+ lo2] pieces a ``high`` / ``highest`` dot splits them into
    have all-zero lo pieces, so ONE bf16 x bf16 pass with float32
    accumulation returns what the multi-pass dot returns.

    On the device the test reads the bit pattern: bfloat16 is float32's
    upper half, so a bf16 number is a float32 whose low 16 bits are zero.
    Rounding to bfloat16 and comparing, as the host does, is NOT a test
    there: inside a fusion the TPU compiler may keep the float32 ->
    bfloat16 -> float32 round trip in float32 (it allows excess precision),
    and the comparison then holds for every ``x`` — on the v5e fractional
    rows took the one-pass dot and answered 4e-3 off (PERF.md §6, PR 29).
    A device array wider than float32 is not examined (False: the rule
    takes float32 programs only)."""
    if isinstance(x, jax.Array):
        if x.dtype.itemsize > 4:
            return jnp.asarray(False)
        bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        return jnp.all((bits & 0xFFFF == 0) & (x == x))  # NaN never is
    x = np.asarray(x)
    # through float32: a bf16 number survives it, and ml_dtypes narrows
    # float32 several times faster than float64
    x32 = x.astype(np.float32)
    return bool(
        (x32 == x).all()
        and (x32.astype(jnp.bfloat16).astype(np.float32) == x32).all()
    )


def onepass_applies(cfg) -> bool:
    """The static side of the one-pass rule: the configurations whose tile
    programs may carry the one-pass branch at all. Everything else (cosine,
    the f64 debug mode, a bf16 corpus, ``precision_policy="mixed"``, an
    uncentred run, a dot that is one pass already) keeps its program as it
    is. The dynamic side is the data's: :func:`bf16_exact` of both centred
    operands — of which a byte stack's (``dtype="uint8"``) is settled by
    its type: see :func:`widen_rows`."""
    return (
        cfg.metric == "l2"
        and cfg.center
        and cfg.dtype in ("float32", "uint8")
        and cfg.precision_policy == "exact"
        and cfg.matmul_precision != "default"
    )


# --- the byte stack: whole-number rows one byte an element at rest ----------
# ONE form rests, at every width: the rows' own bytes (``uint8``), beside
# the whole-number offset a float32 stack of the same rows would have been
# centred by (``center_corpus``: the mean rounded to whole numbers, here
# from exact integer column sums). A tile step widens its tile to float32
# and takes the offset off AFTER widening (:func:`widen_rows`): the result
# is a whole number of magnitude <= 255 — nine bits with its sign, which no
# signed byte holds (so not ``int8`` after a shift by 128: at d = 784 the
# float32 accumulation of the dot is exact only for CENTRED operands, 784 x
# 255^2 = 5.1e7 > 2^24, and a centred byte is a 9-bit number) — and a bf16
# number every time. So a byte stack IS the corpus side of the one-pass
# rule, by type: nothing is read to know it, no upsert can undo it, and
# the values every tile step sees, the norm plane, the zero test's scale
# and therefore the answers are those of the float32 index of the same
# rows, to the bit. At d = 128 the uncentred dot would be exact too (128 x
# 255^2 = 8.3e6 < 2^24) and the subtraction could be saved; one rule at
# every width was preferred to a second form whose zero test scales by
# other norms.
BYTE_MAX = 255.0


def widen_rows(rows: jax.Array, offset: jax.Array | None) -> jax.Array:
    """The float32 rows a byte stack's tile stands for: ``rows`` (.., d)
    uint8 widened, ``offset`` (d,) float32 taken off (None: an uncentred
    index). Any other ``rows`` pass as they are."""
    if rows.dtype != jnp.uint8:
        return rows
    wide = rows.astype(jnp.float32)
    return wide if offset is None else wide - offset


def first_unfit_row(block: jax.Array) -> jax.Array:
    """The first row of ``block`` (n, d) that a byte stack cannot hold
    losslessly — an element that is no whole number in [0, 255]; NaN never
    is — as an int32 scalar, ``n`` where every row fits. A ``uint8`` block
    fits by type and is not read."""
    n = block.shape[0]
    if block.dtype == jnp.uint8:
        return jnp.int32(n)
    x = block.astype(jnp.float32) if not jnp.issubdtype(
        block.dtype, jnp.floating) else block
    fits = (x == jnp.rint(x)) & (x >= 0) & (x <= BYTE_MAX)
    bad = ~jnp.all(fits, axis=-1)
    return jnp.min(jnp.where(bad, jnp.arange(n, dtype=jnp.int32),
                             jnp.int32(n)))


def unfit_row_error(row: int) -> ValueError:
    """What a build says of the first row :func:`first_unfit_row` names."""
    return ValueError(
        f"row {row} of the corpus holds an element that is no whole number "
        "in [0, 255]: a byte stack (dtype='uint8') is lossless and rounds "
        "nothing — build with dtype='float32'")


def byte_rows(corpus, center: bool = True):
    """``(rows, offset)`` of a whole corpus for a byte stack, one array at
    a time (a one-shot call's form; a served index takes its rows in
    blocks, ``serve/index.py build_index_blocks``, with the same check and
    the same offset): the corpus as ``uint8`` — a ``uint8`` array as it
    is, any other after :func:`first_unfit_row` has passed it, else
    ``ValueError`` naming the first offending row — and the whole-number
    offset (:func:`whole_offset`; None where ``center`` is off), (d,)
    float32 on the host."""
    if not isinstance(corpus, jax.Array):
        corpus = np.asarray(corpus)
    n = corpus.shape[0]
    if corpus.dtype != jnp.uint8:
        unfit = int(jax.jit(first_unfit_row)(corpus))
        if unfit < n:
            raise unfit_row_error(unfit)
        corpus = corpus.astype(jnp.uint8)
    if not center:
        return corpus, None
    # exact integer column sums: int32 holds 2^23 rows of bytes
    step = 1 << 23
    total = sum(
        np.asarray(corpus[lo:lo + step].astype(jnp.int32).sum(axis=0),
                   dtype=np.int64)
        for lo in range(0, n, step))
    return corpus, whole_offset(total, n)


def whole_offset(col_sums, rows: int) -> np.ndarray:
    """The whole-number offset of a byte stack from its exact integer
    column sums: the mean rounded to whole numbers, (d,) float32 —
    ``center_corpus``'s offset for the same rows held as floats."""
    total = np.asarray(col_sums, dtype=np.float64)
    return np.rint(total / max(int(rows), 1)).astype(np.float32)


def onepass_fact(cfg, fact):
    """What a tile program is handed as the corpus side of the rule, from
    the ``fact`` of :func:`center_corpus`: a TRUE bool scalar — the program
    then carries both branches and each query tile decides its own side —
    or None: no branch, the program it always was, where the rule does not
    apply to ``cfg`` or the corpus does not qualify. A device fact is READ
    here (``bool(fact)`` waits for the centring pass that made it, nothing
    else): call this last before the program's dispatch, with the work
    that needs no verdict already queued behind that pass, and the device
    does not idle. Under an outer ``jit`` the fact is a tracer and cannot
    be read: it goes into the program, which decides on the device."""
    if fact is None or not onepass_applies(cfg):
        return None
    if isinstance(fact, jax.core.Tracer):
        return fact
    return jnp.asarray(fact) if bool(fact) else None


@jax.jit
def _center_on_device(corpus):
    """``(corpus - mu, mu, fact)`` for a device corpus, one program and two
    passes over it. ``mu`` is the mean, or — where every element is a whole
    number — the mean rounded to whole numbers: L2 is invariant to the
    translation either way, a whole-number offset keeps whole-number rows
    whole, and whole numbers of magnitude <= 256 are bf16 numbers.
    ``fact`` says whether every centred element is one
    (:func:`bf16_exact`)."""
    acc = _acc_dtype(corpus)
    mu = jnp.mean(corpus, axis=0, dtype=acc)
    # a column sum like the mean's own (sums of one shape over one operand
    # are what XLA fuses into a single pass): the fractional parts add up
    # to zero where there are none, and to NaN or more anywhere else
    frac = jnp.sum(jnp.abs(corpus - jnp.rint(corpus)), axis=0, dtype=acc)
    mu = jnp.where(jnp.all(frac == 0), jnp.rint(mu), mu)
    centred = corpus - mu
    return centred, mu, bf16_exact(centred)


def center_corpus(corpus):
    """``(corpus - mu, mu, fact)``: what ``center_for_l2`` and
    ``serve.build_index`` do to a corpus. ``mu`` is the corpus mean (f32
    accumulation on the device, f64 on the host, as ever), rounded to whole
    numbers where the corpus holds nothing else; ``fact`` is the corpus
    side of the one-pass rule, whether every centred element is a bf16
    number — a device bool scalar for a device corpus (no host wait), a
    Python bool for a host one."""
    if isinstance(corpus, jax.Array):
        return _center_on_device(corpus)
    corpus = np.asarray(corpus)
    mu = np.asarray(corpus, dtype=np.float64).mean(axis=0)
    if (corpus == np.rint(corpus)).all():
        mu = np.rint(mu)
    centred = corpus - mu
    return centred, mu, bf16_exact(centred)


@jax.named_scope("knn.center")
def center_for_l2(corpus, queries, all_pairs: bool):
    """Center corpus (and queries consistently) before L2 distances.

    Translation leaves L2 distances unchanged, but cancellation error in the
    ‖x‖²+‖y‖²−2xy matmul form scales with the *centered* norms — centering
    keeps fp noise (and the relative zero-distance threshold, ops.topk) tight
    even when the data sits far from the origin. One shared implementation
    for api.all_knn and both resumable drivers: device-resident inputs are
    centered on device (no host bounce; f64 stays f64 when x64 is on), host
    inputs keep the f64 mean for the debug mode.

    The offset is :func:`center_corpus`'s: the mean, or the mean rounded
    to whole numbers for a whole-number corpus. Returns ``(corpus, queries,
    fact, mu)``; ``fact`` says whether every centred corpus element is a
    bf16 number, the corpus side of the one-pass rule that
    ``backends.serial.masked_dist_tile`` applies; ``mu`` is the offset that
    was subtracted (the resumable drivers fold :func:`offset_is_whole` into
    their run identity: a carry made under the mean must not resume under
    the rounded mean).

    The two paths accumulate the mean at different precisions, so centered
    values for the SAME data differ by fp noise across residencies —
    bit-identical checkpoint resume holds per-residency only, and
    ring_resumable folds the residency into the run fingerprint so a
    cross-residency resume restarts rather than merging mixed carries.
    """
    corpus, mu, fact = center_corpus(corpus)
    queries = corpus if all_pairs else queries - mu
    return corpus, queries, fact, mu


def offset_is_whole(mu) -> bool:
    """Whether a centring offset is the rounded one (a fractional corpus's
    mean is whole in no real case). Waits for a device offset."""
    return bool((mu == np.rint(mu)).all())


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
    onepass: bool = False,
) -> jax.Array:
    """Squared L2 distances between all rows of x (q, d) and y (c, d) -> (q, c).

    The −2·X·Yᵀ term is a single MXU matmul (``preferred_element_type`` forces
    f32/f64 accumulation even for bf16 inputs). Precomputed squared norms may
    be passed in so tiled callers hoist them out of the tile loop.

    ``onepass`` (static) is for operands the caller knows to be bf16 numbers
    (:func:`bf16_exact`): the dot's operands are narrowed to bf16, which
    loses nothing, and the MXU runs one pass whatever ``precision`` says.
    The norms still come from the operands as given.
    """
    acc = _acc_dtype(x)
    if x_sq is None:
        x_sq = sq_norms(x)
    if y_sq is None:
        y_sq = sq_norms(y)
    if onepass:
        x, y = x.astype(jnp.bfloat16), y.astype(jnp.bfloat16)
        precision = jax.lax.Precision.DEFAULT
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
# unit-row identity (d² = 2·d_cos) must treat such rows as
# degenerate — guard with `sq_norms(x) <= _NORM_EPS`, not `== 0`.
_NORM_EPS = 1e-30


def _l2_normalize(x: jax.Array, eps: float = _NORM_EPS) -> jax.Array:
    acc = _acc_dtype(x)
    n = jnp.sqrt(jnp.maximum(sq_norms(x), eps)).astype(acc)
    return x.astype(acc) / n[:, None]


# the cosine dot and its scaling as the trace names them, inside
# ``knn.dist`` (``backends.serial.masked_dist_tile`` opens it), and the
# query side's normalisation, once a query tile ahead of the tile steps
COSINE_SCOPE = "knn.dist_cosine"
QUNIT_SCOPE = "knn.qunit"


@jax.named_scope(QUNIT_SCOPE)
def unit_rows(x: jax.Array) -> jax.Array:
    """The rows of ``x`` (q, d) at unit length: the query side of a
    prepared cosine search, made once for a query tile and not in its tile
    steps. Normalised at full precision, held in ``x``'s own dtype (the
    dot's other operand has it). A row at or under the ``_NORM_EPS`` clamp
    is not unit (a zero row stays zero, at distance 1 from everything)."""
    return _l2_normalize(x).astype(x.dtype)


def cosine_inv_norms(x: jax.Array) -> jax.Array:
    """1 / |row| for the rows of ``x`` (r, d) -> (r,), the norm clamped as
    :func:`_l2_normalize` clamps it: the corpus side of a prepared cosine
    search, what a tile stack keeps in the slot where an L2 stack keeps its
    squared norms (``backends.serial.stack_norms``). A square root and a
    division, not ``rsqrt``: made once a corpus, and the answer's last
    bits hang on it."""
    return 1.0 / jnp.sqrt(jnp.maximum(sq_norms(x), _NORM_EPS))


def pairwise_cosine(
    x: jax.Array,
    y: jax.Array,
    precision: str | None = None,
    y_inv: jax.Array | None = None,
) -> jax.Array:
    """Cosine *distance* (1 − cosine similarity), (q, d) × (c, d) -> (q, c).

    The inner product is one MXU matmul. Range [0, 2]; smaller = more
    similar, so the same top-k machinery applies.

    Two forms of one value. Without ``y_inv`` both operands are normalised
    here, on the device, at every call: the form of a caller that brings
    rows as it has them (the ring's rounds, ``ops/`` users). With ``y_inv``
    (:func:`cosine_inv_norms` of ``y``'s rows, kept by whoever keeps ``y``)
    **``x`` holds unit rows already** (:func:`unit_rows`) and ``y`` is
    touched by the dot alone: ``d = max(1 - (x . y) * y_inv, 0)`` — no
    pass over a (c, d) tile to norm it, none to divide it, no second tile
    written for the dot to read. A row of ``y`` under the clamp has a huge
    ``y_inv`` and a zero dot: distance 1, as in the first form.
    """
    acc = _acc_dtype(x)
    precision = _dot_precision(x, precision)  # by the rows as brought
    if y_inv is None:
        x, y = _l2_normalize(x), _l2_normalize(y)
    sim = jax.lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=acc,
        precision=precision,
    )
    if y_inv is not None:
        sim = sim * y_inv[None, :].astype(acc)
    return jnp.maximum(1.0 - sim, 0.0)


# the inner-product dot as the trace names it, inside ``knn.dist``
IP_SCOPE = "knn.dist_ip"


def pairwise_neg_ip(
    x: jax.Array,
    y: jax.Array,
    precision: str | None = None,
) -> jax.Array:
    """Negated inner products ``-<x_i, y_j>``, (q, d) × (c, d) -> (q, c):
    the ``ip`` metric in the engine's one ordering (smaller = nearer, the k
    smallest ascending), so the same top-k machinery applies. The dot
    alone, at the rows' precision: a score is not a distance — it is not
    translation-invariant (nothing is centred), not bounded below (no
    clamp: the nearest rows' values are the most negative ones) and has
    no zero that means "the same row" (no zero test), and neither side
    has a norm in it."""
    return -jax.lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=_acc_dtype(x),
        precision=_dot_precision(x, precision),
    )


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
        # the corpus side's slot holds 1 / |row| for cosine
        # (``cosine_inv_norms``); given, ``x`` holds unit rows already
        return pairwise_cosine(x, y, precision=precision, y_inv=y_sq)
    if metric == "ip":
        return pairwise_neg_ip(x, y, precision=precision)
    raise ValueError(f"unknown metric {metric!r}")
