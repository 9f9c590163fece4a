"""NumPy float64 oracle implementing the *reference's observable semantics*
(SURVEY.md §4 "Parity"): full pairwise L2 distances, zero-distance exclusion
by value (``/root/reference/knn-serial.c:86``), first-encountered-wins on
exact ties (the reference tests ``sqrt(S) < worst`` strictly while scanning
candidate index ascending), and the quirk vote loops. Deliberately naive —
O(m·q·d) dense — so it can't share bugs with the device code."""

from __future__ import annotations

import numpy as np


def oracle_all_knn(
    corpus: np.ndarray,
    k: int,
    queries: np.ndarray | None = None,
    metric: str = "l2",
    exclude_self: bool | None = None,
    exclude_zero: bool = True,
):
    """Returns (dists (q,k) in sortable space [sq-l2 or 1-cos], ids (q,k))."""
    corpus = np.asarray(corpus, dtype=np.float64)
    all_pairs = queries is None
    q = corpus if all_pairs else np.asarray(queries, dtype=np.float64)
    if exclude_self is None:
        exclude_self = all_pairs

    if metric == "l2":
        d = ((q[:, None, :] - corpus[None, :, :]) ** 2).sum(-1)
    elif metric == "cosine":
        qn = q / np.linalg.norm(q, axis=-1, keepdims=True)
        cn = corpus / np.linalg.norm(corpus, axis=-1, keepdims=True)
        d = 1.0 - qn @ cn.T
        d = np.maximum(d, 0.0)
    else:
        raise ValueError(metric)

    if exclude_zero:
        d = np.where(d <= 0.0, np.inf, d)
    if exclude_self and all_pairs:
        np.fill_diagonal(d, np.inf)

    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    dists = np.take_along_axis(d, order, axis=1)
    ids = order.astype(np.int32)
    ids[np.isinf(dists)] = -1
    return dists, ids


def recall_against_oracle(
    got_ids: np.ndarray,
    oracle_dists: np.ndarray,
    oracle_ids: np.ndarray,
    k: int,
) -> float:
    """Tie-aware recall@k of retrieved ids against the f64 oracle.

    A retrieved id counts as a hit if its oracle distance is within the
    oracle's k-th distance — so when several candidates TIE at the top-k
    boundary, any tied member is as correct as any other (a backend that
    legitimately breaks the tie differently must not be scored as a
    miss). The oracle arrays may carry MORE than k columns; passing a
    wider oracle (e.g. ``oracle_all_knn(X, k=k + margin)``) widens the
    visible tie cohort at the boundary. With exactly k columns this
    degenerates to plain set-intersection recall (the historical
    ``test_mixed_precision._recall``).

    Invalid oracle slots (id −1 / +inf distance: fewer than k valid
    neighbors exist) shrink the denominator — recall is over neighbors
    the oracle could actually produce.
    """
    got = np.asarray(got_ids)[:, :k]
    od = np.asarray(oracle_dists)
    oi = np.asarray(oracle_ids)
    total = 0.0
    rows = 0
    for r in range(got.shape[0]):
        valid = oi[r] >= 0
        n_valid = min(k, int(valid.sum()))
        if n_valid == 0:
            continue
        thresh = od[r, n_valid - 1]
        want = set(oi[r][valid & (od[r] <= thresh)].tolist())
        total += len(set(got[r].tolist()) & want) / n_valid
        rows += 1
    return total / max(rows, 1)


def oracle_vote_quirk(counts: np.ndarray, cmp_j: np.ndarray) -> np.ndarray:
    """Literal python transcription of the reference winner scan semantics
    (``knn-serial.c:121-124``): most conflates count and label."""
    out = np.zeros(counts.shape[0], dtype=np.int64)
    for r in range(counts.shape[0]):
        most = 0
        for j in range(counts.shape[1]):
            if counts[r, j] > most or (counts[r, j] == most and j == cmp_j[r]):
                most = j + 1
        out[r] = most - 1
    return out


def oracle_vote_correct(
    counts: np.ndarray, nearest: np.ndarray, tie_break: str = "nearest"
) -> np.ndarray:
    out = np.zeros(counts.shape[0], dtype=np.int64)
    for r in range(counts.shape[0]):
        maxc = counts[r].max()
        tied = np.flatnonzero(counts[r] == maxc)
        if tie_break == "nearest" and nearest[r] in tied:
            out[r] = nearest[r]
        else:
            out[r] = tied[0]
    return out


def int_sq_l2(q: np.ndarray, c: np.ndarray) -> np.ndarray:
    """Exact squared L2 distances of WHOLE-NUMBER rows, (q, c) int64:
    |q|^2 + |c|^2 - 2 q.c in float64, where every product and partial sum
    is a whole number far below 2^53. Not the direct form: its (q, c, d)
    int64 array is 6.6 GB at 1024 x 1024 x 784 (twice, with its square),
    and fresh pages are dear where tier-1 runs — such a case took minutes
    beside five other workers and the suite ran into its time limit."""
    q, c = np.asarray(q, np.float64), np.asarray(c, np.float64)
    d = (q * q).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2.0 * (q @ c.T)
    return d.astype(np.int64)

