"""The comparison that decides ``correct`` in the range-search cells: the
program's answers for the probe rows against the plain reference's
(``reference_range.py``), both in the suite's range format — for every
probe row a list of (distance, id) pairs of no fixed length.

Numbers compared, each with a limit of its own (``limits`` in the
configuration file; ``RANGE.md`` gives the readings each was set from):

- ``completeness``: the share of the reference's (query row, corpus row)
  pairs that the answers hold. At least ``completeness_min`` (1.0: every
  row within the radius, no pair missing).
- ``foreign_pairs``: answered pairs the reference does not hold — a row
  outside the radius, a tombstone, a padding slot. At most
  ``foreign_pairs_max`` (0).
- ``dist_rel_err_max``: over the pairs both hold, the widest gap between
  the answered distance and the reference's, relative to the reference's.
  At most ``dist_rel_err_max`` (0.0: whole numbers, nothing rounds).
- ``rows_out_of_order``: answered rows whose pairs are not ascending by
  (distance, id), or hold a distance that is not finite or not under the
  row's radius. Limit 0.
- ``lims_do_not_add_up``: answers whose offsets do not start at 0, fall, or
  do not end at the flat lists' length. Limit 0.
"""

from __future__ import annotations

import numpy as np


def rows_of(lims, dists, ids):
    """``[(dists, ids), ...]`` a row of one answer in range format, or None
    where its offsets do not add up."""
    lims = np.asarray(lims, dtype=np.int64)
    dists, ids = np.asarray(dists, np.float64), np.asarray(ids, np.int64)
    if (lims.ndim != 1 or lims.size < 1 or lims[0] != 0
            or (np.diff(lims) < 0).any() or dists.ndim != 1
            or dists.shape != ids.shape or lims[-1] != dists.shape[0]):
        return None
    return [(dists[a:b], ids[a:b]) for a, b in zip(lims[:-1], lims[1:])]


def row_in_order(dists: np.ndarray, ids: np.ndarray, radius: float) -> bool:
    """Ascending by (distance, id), finite, strictly under the radius."""
    if not (np.isfinite(dists).all() and (dists < radius).all()):
        return False
    step_d, step_i = np.diff(dists), np.diff(ids)
    return bool(((step_d > 0) | ((step_d == 0) & (step_i > 0))).all())


def compare_ranges(answers: list, reference: list, radius: float,
                   limits: dict, misshapen: int = 0) -> dict:
    """``{"ok", "numbers": {name: [value, limit, ok]}, "info"}``.
    ``answers`` / ``reference``: a (dists, ids) pair a probe answer, the
    same rows in the same order; ``misshapen`` answers whose offsets did
    not add up never got this far and are counted here."""
    want = held = foreign = disorder = 0
    err = 0.0
    for (d, i), (ref_d, ref_i) in zip(answers, reference):
        d, ref_d = np.asarray(d, np.float64), np.asarray(ref_d, np.float64)
        i, ref_i = np.asarray(i, np.int64), np.asarray(ref_i, np.int64)
        disorder += not row_in_order(d, i, radius)
        both, at, ref_at = np.intersect1d(i, ref_i, return_indices=True)
        want += ref_i.size
        held += both.size
        foreign += i.size - both.size  # (a pair answered twice is foreign)
        if both.size:
            with np.errstate(divide="ignore", invalid="ignore"):
                rel = np.abs(d[at] - ref_d[ref_at]) / np.abs(ref_d[ref_at])
            err = max(err, float(np.where(np.isfinite(rel), rel,
                                          np.inf).max()))
    completeness = held / want if want else 1.0
    numbers = {
        "completeness": [completeness, limits["completeness_min"],
                         completeness >= limits["completeness_min"]],
        "foreign_pairs": [foreign, limits["foreign_pairs_max"],
                          foreign <= limits["foreign_pairs_max"]],
        "dist_rel_err_max": [err, limits["dist_rel_err_max"],
                             err <= limits["dist_rel_err_max"]],
        "rows_out_of_order": [disorder, 0, disorder == 0],
        "lims_do_not_add_up": [misshapen, 0, misshapen == 0],
        # a comparison of nothing proves nothing
        "probe_answers": [len(answers), 1, len(answers) >= 1],
    }
    info = {"reference_pairs": int(want), "answers": len(answers),
            "pairs_a_row": want / max(1, len(answers))}
    return {"ok": all(v[2] for v in numbers.values()), "numbers": numbers,
            "info": info}
