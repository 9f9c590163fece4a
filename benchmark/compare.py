"""The comparison that decides ``correct``: the program's answers for the
probe rows against the plain reference's.

Numbers compared, each with a limit of its own (``limits`` in the
configuration file; PERF.md gives the readings each was set from):

- ``recall_at_k``: share of answer slots naming a reference neighbour, a
  slot with another id counting as a hit where its distance ties the
  reference's k-th within ``tie_rtol`` (``chip_smoke.compare_neighbors``'
  rule: two programs that round the same sums in another order rank
  near-equal candidates differently). At least ``recall_min``.
- ``dist_rel_err_max``: the widest gap between a program distance and the
  reference distance in the same slot, relative to the reference. Sorted
  distances are compared slot by slot, so a swap of near-ties moves it by
  no more than the tie. At most ``dist_rel_err_max``; the number that a
  lower matmul precision moves first.
- every distance finite and every row ascending: exact, limit 0 breaches.
"""

from __future__ import annotations

import numpy as np


def compare_answers(ids, dists, ref_ids, ref_dists, limits: dict) -> dict:
    """``{"ok", "numbers": {name: [value, limit, ok]}}``."""
    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    dists = np.asarray(dists, dtype=np.float64)
    ref_dists = np.asarray(ref_dists, dtype=np.float64)
    numbers: dict = {}
    shape_ok = ids.shape == ref_ids.shape and dists.shape == ref_dists.shape
    numbers["shape_mismatch"] = [0 if shape_ok else 1, 0, shape_ok]
    if not shape_ok or ids.size == 0:
        return {"ok": False, "numbers": numbers}
    k = ref_ids.shape[1]
    finite = np.isfinite(dists)
    bad_order = int((np.diff(dists, axis=1) < 0).sum()) + int((~finite).sum())
    numbers["not_finite_or_not_ascending"] = [bad_order, 0, bad_order == 0]

    same = (ids[:, :, None] == ref_ids[:, None, :]).any(axis=2)
    kth = ref_dists[:, -1:]
    tied = np.abs(dists - kth) <= limits["tie_rtol"] * np.abs(kth)
    recall = float((same | (tied & finite)).mean())
    numbers["recall_at_k"] = [recall, limits["recall_min"],
                              recall >= limits["recall_min"]]

    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.abs(dists - ref_dists) / np.abs(ref_dists)
    rel = np.where(np.isfinite(rel), rel, np.inf)
    err = float(rel.max())
    numbers["dist_rel_err_max"] = [err, limits["dist_rel_err_max"],
                                   err <= limits["dist_rel_err_max"]]
    finite_rel = rel[np.isfinite(rel)]
    info = {"dist_rel_err_mean": float(finite_rel.mean())
            if finite_rel.size else float("inf"),
            "ids_equal_share": float((ids == ref_ids).mean()),
            "answers": int(ids.shape[0]), "k": int(k)}
    return {"ok": all(v[2] for v in numbers.values()), "numbers": numbers,
            "info": info}


def say(numbers: dict, out=None, info: dict | None = None) -> None:
    """One line per number compared, beside its limit."""
    import sys

    if info:
        print(f"check info (not compared): {info}", file=out or sys.stdout,
              flush=True)
    for name, (value, limit, ok) in numbers.items():
        print(f"check {name}: value={value!r} limit={limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=out or sys.stdout,
              flush=True)
