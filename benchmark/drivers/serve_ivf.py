"""Driver of the clustered (IVF-Flat) serving cells: ``drivers/serve.py``'s
parent — its ``drive``, and through it ``wait_for`` and ``traced_span``,
imported and not copied — around ``serve_launcher_ivf.py``. An index that
may miss a neighbour cannot be held to "the program's distance against the
reference's in the same slot", so for the length of one run this file puts
two things of its own under the names ``drive`` calls (in this process;
folding them into the shared files is a ``benchmark`` issue's):

- ``compare.compare_answers``: :func:`compare_ivf` — the answers of the
  window for the probe rows against the reference's 100 nearest (not 10):
  recall held to the configuration's floor, every returned distance held
  to the reference's FOR THAT ID, and no returned row nearer than it can
  be; beside them the counts that have to be 0 (below);
- ``loadgen.Log``: every whole answer of the run — not the probe block
  alone — is looked through for the same id twice in a row and for an id
  outside the corpus.

``run.py --control`` switches on the control that the environment variable
``IVF_CONTROL`` names (``nprobe``, the default, or ``rerank_default``:
the configuration's two; ``serve_launcher_ivf.py``
lists the faults that ``benchmark/tests/test_ivf_cell.py`` plants under
the same name). A traced
run hands the per-layer readers ``run["scopes"]`` (as ``serve_cos.py``
does) and ``run["ivf"]`` (the index's summary: ``dim``, ``bucket_cap``,
...), and puts into the line's ``breakdown`` the scopes and ``ivf``: the
index's summary with the window's probe counts. This parent never imports
jax.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
PROBE_COUNTERS = (
    "ivf_probe_slots_total", "ivf_probe_live_rows_total",
    'ivf_probe_partitions_total{kind="probes"}',
    'ivf_probe_partitions_total{kind="distinct"}',
    "ivf_probe_distinct_live_rows_total")


def duplicate_or_dead(ids: np.ndarray, corpus_rows: int) -> int:
    """Slots of (rows, k) ids that repeat an id of their row, or name no
    row of the corpus."""
    ranked = np.sort(ids, axis=1)
    repeats = int((np.diff(ranked, axis=1) == 0).sum())
    return repeats + int(((ids < 0) | (ids >= corpus_rows)).sum())


def compare_ivf(ids, dists, ref_ids, ref_dists, limits: dict,
                counted=None) -> dict:
    """``{"ok", "numbers": {name: [value, limit, ok]}, "info"}`` for
    answers (n, k) against the reference's (n, R) nearest, R >= k.

    - ``recall_at_k``: share of answer slots naming one of the reference's
      first k, a slot with another id counting as a hit where its distance
      ties the reference's k-th within ``tie_rtol`` (``compare.py``'s
      rule). At least ``recall_min``: the source's floor.
    - ``returned_dist_rel_err_max``: over every returned id found among the
      reference's R, the gap between its distance and the reference's for
      THAT id, relative to the reference's. A finish in a lower precision,
      or a distance computed against another row, moves it.
    - ``impossible_distances``: returned ids outside the reference's R
      whose distance is under the reference's R-th (less the limit above):
      a row claimed nearer than it is. Limit 0.
    - ``duplicate_or_dead_ids``: in these answers and, through
      ``counted()``, in every whole answer of the run. Limit 0.
    - not finite or not ascending: limit 0."""
    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    dists = np.asarray(dists, dtype=np.float64)
    ref_dists = np.asarray(ref_dists, dtype=np.float64)
    numbers: dict = {}
    k = ids.shape[1] if ids.ndim == 2 else 0
    shape_ok = (ids.ndim == 2 and ids.shape == dists.shape
                and ref_ids.shape == ref_dists.shape
                and ref_ids.shape[0] == ids.shape[0]
                and ref_ids.shape[1] >= k > 0)
    numbers["shape_mismatch"] = [0 if shape_ok else 1, 0, shape_ok]
    if not shape_ok:
        return {"ok": False, "numbers": numbers}
    finite = np.isfinite(dists)
    bad_order = int((np.diff(dists, axis=1) < 0).sum()) + int((~finite).sum())
    numbers["not_finite_or_not_ascending"] = [bad_order, 0, bad_order == 0]

    among = ids[:, :, None] == ref_ids[:, None, :]  # (n, k, R)
    kth = ref_dists[:, k - 1:k]
    tied = np.abs(dists - kth) <= limits["tie_rtol"] * np.abs(kth)
    hit = among[:, :, :k].any(axis=2) | (tied & finite)
    recall = float(hit.mean())
    numbers["recall_at_k"] = [recall, limits["recall_min"],
                              recall >= limits["recall_min"]]

    found = among.any(axis=2)
    theirs = np.take_along_axis(ref_dists, among.argmax(axis=2), axis=1)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.abs(dists - theirs) / np.abs(theirs)
    rel = np.where(found, np.where(np.isfinite(rel), rel, np.inf), 0.0)
    err = float(rel.max())
    limit = limits["returned_dist_rel_err_max"]
    numbers["returned_dist_rel_err_max"] = [err, limit, err <= limit]
    last = ref_dists[:, -1:]
    nearer = int((~found & (dists < last * (1.0 - limit))).sum())
    numbers["impossible_distances"] = [nearer, 0, nearer == 0]
    rows = int(limits["corpus_rows"])
    bad_ids = duplicate_or_dead(ids, rows) + (counted() if counted else 0)
    numbers["duplicate_or_dead_ids"] = [bad_ids, 0, bad_ids == 0]
    info = {"recall_without_ties": float(among[:, :, :k].any(axis=2).mean()),
            "returned_ids_among_reference": float(found.mean()),
            "dist_rel_err_mean": float(rel[found].mean())
            if found.any() else 0.0,
            "answers": int(ids.shape[0]), "k": int(k),
            "reference_k": int(ref_ids.shape[1])}
    return {"ok": all(v[2] for v in numbers.values()), "numbers": numbers,
            "info": info}


@contextlib.contextmanager
def clustered_checks(config: dict, run_dir: str):
    """The two names of the module docstring, for one run; the comparison
    also reads what the ladder did from the child's ``final.json``
    (``drive`` has stopped the child by then)."""
    from benchmark import compare, loadgen

    # "logged" / "compared": that drive() went through the two names
    counted = {"bad": 0, "lock": threading.Lock(), "logged": False,
               "compared": False}
    limits = {**config["limits"], "corpus_rows": config["rows"]}

    class Log(loadgen.Log):
        def record(self, *, status, rows, doc, **rest):
            counted["logged"] = True
            if status == 200:
                answer = loadgen.check_answer(doc, rows, self.k)
                if answer is not None:
                    n = duplicate_or_dead(answer[0], config["rows"])
                    with counted["lock"]:
                        counted["bad"] += n
            super().record(status=status, rows=rows, doc=doc, **rest)

    def compare_answers(ids, dists, ref_ids, ref_dists, _limits):
        verdict = compare_ivf(ids, dists, ref_ids, ref_dists, limits,
                              counted=lambda: counted["bad"])
        with open(os.path.join(run_dir, "final.json")) as f:
            final = json.load(f)
        shed = int(final.get("degradations", 1))
        full = final.get("rung") in ("full", None) and shed == 0
        verdict["numbers"]["degraded_batches"] = [
            shed if shed else int(not full), 0, full]
        verdict["ok"] = verdict["ok"] and full
        counted["compared"] = "recall_at_k" in verdict["numbers"]
        return verdict

    kept = (loadgen.Log, compare.compare_answers)
    loadgen.Log, compare.compare_answers = Log, compare_answers
    try:
        yield counted
    finally:
        loadgen.Log, compare.compare_answers = kept


def run(cell: dict, args, t_start: float):
    if "jax" in sys.modules:
        raise RuntimeError("the serving parent must stay off jax: the "
                           "child holds the chip")
    from benchmark import harness

    serve = harness.load_by_path("drivers", "serve")
    config = cell["config"]
    run_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg_path = os.path.join(run_dir, "config.json")
    mix_path = os.path.join(run_dir, "traffic.json")
    for path, doc in ((cfg_path, config), (mix_path, cell["traffic"])):
        with open(path, "w") as f:
            json.dump(doc, f)
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher_ivf.py"),
           "--config", cfg_path, "--traffic", mix_path,
           "--seed", str(args.seed), "--run-dir", run_dir,
           "--chips", str(cell["chips"])]
    if args.control:
        cmd += ["--control", os.environ.get("IVF_CONTROL", "nprobe")]
    if args.allow_cpu:
        cmd.append("--allow-cpu")
    child = subprocess.Popen(cmd, cwd=ROOT)
    try:
        with clustered_checks(config, run_dir) as went:
            result = serve.drive(cell, args, t_start, child, run_dir)
        if result is not None and not (went["logged"] and went["compared"]):
            # drive() no longer looks the two names up where this file
            # puts them: the 10-slot comparison ran, which says nothing
            # of an index that may miss a neighbour
            print("error: the run went past this cell's comparison "
                  f"(logged {went['logged']}, compared {went['compared']})",
                  file=sys.stderr, flush=True)
            result["correct"] = False
    finally:
        if child.poll() is None:
            child.terminate()
            try:
                child.wait(60)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
    if result is not None and args.trace:
        with open(os.path.join(run_dir, "final.json")) as f:
            final = json.load(f)
        scopes, about = final.get("scopes"), final.get("index")
        result["run"]["scopes"] = dict(scopes) if scopes else None
        result["run"]["ivf"] = about
        delta = result["run"].get("window_metrics_delta") or {}
        if "breakdown" in result:
            if scopes:
                result["breakdown"]["scopes"] = scopes
            result["breakdown"]["ivf"] = {
                **(about or {}),
                "window": {n: delta.get(n) for n in PROBE_COUNTERS}}
    return result
