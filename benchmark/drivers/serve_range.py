"""Driver of the range-search serving cells: ``drivers/serve.py``'s parent —
its ``drive``, and through it ``wait_for`` and ``traced_span``, imported
and not copied — around ``serve_launcher_range.py``, with a radius on every
request and answers of no fixed length. ``drive`` knows "(rows, k)" only,
so for the length of one run this file puts four things of its own under
the names ``drive`` calls (in this process, as ``serve_filter.py`` does;
folding them into the shared files is a ``benchmark`` issue's):

- ``harness.query_pool``: the mix's stratified pool
  (``datagen/dupgroups_u8_blocks.py query_pool``: near-copies of planted
  groups by their expected number of results, laid out with the probe
  block's period);
- ``loadgen.Conn``: ``loadgen``'s client, its connection adding the
  header ``X-Radius`` to every request;
- ``loadgen.check_answer``: EVERY answer of the window is checked for
  shape and order — ``lims`` of rows + 1 offsets that add up to the flat
  lists, every row ascending by (distance, id), every distance finite and
  under the radius — and one that is not is a failed request; a row's
  answer has no fixed length, so what ``drive`` keeps and stacks of a
  probe row is one object (:class:`Row`);
- ``compare.compare_answers``: those answers against the reference's
  lists for the same rows (``compare_range.compare_ranges``: completeness
  1.0, no foreign pair, distances equal).

A traced run hands the per-layer readers ``run["scopes"]``,
``run["range"]`` (``scan_s`` / ``overflow_s`` / ``finish_s``: the device
seconds under ``knn.scan_range`` / ``knn.range_overflow`` /
``knn.range_finish``) and ``run["about"]`` (the corpus's ``rows`` and
``dim``), and puts the scopes, the launcher's phases and memory readings
and ``range`` (the three scopes' seconds, the counters' movements in the
traced span) into the line's ``breakdown``. This parent never imports jax.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
RADIUS_HEADER = "X-Radius"
COUNTERS = ('knn_dist_tile_steps_total{path="range"}',
            "knn_range_rows_total", "knn_range_results_total",
            "knn_range_overflow_rows_total",
            "knn_range_overflow_tiles_total",
            "knn_range_refused_rows_total")


def whole_answer(doc: dict, rows: int, radius: float):
    """``loadgen.check_answer`` for an answer in range format: ``(ids,
    dists)``, each a LIST of one array a row, where the offsets add up and
    every row is in order and under the radius; else None."""
    from benchmark import compare_range

    try:
        answer = compare_range.rows_of(doc["lims"], doc["dists"], doc["ids"])
    except (KeyError, ValueError, TypeError):
        return None
    if answer is None or len(answer) != rows:
        return None
    if not all(compare_range.row_in_order(d, i, radius) for d, i in answer):
        return None
    return [i for _, i in answer], [d for d, _ in answer]


class Row:
    """One probe row's answer, a (dists, ids) pair of no fixed length:
    what ``drive`` stacks in place of a (k,) row (``np.stack`` makes an
    object array of them)."""

    __slots__ = ("dists", "ids")

    def __init__(self, dists, ids):
        self.dists, self.ids = dists, ids


@contextlib.contextmanager
def range_clients(config: dict, mix: dict, run_dir: str):
    """The four names of the module docstring, for one run."""
    from benchmark import compare, compare_range, harness, loadgen

    radius = float(mix["radius"])

    def query_pool(cfg, seed, rows):
        return harness.datagen_for(cfg).query_pool(seed, rows, cfg, mix)[0]

    class Conn(loadgen.Conn):
        def open(self) -> None:
            """``loadgen.Conn``'s connection, every request of which names
            the radius beside the headers it is given."""
            super().open()
            request = self.conn.request
            self.conn.request = lambda method, url, body=None, headers=(): (
                request(method, url, body=body,
                        headers={**dict(headers), RADIUS_HEADER: repr(radius)}))

    def check_answer(doc, rows, k):
        answer = whole_answer(doc, rows, radius)
        if answer is None:
            return None
        both = [Row(d, i) for i, d in zip(*answer)]
        return both, both  # ``Log.record`` keeps [0][i] and [1][i]

    def compare_answers(answers, _, ref_rows, __, limits):
        ref = np.load(os.path.join(run_dir, "probe_ref.npz"))
        reference = compare_range.rows_of(
            ref["lims"], ref["flat_dists"], ref["flat_ids"])
        return compare_range.compare_ranges(
            [(row.dists, row.ids) for row in answers],
            [reference[int(r)] for r in np.asarray(ref_rows)],
            radius, limits)

    names = ((harness, "query_pool", query_pool), (loadgen, "Conn", Conn),
             (loadgen, "check_answer", check_answer),
             (compare, "compare_answers", compare_answers))
    kept = [(mod, name, getattr(mod, name)) for mod, name, _ in names]
    for mod, name, new in names:
        setattr(mod, name, new)
    try:
        yield
    finally:
        for mod, name, old in kept:
            setattr(mod, name, old)


def run(cell: dict, args, t_start: float):
    if "jax" in sys.modules:
        raise RuntimeError("the serving parent must stay off jax: the "
                           "child holds the chip")
    from benchmark import harness

    serve = harness.load_by_path("drivers", "serve")
    config, mix = cell["config"], cell["traffic"]
    run_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg_path = os.path.join(run_dir, "config.json")
    mix_path = os.path.join(run_dir, "traffic.json")
    for path, doc in ((cfg_path, config), (mix_path, mix)):
        with open(path, "w") as f:
            json.dump(doc, f)
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher_range.py"),
           "--config", cfg_path, "--traffic", mix_path,
           "--seed", str(args.seed), "--run-dir", run_dir,
           "--chips", str(cell["chips"])]
    if args.control:
        cmd.append("--control")
    if args.allow_cpu:
        cmd.append("--allow-cpu")
    child = subprocess.Popen(cmd, cwd=ROOT)
    try:
        with range_clients(config, mix, run_dir):
            result = serve.drive(cell, args, t_start, child, run_dir)
    finally:
        if child.poll() is None:
            child.terminate()
            try:
                child.wait(60)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
    if result is None and child.returncode == 4:
        # the launcher's verdict on the checkout, handed on as it is
        raise SystemExit(4)
    if result is not None and args.trace:
        with open(os.path.join(run_dir, "final.json")) as f:
            final = json.load(f)
        scopes = final.get("scopes")
        result["run"]["scopes"] = dict(scopes) if scopes else None
        result["run"]["range"] = final.get("range")
        result["run"]["about"] = {key: config[key] for key in ("rows", "dim")}
        if "breakdown" in result:
            delta = result["run"].get("traced_metrics_delta") or {}
            result["breakdown"].update(
                scopes=scopes, phases=final.get("phases"),
                memory=final.get("memory"),
                # what the range readers divide, by name
                range={**(final.get("range") or {}),
                       **{name: delta.get(name) for name in COUNTERS}})
    return result
