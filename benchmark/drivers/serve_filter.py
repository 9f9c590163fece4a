"""Driver of the filtered serving cells: ``drivers/serve.py``'s parent — its
``drive``, and through it ``wait_for`` and ``traced_span``, imported and not
copied — around ``serve_launcher_filter.py``, with a predicate on every
query row. ``drive`` knows rows only, so for the length of one run this file
puts four things of its own under the names ``drive`` calls (in this
process; folding them into the shared files is a ``benchmark`` issue's):

- ``harness.query_pool``: the pool's rows WITH their tags as two more
  columns (whole numbers a float32 holds exactly; -1: none), from
  ``datagen/clustered_u8_tags.py query_pool`` over the bags this parent
  makes for itself on a thread while the child builds;
- ``loadgen.Conn``: ``loadgen``'s client, whose body is the rows followed
  by the tags as int32 under the header ``X-Filter-Tags`` (``--control``:
  the tags are DROPPED, the rows go out alone — the control
  ``drop_filters``);
- ``loadgen.check_answer`` / ``loadgen.Log``: an answer may end in empty
  slots (a query that fewer than k rows match: distance +inf, id -1) and
  is then whole; and EVERY answered row of the run — not the probe block
  alone — has each returned id's bag looked up for the row's tags, the
  failures counted;
- ``compare.compare_answers``: the shared comparison on the slots the
  reference fills, the empty slots compared for being empty on both
  sides, and ``predicate_failures`` beside its numbers with a limit of 0.

A traced run hands the per-layer readers ``run["scopes"]`` (as
``serve_cos.py`` does) and ``run["filter"]`` (the configuration's ``rows``
and ``dim``, the index's summary of its tags) and puts into the line's
``breakdown`` the scopes and ``filter_rows``, the window's query rows by
regime (``FILTER.md`` says how to read it). This parent never imports jax.
"""

from __future__ import annotations

import contextlib
import http.client
import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FILTER_HEADER = "X-Filter-Tags"
REGIMES = ("none", "scan", "gather", "empty")


def whole_answer(doc: dict, rows: int, k: int):
    """``loadgen.check_answer`` for a filtered answer: (ids, dists) where
    the reply has the right shape and every row is finite ascending
    distances followed by empty slots alone (+inf, id -1), else None."""
    try:
        ids = np.asarray(doc["ids"], dtype=np.int64)
        dists = np.asarray(doc["dists"], dtype=np.float64)
    except (KeyError, ValueError, TypeError):
        return None
    if ids.shape != (rows, k) or dists.shape != (rows, k):
        return None
    empty = np.isposinf(dists)
    if np.isnan(dists).any() or np.isneginf(dists).any():
        return None
    with np.errstate(invalid="ignore"):
        falling = np.diff(dists, axis=1) < 0  # inf - inf: nan, not < 0
    if falling.any() or (empty != (ids < 0)).any():
        return None
    return ids, dists


def predicate_failures(bag_matrix: np.ndarray, ids: np.ndarray,
                       tags: np.ndarray) -> int:
    """Returned ids (rows, k) whose bag lacks a tag of their query row
    (rows, W), -1 none; empty slots (id < 0) name no row."""
    bags = bag_matrix[np.maximum(ids, 0)]  # (rows, k, B)
    has = (bags[:, :, None, :] == tags[:, None, :, None]).any(-1)
    ok = (has | (tags < 0)[:, None, :]).all(-1)
    return int((~ok & (ids >= 0)).sum())


def compare_filtered(plain, failures):
    """``compare.compare_answers`` for answers that may end in empty
    slots: where the reference's slot is empty the answer's has to be, and
    the pair then takes no further part (both read the row's last filled
    distance there, under one id); the returned numbers gain
    ``predicate_failures`` (``failures()``, limit 0)."""
    def compare_answers(ids, dists, ref_ids, ref_dists, limits):
        ids, ref_ids = np.array(ids), np.array(ref_ids)
        dists = np.array(dists, dtype=np.float64)
        ref_dists = np.array(ref_dists, dtype=np.float64)
        both = np.isposinf(ref_dists) & np.isposinf(dists) & (ids < 0)
        last = np.max(np.where(np.isfinite(ref_dists), ref_dists, 1.0),
                      axis=1, keepdims=True)
        fill = np.broadcast_to(last, dists.shape)
        dists[both], ref_dists[both] = fill[both], fill[both]
        ids[both], ref_ids[both] = -1, -1
        verdict = plain(ids, dists, ref_ids, ref_dists, limits)
        n = failures()
        limit = limits.get("predicate_failures_max", 0)
        verdict["numbers"]["predicate_failures"] = [n, limit, n <= limit]
        verdict["ok"] = verdict["ok"] and n <= limit
        return verdict

    return compare_answers


@contextlib.contextmanager
def filtered_clients(config: dict, seed: int, made: dict, bagger,
                     control: bool):
    """The four names of the module docstring, for one run."""
    from benchmark import compare, harness, loadgen

    dim = config["dim"]
    width = config["max_query_tags"]
    counted = {"failures": 0, "lock": threading.Lock()}

    def query_pool(cfg, seed_, rows):
        bagger.join()
        gen = harness.datagen_for(cfg)
        pool, tags = gen.query_pool(
            seed_, rows, cfg["data"], dim, made["which"], made["indptr"],
            made["indices"])
        made["pool_tags"] = tags
        return np.concatenate([pool, tags.astype(np.float32)], axis=1)

    class Conn(loadgen.Conn):
        def post(self, tenant: str, q: np.ndarray) -> tuple:
            rows = np.ascontiguousarray(q[:, :dim], dtype="<f4")
            headers = {"Content-Type": "application/octet-stream",
                       loadgen.TENANT_HEADER: tenant}
            body = rows.tobytes()
            if not control:
                body += np.ascontiguousarray(
                    q[:, dim:], dtype="<i4").tobytes()
                headers[FILTER_HEADER] = str(width)
            for _ in range(2):  # as loadgen.Conn.post
                fresh = self.conn is None
                try:
                    if fresh:
                        self.open()
                    self.conn.request("POST", "/query", body=body,
                                      headers=headers)
                    resp = self.conn.getresponse()
                    data = resp.read()
                    if resp.status != 200:
                        return resp.status, {}
                    return 200, json.loads(data)
                except (OSError, http.client.HTTPException, ValueError):
                    self.close()
                    if fresh:
                        return 0, {}
            return 0, {}

    class Log(loadgen.Log):
        def record(self, *, status, rows, offset, doc, **rest):
            if status == 200:
                answer = whole_answer(doc, rows, self.k)
                if answer is not None:
                    idx = (offset + np.arange(rows)) % self.pool_rows
                    n = predicate_failures(
                        made["matrix"], answer[0], made["pool_tags"][idx])
                    with counted["lock"]:
                        counted["failures"] += n
            super().record(status=status, rows=rows, offset=offset, doc=doc,
                           **rest)

    names = ((harness, "query_pool", query_pool), (loadgen, "Conn", Conn),
             (loadgen, "check_answer", whole_answer), (loadgen, "Log", Log),
             (compare, "compare_answers", compare_filtered(
                 compare.compare_answers, lambda: counted["failures"])))
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
    config = cell["config"]
    run_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg_path = os.path.join(run_dir, "config.json")
    mix_path = os.path.join(run_dir, "traffic.json")
    for path, doc in ((cfg_path, config), (mix_path, cell["traffic"])):
        with open(path, "w") as f:
            json.dump(doc, f)
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher_filter.py"),
           "--config", cfg_path, "--traffic", mix_path,
           "--seed", str(args.seed), "--run-dir", run_dir,
           "--chips", str(cell["chips"])]
    if args.allow_cpu:
        cmd.append("--allow-cpu")
    child = subprocess.Popen(cmd, cwd=ROOT)
    # this side's own copy of the bags, for the pool's tags and the check
    # of every answer: made while the child builds
    made: dict = {}
    gen = harness.datagen_for(config)
    bagger = threading.Thread(
        target=lambda: made.update(zip(
            ("which", "indptr", "indices", "matrix"),
            gen.bags(config["rows"], config["data"], threads=4))),
        name="driver-bags", daemon=True)
    bagger.start()
    try:
        with filtered_clients(config, args.seed, made, bagger,
                              args.control):
            result = serve.drive(cell, args, t_start, child, run_dir)
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
        scopes = final.get("scopes")
        result["run"]["scopes"] = dict(scopes) if scopes else None
        result["run"]["filter"] = {
            "rows": config["rows"], "dim": config["dim"],
            "tags": final.get("tags")}
        delta = result["run"].get("window_metrics_delta") or {}
        by_regime = {r: delta.get('filter_rows_total{regime="%s"}' % r)
                     for r in REGIMES}
        if "breakdown" in result:
            if scopes:
                result["breakdown"]["scopes"] = scopes
            if any(v is not None for v in by_regime.values()):
                result["breakdown"]["filter_rows"] = by_regime
    return result
