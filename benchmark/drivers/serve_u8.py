"""Driver of the byte-stack serving cells: ``drivers/serve.py``'s parent —
its ``drive``, and through it ``wait_for`` and ``traced_span``, imported
and not copied, as ``drivers/serve_ip.py`` does — around
``serve_launcher_u8.py``, the child that makes its corpus a block at a
time, twice (the streamed reference, the build in blocks). A traced run
hands the per-layer readers ``run["scopes"]`` (own device seconds in the
traced span by innermost ``knn.*`` scope), ``run["u8"]`` (``scan_s``: the
device seconds under ``knn.scan_u8``; ``rest_bytes_per_row``: the gauge
``serve_index_rest_bytes_per_row``) and ``run["about"]`` (the corpus's
``rows``, ``dim``, ``k``), and puts the scopes, the launcher's phases, its
memory readings and ``scan_u8`` (the scope ``knn.scan_u8``'s device seconds
and the counter ``knn_dist_tile_steps_total{path="u8"}``'s movement in the
span) into the line's ``breakdown``. This parent never
imports jax.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


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
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher_u8.py"),
           "--config", cfg_path, "--traffic", mix_path,
           "--seed", str(args.seed), "--run-dir", run_dir,
           "--chips", str(cell["chips"])]
    if args.control:
        cmd.append("--control")
    if args.allow_cpu:
        cmd.append("--allow-cpu")
    child = subprocess.Popen(cmd, cwd=ROOT)
    try:
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
        result["run"]["u8"] = final.get("u8")
        result["run"]["about"] = {
            key: config[key] for key in ("rows", "dim", "k")}
        if "breakdown" in result:
            steps = 'knn_dist_tile_steps_total{path="u8"}'
            result["breakdown"].update(
                scopes=scopes, phases=final.get("phases"),
                memory=final.get("memory"),
                # what the two ``u8_scan_*`` readers divide, by name
                scan_u8={"scope": "knn.scan_u8",
                         "device_s": (final.get("u8") or {}).get("scan_s"),
                         "counter": steps,
                         "steps": (result["run"].get(
                             "traced_metrics_delta") or {}).get(steps)})
    return result
