"""Driver of the inner-product serving cells: ``drivers/serve.py``'s parent —
its ``drive``, and through it ``wait_for`` and ``traced_span``, imported and
not copied, as ``drivers/serve_cos.py`` does — around
``serve_launcher_ip.py``, the child whose reference is the inner-product
one. A traced run also hands the per-layer readers ``run["scopes"]`` (own
device seconds in the traced span by the program's innermost ``knn.*``
scope, as ``{scope: seconds}``; None where the trace names none) and puts
the same, largest first, into the line's ``breakdown.scopes``. This parent
never imports jax.
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
    run_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg_path = os.path.join(run_dir, "config.json")
    mix_path = os.path.join(run_dir, "traffic.json")
    for path, doc in ((cfg_path, cell["config"]), (mix_path, cell["traffic"])):
        with open(path, "w") as f:
            json.dump(doc, f)
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher_ip.py"),
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
            scopes = json.load(f).get("scopes")
        result["run"]["scopes"] = dict(scopes) if scopes else None
        if scopes and "breakdown" in result:
            result["breakdown"]["scopes"] = scopes
    return result
