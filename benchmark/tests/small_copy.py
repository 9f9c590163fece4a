"""A temporary copy of the benchmark at a size a CPU test can hold: the same
files, with the row counts and tiles of the configurations cut (never a
width) and shorter traffic. The tests drive ``run.py`` in the copy."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def edit_json(path: str, change) -> None:
    with open(path) as f:
        doc = json.load(f)
    change(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def make(tmp: str) -> str:
    """Copy BENCHMARK.json and benchmark/ into ``tmp`` and cut the sizes.
    Returns the root of the copy."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    shutil.copytree(
        BENCH, os.path.join(tmp, "benchmark"),
        ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"),
    )
    b = os.path.join(tmp, "benchmark")

    def mnist(c):
        c["rows"] = 8192
        c["knn"].update(query_tile=512, corpus_tile=1024)

    def bigann(c):
        c["rows"] = 16384
        c["knn"].update(corpus_tile=2048)

    edit_json(os.path.join(b, "configs", "mnist8m-784-l2.json"), mnist)
    edit_json(os.path.join(b, "configs", "bigann10m-128-l2.json"), bigann)
    edit_json(os.path.join(b, "traffic", "allknn-sweep.json"),
              lambda t: t.update(slice_rows=512, trace_seconds=0.5))
    edit_json(os.path.join(b, "traffic", "small-steady.json"),
              lambda t: t.update(trace_seconds=0.5, drain_s=3.0,
                                 connections=8, warm_sizes=[64, 128, 256]))
    edit_json(os.path.join(b, "traffic", "bulk-saturated.json"),
              lambda t: t.update(
                  trace_seconds=0.5, lead_in_s=0.5, warm_sizes=[256],
                  rows_per_request={"law": "fixed", "rows": 256}))
    edit_json(os.path.join(b, "configs", "bigann10m-128-l2.json"),
              lambda c: c["slo"].update(max_batch_rows=256))
    return tmp


def run_cell(root: str, workload: str, *extra, seconds: float = 2.0,
             seed: int = 2**31 + 11, trace: int = 0, timeout: float = 600):
    """Run one cell of the copy on the CPU; returns (exit code, the last
    stdout line parsed or None, all of stdout)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace),
         "--allow-cpu", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    last = None
    if p.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return p.returncode, last, p.stdout + p.stderr
