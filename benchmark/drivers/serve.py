"""Driver of the serving cells. This parent never imports jax: it starts
one child that holds the chip (``serve_launcher.py``), runs the generator
(``loadgen.py``) against it, reads ``/metrics`` before and after the window,
signals the child to trace a few seconds of the window in a ``--trace 1``
run, compares the window's answers for the probe rows with the reference the
child computed during set-up, and stops the child with SIGTERM, whose exit
code has to be 0.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def wait_for(path: str, child, timeout_s: float):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        if child.poll() is not None:
            return None
        time.sleep(0.02)
    return None


def traced_span(child, run_dir: str, url: str, wait_s: float,
                trace_s: float, out: dict) -> None:
    """In a thread: start the child's profiler ``wait_s`` into the window,
    stop it ``trace_s`` later, with a ``/metrics`` read at each end."""
    from benchmark import loadgen

    time.sleep(wait_s)
    child.send_signal(signal.SIGUSR1)
    if wait_for(os.path.join(run_dir, "trace_on.json"), child, 30) is None:
        return
    before = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
    time.sleep(trace_s)
    after = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
    child.send_signal(signal.SIGUSR2)
    wait_for(os.path.join(run_dir, "trace_off.json"), child, 120)
    out["delta"] = loadgen.metrics_delta(before, after)


def run(cell: dict, args, t_start: float):
    if "jax" in sys.modules:
        raise RuntimeError("the serving parent must stay off jax: the "
                           "child holds the chip")
    from benchmark import harness

    config, mix = cell["config"], cell["traffic"]
    run_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg_path = os.path.join(run_dir, "config.json")
    mix_path = os.path.join(run_dir, "traffic.json")
    for path, doc in ((cfg_path, config), (mix_path, mix)):
        with open(path, "w") as f:
            json.dump(doc, f)
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher.py"),
           "--config", cfg_path, "--traffic", mix_path,
           "--seed", str(args.seed), "--run-dir", run_dir,
           "--chips", str(cell["chips"])]
    if args.control:
        cmd.append("--control")
    if args.allow_cpu:
        cmd.append("--allow-cpu")
    child = subprocess.Popen(cmd, cwd=ROOT)
    try:
        return drive(cell, args, t_start, child, run_dir)
    finally:
        if child.poll() is None:
            child.terminate()
            try:
                child.wait(60)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()


def drive(cell, args, t_start, child, run_dir):
    from benchmark import compare, harness, loadgen
    from benchmark.harness import say

    config, mix = cell["config"], cell["traffic"]
    k = config["k"]
    ready = wait_for(os.path.join(run_dir, "ready.json"), child, 1100)
    if ready is None:
        print("error: the serving child did not come up "
              f"(exit code {child.poll()})", file=sys.stderr, flush=True)
        return None
    url, device = ready["url"], ready["device"]
    peaks = harness.peaks_for(device["kind"], args.allow_cpu)
    pool = harness.query_pool(config, args.seed, int(mix["query_pool_rows"]))
    probe_lo = loadgen.probe_block(args.seed, pool.shape[0])
    timeout_s = float(config["request_timeout_s"])

    def window(mix: dict) -> dict:
        """One window of the mix against the running server."""
        log = loadgen.Log(probe_lo, pool.shape[0], k)
        traced: dict = {}
        tracer = None
        if args.trace:
            tracer = threading.Thread(
                target=traced_span, daemon=True,
                args=(child, run_dir, url,
                      float(mix.get("lead_in_s", 0.0)) + 0.2 * args.seconds,
                      float(mix["trace_seconds"]), traced))
        before = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
        # what the user waited for before the first timed request, less
        # the reference (the check's cost, not the system's) and the
        # runtime's hand-over of the chip (the machine's, not the program's)
        setup_s = (time.time() - t_start - ready["ref_s"]
                   - ready["chip_wait_s"])
        if tracer:
            tracer.start()
        if mix["loop"] == "open":
            sched = loadgen.open_schedule(mix, args.seed, args.seconds,
                                          pool.shape[0], probe_lo)
            span = loadgen.run_open(url, mix, sched, pool, log,
                                    args.seconds, timeout_s)
            numbers = loadgen.reduce_open(log, span)
        else:
            span = loadgen.run_closed(url, mix, pool, log, args.seconds,
                                      timeout_s)
            setup_s += float(mix["lead_in_s"])  # the lead-in is warm-up
            numbers = loadgen.reduce_closed(log, span)
        after = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
        if tracer:
            tracer.join(200)
        late, lat = numbers.pop("late_s"), numbers.pop("latency_s")
        say("window " + json.dumps({**numbers, "rate": mix.get(
            "rate_requests_per_s"), "setup_s": setup_s}))
        return {"numbers": numbers, "late": late, "lat": lat, "log": log,
                "delta": loadgen.metrics_delta(before, after),
                "traced": traced.get("delta"), "setup_s": setup_s}

    last = window(mix)
    numbers, log, window_delta = last["numbers"], last["log"], last["delta"]
    child.send_signal(signal.SIGTERM)
    try:
        rc = child.wait(300)
    except subprocess.TimeoutExpired:
        rc = None
    final = None
    if rc == 0:
        with open(os.path.join(run_dir, "final.json")) as f:
            final = json.load(f)
    if final is None:
        print(f"error: the serving child exited with {rc}, not 0",
              file=sys.stderr, flush=True)
        return None
    device = final["device"]

    # the check: every answer of the window for a row of the probe block
    ref = np.load(os.path.join(run_dir, "probe_ref.npz"))
    rel = [p[0] - probe_lo for p in log.probe]
    if rel:
        verdict = compare.compare_answers(
            np.stack([p[1] for p in log.probe]),
            np.stack([p[2] for p in log.probe]),
            ref["ids"][rel], ref["dists"][rel], config["limits"])
    else:
        verdict = {"ok": False, "numbers": {
            "probe_answers": [0, 1, False]}}
    compiled = sum(v for name, v in window_delta.items()
                   if name.startswith("serve_executables_compiled_total"))
    verdict["numbers"]["compiled_in_window"] = [compiled, 0, compiled == 0]
    verdict["numbers"]["answers_misshapen_or_failed"] = [
        numbers["failed"], 0, numbers["failed"] == 0]
    compare.say(verdict["numbers"], info=verdict.get("info"))
    say(f"compared {len(rel)} answers of the window for probe rows "
        f"{probe_lo}..{probe_lo + loadgen.PROBE_BLOCK - 1}")
    correct = all(v[2] for v in verdict["numbers"].values())

    result = {
        "correct": bool(correct),
        "attempted": int(numbers["attempted"]),
        "failed": int(numbers["failed"]),
        "metrics": harness.end_to_end(
            cell, {**numbers, "setup_s": last["setup_s"]}),
        "device": device,
    }
    if args.trace:
        delta = last["traced"] or {}
        harness.add_trace(
            result, cell, final["trace"], peaks,
            q_rows=delta.get("serve_queries_total", 0.0),
            batches=delta.get("serve_batches_total", 0.0),
            traced_metrics_delta=delta or None,
            window_metrics_delta=window_delta,
            loadgen={"late_s": last["late"], "latency_s": last["lat"]})
    return result
