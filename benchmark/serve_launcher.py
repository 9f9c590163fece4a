"""The child that holds the chip in a serving cell.

It makes the corpus on the device from the seed, computes the probe block's
reference answers while nothing of the program is on the device yet, then
builds exactly what ``mpi_knn_tpu/frontend/cli.py serve_main`` builds —
``build_index`` -> ``ServeSession(index, resilience=ResiliencePolicy())`` ->
``Frontend(session, SLOPolicy(...))`` -> ``FrontendHTTPServer`` — warms only
the buckets the mix can reach, and writes the ready file. Two departures
from ``mpi-knn serve``: the corpus is made on the device (its ``--data``
forms all make the array on the host), and the index's centring mean is
then moved to the host, where an index built from a host array has it.

SIGUSR1 / SIGUSR2 from the parent start and stop ``jax.profiler`` (only the
process that holds the chip can trace it); SIGTERM stops the server, reduces
the trace, writes ``final.json`` and exits 0.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def write_json(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)  # a reader sees nothing or the whole file


def main(argv=None) -> int:
    t_launch = time.time()
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--run-dir", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--control", action="store_true")
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # first

    import numpy as np

    from benchmark import harness, loadgen, reference, trace
    from benchmark.harness import say

    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    device, chip_wait_s = harness.find_chip(args.chips, args.allow_cpu)
    harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"launcher: compile cache {harness.compile_cache()}")

    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession, build_index

    rows, dim, k = config["rows"], config["dim"], config["k"]
    gen = harness.datagen_for(config)
    X = gen.device_corpus(args.seed, rows, dim, config["data"])
    X.block_until_ready()
    say(f"launcher: corpus {X.shape} in {time.time() - t_launch:.2f}s")

    # the reference, before any state of the program is on the device
    t_ref = time.perf_counter()
    pool = harness.query_pool(config, args.seed, int(mix["query_pool_rows"]))
    probe_lo = loadgen.probe_block(args.seed, pool.shape[0])
    probe = pool[probe_lo:probe_lo + loadgen.PROBE_BLOCK]
    ref_d, ref_i = reference.exact_knn(
        X, probe, k, exclude_zero=config["exclude_zero"])
    np.savez(os.path.join(args.run_dir, "probe_ref.npz"),
             dists=ref_d, ids=ref_i, probe_lo=probe_lo)
    ref_s = time.perf_counter() - t_ref
    say(f"launcher: reference for {len(probe)} probe rows in {ref_s:.2f}s")

    cfg = harness.knn_config(config, args.control)
    slo = config["slo"]
    index = build_index(X, cfg)
    del X  # the launcher drops its array; the index holds the tiles
    if index.mu is not None:
        # an index that `mpi-knn serve` builds from its host array holds
        # the centring mean on the host and centres each batch in numpy. An
        # index built from a device array centres and pads every batch on
        # the device, one tiny program for each distinct row count, which
        # compiles inside the window. Hand it the mean as serve has it.
        index.mu = np.asarray(index.mu, dtype=np.float64)
    session = ServeSession(index, resilience=ResiliencePolicy())
    frontend = Frontend(session, SLOPolicy(
        max_batch_rows=slo["max_batch_rows"],
        max_wait_s=slo["max_wait_ms"] / 1e3,
        max_queue_rows=slo["max_queue_rows"],
    ))
    frontend.start(warm_sizes=list(mix["warm_sizes"]), background=False)
    server = FrontendHTTPServer(
        frontend, host="127.0.0.1", port=0,
        request_timeout_s=float(config["request_timeout_s"]), quiet=True,
    ).start()
    say(f"launcher: warm {session.warm_report}")

    events = {name: threading.Event() for name in ("start", "stop", "term")}
    signal.signal(signal.SIGUSR1, lambda *_: events["start"].set())
    signal.signal(signal.SIGUSR2, lambda *_: events["stop"].set())
    signal.signal(signal.SIGTERM, lambda *_: events["term"].set())
    signal.signal(signal.SIGINT, lambda *_: events["term"].set())
    write_json(os.path.join(args.run_dir, "ready.json"), {
        "url": server.url, "pid": os.getpid(), "ref_s": ref_s,
        "chip_wait_s": chip_wait_s, "device": device, "launch_to_ready_s": time.time() - t_launch,
    })

    span = trace.TracedSpan(os.path.join(args.run_dir, "trace"))
    while not events["term"].is_set():
        if events["start"].is_set() and not span.running:
            events["start"].clear()
            span.start()
            write_json(os.path.join(args.run_dir, "trace_on.json"),
                       {"at": time.time()})
        if events["stop"].is_set() and span.running:
            events["stop"].clear()
            span.stop()
            write_json(os.path.join(args.run_dir, "trace_off.json"),
                       {"at": time.time()})
        time.sleep(0.01)
    span.stop()
    server.stop()
    frontend.stop()
    stats = frontend.stats()
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    summary = None
    if os.path.isdir(span.log_dir):
        summary = span.summary(allow_empty=args.allow_cpu)
    write_json(os.path.join(args.run_dir, "final.json"), {
        "device": device, "trace": summary,
        "queries_served": stats.get("queries_served"),
        "batches_retired": stats.get("batches_retired"),
        "rejected": stats.get("rejected"), "rung": stats.get("rung"),
    })
    say(f"launcher: shutdown after {stats.get('queries_served')} rows in "
        f"{stats.get('batches_retired')} batches")
    return 0


if __name__ == "__main__":
    sys.exit(main())
