"""The child that holds the chip in a filtered serving cell:
``serve_launcher.py``'s shape — corpus on the device from the seed, the
probe block's reference while nothing of the program is on the device,
``build_index`` -> ``ServeSession(index, resilience=ResiliencePolicy())`` ->
``Frontend`` -> ``FrontendHTTPServer`` as ``mpi-knn serve --tags`` builds
them, the ready file, the profiler on SIGUSR1 / SIGUSR2, ``final.json`` on
SIGTERM — with what a tagged corpus adds:

- the bags are made on the host (``datagen/clustered_u8_tags.py``) on a
  thread beside the corpus, handed to ``build_index(..., tags=)``, and the
  reference is ``reference_filter.exact_knn_filtered`` for the probe
  block's rows WITH their tags;
- the first thing it does is build the program's configuration: a program
  that has no ``max_query_tags`` ends the run here, at once;
- ``final.json`` carries the kernels' scopes of the traced span
  (``serve_launcher_cos.traced_scopes``) and the index's summary of its
  tags.

(Folding the launchers into one that reads its corpus, reference and build
from the configuration is a ``benchmark`` issue's: this PR may edit no file
that is there.)
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


def main(argv=None) -> int:
    t_launch = time.time()
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--run-dir", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # first

    import numpy as np

    from benchmark import harness, loadgen, reference_filter, trace
    from benchmark.harness import say
    from benchmark.serve_launcher import write_json
    from benchmark.serve_launcher_cos import traced_scopes

    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    cfg = harness.knn_config(config, False)  # a parent of this PR: TypeError
    rows, dim, k = config["rows"], config["dim"], config["k"]
    gen = harness.datagen_for(config)
    made: dict = {}
    bagger = threading.Thread(
        target=lambda: made.update(zip(
            ("which", "indptr", "indices", "matrix"),
            gen.bags(rows, config["data"]))),
        name="launcher-bags", daemon=True)
    bagger.start()
    device, chip_wait_s = harness.find_chip(args.chips, args.allow_cpu)
    harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"launcher: compile cache {harness.compile_cache()}")

    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession, build_index

    bagger.join()
    if "which" not in made:
        raise SystemExit("error: the bags' generator failed")
    say(f"launcher: {len(made['indices'])} tags on {rows} rows in "
        f"{time.time() - t_launch:.2f}s")
    X = gen.device_corpus(args.seed, rows, dim, config["data"],
                          made["which"])
    X.block_until_ready()
    say(f"launcher: corpus {X.shape} in {time.time() - t_launch:.2f}s")

    # the reference, before any state of the program is on the device
    t_ref = time.perf_counter()
    pool, pool_tags = gen.query_pool(
        args.seed, int(mix["query_pool_rows"]), config["data"], dim,
        made["which"], made["indptr"], made["indices"])
    probe_lo = loadgen.probe_block(args.seed, pool.shape[0])
    probe = slice(probe_lo, probe_lo + loadgen.PROBE_BLOCK)
    ref_d, ref_i = reference_filter.exact_knn_filtered(
        X, made.pop("matrix"), pool[probe], pool_tags[probe], k,
        exclude_zero=config["exclude_zero"])
    np.savez(os.path.join(args.run_dir, "probe_ref.npz"),
             dists=ref_d, ids=ref_i, probe_lo=probe_lo)
    ref_s = time.perf_counter() - t_ref
    say(f"launcher: reference for {ref_d.shape[0]} probe rows in "
        f"{ref_s:.2f}s; rows with fewer than {k} matches: "
        f"{int((ref_i[:, -1] < 0).sum())}")

    slo = config["slo"]
    index = build_index(X, cfg, tags=(made["indptr"], made["indices"]))
    del X  # the launcher drops its array; the index holds the tiles
    made.clear()
    if index.mu is not None:
        # as serve_launcher.py: the mean on the host, where `mpi-knn serve`
        # has it, so that a batch is centred in numpy
        index.mu = np.asarray(index.mu, dtype=np.float64)
    say(f"launcher: index with tags {index.tags.summary()} in "
        f"{time.time() - t_launch:.2f}s")
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
        "chip_wait_s": chip_wait_s, "device": device,
        "launch_to_ready_s": time.time() - t_launch,
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
        "scopes": traced_scopes(args.run_dir),
        "tags": index.tags.summary(),
        "queries_served": stats.get("queries_served"),
        "batches_retired": stats.get("batches_retired"),
        "rejected": stats.get("rejected"), "rung": stats.get("rung"),
    })
    say(f"launcher: shutdown after {stats.get('queries_served')} rows in "
        f"{stats.get('batches_retired')} batches")
    return 0


if __name__ == "__main__":
    sys.exit(main())
