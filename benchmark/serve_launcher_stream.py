"""The child that holds the chip in a streaming cell (a corpus that changes
while it is served). ``serve_launcher.py``'s sequence — corpus on the
device from the seed, the reference while nothing of the program is on the
device, then exactly what ``mpi_knn_tpu/frontend/cli.py serve_main``
builds, warmed, behind the program's own HTTP server; SIGUSR1 / SIGUSR2
trace, SIGTERM writes ``final.json`` and exits 0 — with these differences:

- the data is the runbook's (``runbook.py``, ``datagen/
  clustered_f32_stream.py``): an id space ordered by cluster;
- the reference is a model of the index (``reference_stream.py``) walked
  through the runbook's steps: the probe block's exact live neighbours at
  the search steps of the traffic file's ``checkpoints``, and at the last
  one once more with the deleted rows counted in ("had they stayed");
- the index is built with the configuration's ``bucket_headroom``, and the
  host mirror of its id plane is made here, during set-up, its seconds and
  bytes on a line of their own (``warm_mutation`` would make it a moment
  later; a write never should);
- a traced run hands on the kernels' scopes (``final.json`` ``"scopes"``,
  own device seconds by innermost ``knn.*`` scope), with everything under
  the scatter programs' ``knn.mutate/upsert`` / ``knn.mutate/delete``
  under those two names.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

MUTATE_SCOPE = re.compile(r"knn\.mutate/(upsert|delete)")


def write_json(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)  # a reader sees nothing or the whole file


def rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def probe_reference(model, book: dict, mix: dict, probe, k: int) -> dict:
    """Walk the model through the runbook to the last checkpoint; the
    arrays of ``probe_ref.npz``."""
    import numpy as np

    from benchmark import runbook

    warm, cps = int(mix["warm_cycles"]), sorted(mix["checkpoints"])
    deleted = np.zeros(model.ids, dtype=bool)
    out = {}
    for i, cycle in enumerate(book["cycles"][: warm + cps[-1]]):
        w = i - warm + 1  # the window's cycles count from 1
        for op in runbook.steps(cycle):
            if op["operation"] == "search" and w in cps:
                out[f"dists_{w}"], out[f"ids_{w}"] = model.exact_knn_live(
                    probe, k)
                if w == cps[-1]:
                    _, stayed = model.exact_knn_live(probe, k,
                                                     also_live=deleted)
                    out["touched_by_deletes"] = deleted[stayed].any(axis=1)
            model.apply(op)
            if op["operation"] == "delete":
                deleted[op["start"]:op["end"]] = True
    return out


def traced_scopes(run_dir: str):
    """``[[scope, seconds], ...]`` of the run's trace, largest first, or
    None (no trace, no window annotation, no scope names in it)."""
    from benchmark import harness, trace

    xplane = trace.newest_xplane(os.path.join(run_dir, "trace"))
    if xplane is None:
        return None
    spans = [(s, s + d) for n, s, d in trace.read_xplane(xplane)["host"]
             if n == trace.WINDOW_ANNOTATION]
    if not spans:
        return None
    ring = harness.load_by_path("drivers", "allknn_ring")
    innermost = ring.scope_key

    def scope_key(op_name: str) -> str:
        found = MUTATE_SCOPE.search(op_name)
        return found.group(0) if found else innermost(op_name)

    ring.scope_key = scope_key  # this module object is this call's own
    return ring.ring_scopes(xplane, min(s for s, _ in spans),
                            max(e for _, e in spans))


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

    from benchmark import harness, reference_stream, runbook, trace
    from benchmark.harness import say

    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    device, chip_wait_s = harness.find_chip(args.chips, args.allow_cpu)
    harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"launcher: compile cache {harness.compile_cache()}")

    import jax.numpy as jnp

    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.ivf.mutate import freelist_of
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession, build_index

    rows, dim, k = config["rows"], config["dim"], config["k"]
    spec = config["data"]
    gen = harness.datagen_for(config)
    book = runbook.plan(config, mix, args.seed)
    subs = gen.sub_centres(args.seed, spec, dim, book["cluster_of_block"])
    X = gen.device_corpus(args.seed, rows, dim, spec, subs)
    X.block_until_ready()
    say(f"launcher: corpus {X.shape} in {time.time() - t_launch:.2f}s")

    # the reference, before any state of the program is on the device
    t_ref = time.perf_counter()
    pool = gen.query_rows(args.seed, spec,
                          runbook.pool_targets(book, mix, args.seed), subs)
    probe_rows = int(mix["probe_rows"])
    probe_lo = runbook.probe_block(args.seed, pool.shape[0], probe_rows)
    to_ref = int(mix["warm_cycles"]) + max(mix["checkpoints"])
    added = np.concatenate([
        gen.host_block(args.seed, spec, b, subs[b])
        for cycle in book["cycles"][:to_ref]
        for b in runbook.blocks_of(cycle["insert"], book["block_rows"])])
    model = reference_stream.StreamModel(
        [(0, X), (rows, jnp.asarray(added))], live_rows=rows)
    ref = probe_reference(model, book, mix,
                          pool[probe_lo:probe_lo + probe_rows], k)
    np.savez(os.path.join(args.run_dir, "probe_ref.npz"),
             probe_lo=probe_lo, **ref)
    del model, added
    ref_s = time.perf_counter() - t_ref
    say(f"launcher: reference for {probe_rows} probe rows at the search "
        f"steps of cycles {sorted(mix['checkpoints'])} (and once more with "
        f"the deleted rows) in {ref_s:.2f}s")

    cfg = harness.knn_config(config, args.control)
    slo = config["slo"]
    index = build_index(X, cfg)
    del X  # the launcher drops its array; the index holds the tiles
    if index.mu is not None:
        # as serve_launcher.py: the centring mean on the host, where an
        # index that `mpi-knn serve` builds from its host array has it
        index.mu = np.asarray(index.mu, dtype=np.float64)
    rss0, t_fl = rss_bytes(), time.perf_counter()
    mirror = freelist_of(index)
    say(f"launcher: host mirror of {index.tile_ids.size} slots "
        f"({mirror.live} live) in {time.perf_counter() - t_fl:.3f}s, "
        f"{getattr(mirror, 'nbytes', None)} bytes of arrays, resident set "
        f"+{rss_bytes() - rss0} bytes")
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
    summary = scopes = None
    if os.path.isdir(span.log_dir):
        summary = span.summary(allow_empty=args.allow_cpu)
        scopes = traced_scopes(args.run_dir)
    write_json(os.path.join(args.run_dir, "final.json"), {
        "device": device, "trace": summary, "scopes": scopes,
        "queries_served": stats.get("queries_served"),
        "batches_retired": stats.get("batches_retired"),
        "rejected": stats.get("rejected"), "rung": stats.get("rung"),
    })
    say(f"launcher: shutdown after {stats.get('queries_served')} rows in "
        f"{stats.get('batches_retired')} batches")
    return 0


if __name__ == "__main__":
    sys.exit(main())
