"""The child that holds the chip in a range-search serving cell.

As ``serve_launcher_u8.py``: it never holds the corpus. The generator is a
function of (seed, block) (``datagen/dupgroups_u8_blocks.py``) and is run
TWICE — once for the plain reference, which streams the blocks past the
probe rows and keeps every corpus row within the radius of each
(``reference_range.py``; its time, generator included, is taken out of
``setup_s`` as every launcher's reference is), once for the build, which
takes its rows in blocks (``mpi_knn_tpu.serve.build_index_blocks``). Then
exactly what ``mpi-knn serve --dtype uint8 --range-cap N`` builds:
``ServeSession(index, resilience=ResiliencePolicy())`` -> ``Frontend`` ->
``FrontendHTTPServer``, warmed at the buckets the mix can reach — the k-NN
program and the range program of each.

Before it asks for the chip it asks the PROGRAM: a checkout whose
``KNNConfig`` knows no ``range_cap``, or that has no range programs (the
parent commit), ends the run at once with code 4 and nothing allocated.

``--control`` hands the build every row with its top bit lost
(``x & 127``), the byte cell's control: the reference keeps the rows as
they are, so the comparison must read ``correct`` false.

SIGUSR1 / SIGUSR2 start and stop ``jax.profiler``; SIGTERM stops the
server, reduces the trace, writes ``final.json`` and exits 0. ``final.json``
carries ``"scopes"`` (own device seconds in the traced span by innermost
``knn.*`` scope), ``"range"`` (``scan_s`` / ``overflow_s`` / ``finish_s``:
those under ``knn.scan_range`` / ``knn.range_overflow`` /
``knn.range_finish``, whatever is nested in them), ``"memory"`` and
``"phases"``.
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

SCOPES = {"scan_s": "knn.scan_range", "overflow_s": "knn.range_overflow",
          "finish_s": "knn.range_finish"}


def traced_scopes(run_dir: str):
    """``(scopes, under)`` of the run's trace: own device seconds inside
    the traced span by innermost ``knn.*`` scope, largest first, and
    ``{"scan_s", "overflow_s", "finish_s"}``, those of every operation
    under each of :data:`SCOPES`; ``(None, None)`` where there is no trace,
    no window annotation or no scope name in it."""
    from benchmark import trace
    from benchmark.serve_launcher_u8 import scope_key
    from mpi_knn_tpu.obs.xplane import parse_xplane

    xplane = trace.newest_xplane(os.path.join(run_dir, "trace"))
    if xplane is None:
        return None, None
    spans = [(s, s + d) for n, s, d in trace.read_xplane(xplane)["host"]
             if n == trace.WINDOW_ANNOTATION]
    if not spans:
        return None, None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    events = []
    for e in parse_xplane(xplane):
        if e["line"] != trace.OPS_LINE or "scope" not in e:
            continue
        s, d = e["start_ps"] * 1e-12, e["dur_ps"] * 1e-12
        if s < hi and s + d > lo:
            within = tuple(name in e["scope"] for name in SCOPES.values())
            events.append(((scope_key(e["scope"]), within),
                           max(s, lo), min(s + d, hi) - max(s, lo)))
    if not events:
        return None, None
    own = trace.self_times(events)
    scopes: dict = {}
    under = dict.fromkeys(SCOPES, 0.0)
    for (key, within), sec in own.items():
        scopes[key] = scopes.get(key, 0.0) + sec
        for name, inside in zip(SCOPES, within):
            if inside:
                under[name] += sec
    return ([[k, v] for k, v in sorted(scopes.items(),
                                       key=lambda kv: -kv[1])], under)


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

    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    from benchmark import harness
    from benchmark.harness import say

    # the program first, the chip after: nothing is allocated for a
    # checkout that cannot answer a radius
    try:
        from mpi_knn_tpu.serve import build_index_blocks

        cfg = harness.knn_config(config, False)
        if not getattr(cfg, "range_cap", 0):
            raise ValueError("no range_cap in this KNNConfig")
        from mpi_knn_tpu.backends import range_scan  # noqa: F401
    except (ImportError, ValueError, TypeError) as e:
        print(f"error: this checkout cannot run the cell: {e}",
              file=sys.stderr, flush=True)
        return 4

    import numpy as np

    from benchmark import loadgen, reference_range, trace
    from benchmark.serve_launcher_u8 import memory_now

    device, chip_wait_s = harness.find_chip(args.chips, args.allow_cpu)
    harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"launcher: compile cache {harness.compile_cache()}")

    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession

    rows, dim = config["rows"], config["dim"]
    gen = harness.datagen_for(config)
    sizes = gen.block_rows_of(rows, config["data"])

    def block_of(b: int):
        return gen.device_block(args.seed, b, sizes[b], dim, config["data"])

    phases = {"before_reference_s": time.time() - t_launch}

    # the reference, before any state of the program is on the device: the
    # blocks streamed past the probe rows, the corpus never whole
    t = time.perf_counter()
    pool, stratum = gen.query_pool(
        args.seed, int(mix["query_pool_rows"]), config, mix)
    probe_lo = loadgen.probe_block(args.seed, pool.shape[0])
    probe = pool[probe_lo:probe_lo + loadgen.PROBE_BLOCK]
    lims, ref_d, ref_i = reference_range.range_search_blocks(
        block_of, sizes, probe, float(mix["radius"]),
        exclude_zero=config["exclude_zero"])
    # ``dists`` / ``ids``: what ``drivers/serve.py drive`` indexes by probe
    # row — here the row itself; the lists are under the other names
    np.savez(os.path.join(args.run_dir, "probe_ref.npz"),
             dists=np.arange(len(probe), dtype=np.float64),
             ids=np.arange(len(probe), dtype=np.int64), probe_lo=probe_lo,
             lims=lims, flat_dists=ref_d, flat_ids=ref_i,
             stratum=stratum[probe_lo:probe_lo + loadgen.PROBE_BLOCK])
    ref_s = phases["generator_and_reference_s"] = time.perf_counter() - t
    per_row = np.diff(lims)
    say(f"launcher: reference for {len(probe)} probe rows over "
        f"{len(sizes)} blocks in {ref_s:.2f}s (generator included): "
        f"{int(lims[-1])} pairs, the longest row {int(per_row.max())}, "
        f"{int((per_row == 0).sum())} rows empty")

    lost = int(config["control"]["lost_bit"]) if args.control else 0
    if lost:
        say(f"control: every row reaches the build without bit {lost}")

    def build_block(b: int):
        if b >= len(sizes):
            return None
        blk = block_of(b)
        return blk & np.uint8(255 - lost) if lost else blk

    t = time.perf_counter()
    index = build_index_blocks((rows, dim), build_block, cfg)
    index.tiles.block_until_ready()
    phases["generator_and_block_build_s"] = time.perf_counter() - t
    built = memory_now()
    say(f"launcher: {rows} x {dim} in {len(sizes)} blocks built in "
        f"{phases['generator_and_block_build_s']:.2f}s; device bytes in "
        f"use {built['in_use']}, peak {built['peak']}")

    slo = config["slo"]
    t = time.perf_counter()
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
    phases["warm_s"] = time.perf_counter() - t
    say(f"launcher: warm {session.warm_report} in {phases['warm_s']:.2f}s")

    events = {name: threading.Event() for name in ("start", "stop", "term")}
    signal.signal(signal.SIGUSR1, lambda *_: events["start"].set())
    signal.signal(signal.SIGUSR2, lambda *_: events["stop"].set())
    signal.signal(signal.SIGTERM, lambda *_: events["term"].set())
    signal.signal(signal.SIGINT, lambda *_: events["term"].set())
    from benchmark.serve_launcher import write_json

    write_json(os.path.join(args.run_dir, "ready.json"), {
        "url": server.url, "pid": os.getpid(), "ref_s": ref_s,
        "chip_wait_s": chip_wait_s, "device": device,
        "launch_to_ready_s": time.time() - t_launch,
    })
    say("launcher: phases " + json.dumps(phases))

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
    summary, scopes, under = None, None, None
    if os.path.isdir(span.log_dir):
        summary = span.summary(allow_empty=args.allow_cpu)
        scopes, under = traced_scopes(args.run_dir)
    write_json(os.path.join(args.run_dir, "final.json"), {
        "device": device, "trace": summary,
        "queries_served": stats.get("queries_served"),
        "batches_retired": stats.get("batches_retired"),
        "rejected": stats.get("rejected"), "rung": stats.get("rung"),
        "scopes": scopes, "phases": phases,
        "memory": {"after_build": built, "at_end": memory_now()},
        "range": under,
    })
    say(f"launcher: shutdown after {stats.get('queries_served')} rows in "
        f"{stats.get('batches_retired')} batches")
    return 0


if __name__ == "__main__":
    sys.exit(main())
