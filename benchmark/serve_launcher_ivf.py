"""The child that holds the chip in a clustered (IVF-Flat) serving cell:
``serve_launcher.py``'s shape — corpus on the device from the seed, the
probe block's reference while nothing of the program is on the device,
the index -> ``ServeSession(index, resilience=ResiliencePolicy())`` ->
``Frontend`` -> ``FrontendHTTPServer`` as ``mpi-knn serve --partitions``
builds them, the ready file, the profiler on SIGUSR1 / SIGUSR2,
``final.json`` on SIGTERM — with what a clustered index changes:

- the index is ``mpi_knn_tpu.ivf.build_ivf_index`` from the DEVICE array
  (mean, training sample, assignment and fill on the device), and the first
  thing this file does is build the program's configuration: a program
  without ``kmeans_sample`` ends the run here, at once;
- the reference is ``reference.exact_knn`` as it stands, asked for the
  probe block's ``reference_k`` (100) nearest, not k: the comparison
  (``drivers/serve_ivf.py``) looks every returned id up among them;
- ``final.json`` carries the kernels' scopes of the traced span (with
  ``score`` and ``gather`` told apart under ``knn.ivf``), the index's
  summary (``bucket_cap``, fill, resident bytes, the build's phases from
  the program's own spans) and what the ladder did (``degradations``: the
  configuration's guarantee is that every batch runs at the stated
  ``nprobe``);
- ``--control NAME`` switches on one of the configuration's controls
  (``nprobe``: the ``control.nprobe`` of the file in place of the stated
  one; ``rerank_default``: the exact finish's operands rounded to
  bfloat16's precision before anything is traced, the nearest precision below that
  this program can compute in — the finish is a matrix-vector product a
  row, which the v5e compiler lowers to a float32 multiply-reduce with no
  MXU pass for a ``Precision`` attribute to thin out; the CPU shows it
  too, so ``benchmark/tests/test_ivf_cell.py`` plants it as a fault) or
  plants a fault for those tests (``empty_partition``: the partition that
  holds most of the probe block's true neighbours emptied after the
  build; ``duplicate_row``: a probe row's nearest neighbour stored a
  second time; ``degraded_batch``: one rung of the ladder shed before the
  window).

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
CONTROLS = ("nprobe", "rerank_default")
PLANTS = ("empty_partition", "duplicate_row", "degraded_batch")
BUILD_SPANS = ("index-build", "ivf-train", "ivf-assign", "ivf-fill")


def round_the_finish() -> None:
    """The exact finish on operands rounded to bfloat16's precision,
    before anything is traced (control ``rerank_default``). By
    ``lax.reduce_precision``, an operation the compiler keeps where it
    stands: a ``float32 -> bfloat16 -> float32`` round trip of the query
    rows read what the sound program reads on the chip (three seeds; the
    CPU reads 3e-3 — the cause was not pinned down), and one of the
    gathered rows is hoisted out of the probe loop as a bfloat16 copy of
    the whole store (17.5 of 15.75 GiB at the cell's size)."""
    import jax

    from mpi_knn_tpu.ivf import search

    exact = search.rerank_exact_topk

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rounded(q_x, q_ids, q_sq, rows, *rest, **kw):
        return exact(bf16(q_x), q_ids, q_sq, bf16(rows), *rest, **kw)

    search.rerank_exact_topk = rounded


def plant_in_store(name: str, index, ref_ids) -> str:
    """A fault in the built store; says what it did."""
    import jax.numpy as jnp
    import numpy as np

    ids = np.asarray(index.bucket_ids)
    where = np.full(index.m, -1, np.int64)
    live = ids >= 0
    where[ids[live]] = np.nonzero(live)[0]  # row -> partition
    if name == "empty_partition":
        k = index.cfg.k
        p = int(np.bincount(where[ref_ids[:, :k]].reshape(-1),
                            minlength=index.partitions).argmax())
        index.bucket_ids = index.bucket_ids.at[p].set(-1)
        return f"partition {p} of {index.partitions} emptied"
    for g in ref_ids[:, 0]:  # duplicate_row
        p = int(where[g])
        free = np.nonzero(ids[p] < 0)[0]
        if free.size:
            s, at = int(free[0]), int(np.nonzero(ids[p] == g)[0][0])
            index.buckets = index.buckets.at[p, s].set(index.buckets[p, at])
            index.bucket_sqs = index.bucket_sqs.at[p, s].set(
                index.bucket_sqs[p, at])
            index.bucket_ids = index.bucket_ids.at[p, s].set(jnp.int32(g))
            return f"row {int(g)} stored twice in partition {p}"
    raise SystemExit("error: no probe neighbour's partition has a free slot")


def closed_spans(flight_path: str) -> list:
    """The closed spans of the program's flight record."""
    from mpi_knn_tpu.obs.spans import read_flight, reconstruct_spans

    spans, _ = reconstruct_spans(read_flight(flight_path))
    return [s for s in spans if s.get("dur_s") is not None]


def build_phases(spans: list) -> dict:
    """Seconds of the build's spans."""
    return {s["name"]: round(float(s["dur_s"]), 4) for s in spans
            if s.get("cat") == "index" and s["name"] in BUILD_SPANS}


def setup_spans(spans: list, most: int = 10) -> dict:
    """``{cat.name: [spans, seconds]}`` of the set-up's spans (the build
    and the warm-up), the longest first: where set-up time went."""
    by_name: dict = {}
    for s in spans:
        n, sec = by_name.get(f"{s['cat']}.{s['name']}", (0, 0.0))
        by_name[f"{s['cat']}.{s['name']}"] = (n + 1, sec + s["dur_s"])
    top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:most]
    return {k: [n, round(sec, 3)] for k, (n, sec) in top}


def ivf_scopes(run_dir: str):
    """``serve_launcher_cos.traced_scopes`` with ``knn.ivf``'s two
    sub-scopes kept apart (``allknn_ring.SUB_SCOPES`` names the sub-scopes
    it keeps; in this process it names these too)."""
    from benchmark import harness
    from benchmark.serve_launcher_cos import traced_scopes

    ring = harness.load_by_path("drivers", "allknn_ring")
    ring.SUB_SCOPES = (*ring.SUB_SCOPES, "score", "gather")
    load = harness.load_by_path
    harness.load_by_path = (
        lambda kind, name: ring if (kind, name) == ("drivers", "allknn_ring")
        else load(kind, name))
    try:
        return traced_scopes(run_dir)
    finally:
        harness.load_by_path = load


def main(argv=None) -> int:
    t_launch = time.time()
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--run-dir", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--control", choices=CONTROLS + PLANTS, default=None)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # first

    import numpy as np

    from benchmark import harness, loadgen, reference, trace
    from benchmark.harness import say
    from benchmark.serve_launcher import write_json

    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        mix = json.load(f)
    cfg = harness.knn_config(config, False)  # a parent of PR 41: TypeError
    if args.control == "nprobe":
        cfg = cfg.replace(nprobe=int(config["control"]["nprobe"]))
    device, chip_wait_s = harness.find_chip(args.chips, args.allow_cpu)
    harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"launcher: compile cache {harness.compile_cache()}")

    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.ivf import build_ivf_index
    from mpi_knn_tpu.obs import metrics as obs_metrics
    from mpi_knn_tpu.obs import spans as obs_spans
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession

    if args.control == "rerank_default":
        round_the_finish()
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
        X, probe, int(config["reference_k"]),
        exclude_zero=config["exclude_zero"])
    np.savez(os.path.join(args.run_dir, "probe_ref.npz"),
             dists=ref_d, ids=ref_i, probe_lo=probe_lo)
    ref_s = time.perf_counter() - t_ref
    say(f"launcher: reference ({ref_d.shape[1]} nearest) for {len(probe)} "
        f"probe rows in {ref_s:.2f}s")

    # the build's phases come from the program's own spans: a flight
    # record for the length of the set-up alone (the window is served, as
    # in the other cells, with none)
    flight = os.path.join(args.run_dir, "setup_flight.jsonl")
    obs_spans.set_recorder(obs_spans.FlightRecorder(flight))
    t_build = time.perf_counter()
    index = build_ivf_index(X, cfg)
    del X  # the launcher drops its array; the index holds the store
    build_s = time.perf_counter() - t_build
    about = {
        "rows": int(index.m), "dim": int(index.dim),
        "partitions": int(index.partitions), "nprobe": int(index.nprobe),
        "bucket_cap": int(index.bucket_cap),
        "fill_pct": 100.0 * index.m / (index.partitions * index.bucket_cap),
        "resident_bytes": int(
            index.nbytes_resident + index.bucket_ids.size * 4
            + index.bucket_sqs.size * 4 + index.centroids.size * 4),
        "build_s": round(build_s, 3),
        "phases_s": build_phases(closed_spans(flight)),
        "peak_bytes_after_build": harness.memory_peak_bytes(),
    }
    say(f"launcher: index {json.dumps(about)}")
    if args.control in ("empty_partition", "duplicate_row"):
        say("launcher: planted: " + plant_in_store(
            args.control, index, ref_i))
    session = ServeSession(index, resilience=ResiliencePolicy())
    slo = config["slo"]
    frontend = Frontend(session, SLOPolicy(
        max_batch_rows=slo["max_batch_rows"],
        max_wait_s=slo["max_wait_ms"] / 1e3,
        max_queue_rows=slo["max_queue_rows"],
    ))
    # the configuration's index is frozen (bucket_headroom 0, no writer in
    # the mix), and the compaction cell would hold the store twice
    frontend.start(warm_sizes=list(mix["warm_sizes"]), background=False,
                   warm_writes=False)
    obs_spans.set_recorder(None)
    say("launcher: set-up spans "
        + json.dumps(setup_spans(closed_spans(flight))))
    if args.control == "degraded_batch":
        say(f"launcher: planted: shed to {session.shed_rung(reason='planted')}")
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
    shed = obs_metrics.get_registry().counter(
        "serve_degradations_total",
        help="ladder rungs shed (deadline breach or queue overload)")
    write_json(os.path.join(args.run_dir, "final.json"), {
        "device": device, "trace": summary,
        "scopes": ivf_scopes(args.run_dir), "index": about,
        "degradations": int(shed.value), "rung": stats.get("rung"),
        "queries_served": stats.get("queries_served"),
        "batches_retired": stats.get("batches_retired"),
        "rejected": stats.get("rejected"),
    })
    say(f"launcher: shutdown after {stats.get('queries_served')} rows in "
        f"{stats.get('batches_retired')} batches, rung {stats.get('rung')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
