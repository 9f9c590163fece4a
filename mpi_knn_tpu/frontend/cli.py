"""``mpi-knn serve`` and ``mpi-knn loadgen`` — the network front end and
its load generator.

``serve`` builds a device-resident index over ``--data`` (the run
driver's corpus specs), wraps a :class:`~mpi_knn_tpu.serve.ServeSession`
in the coalescing front end, and listens on a loopback (or given) HTTP
port: ``POST /query`` (JSON or raw f32 rows, ``X-Tenant`` header),
``GET /metrics`` (Prometheus exposition), ``GET /healthz``. ``--port 0``
binds an ephemeral port; ``--ready-file`` writes the final URL once the
server is listening (the CI gate's rendezvous — parsing a log for a port
number is a race, a file appearing is not).

``loadgen`` drives a running server with open-loop multi-tenant load and
prints/writes the throughput-vs-p50/p99 rows (``frontend/loadgen.py``;
``--sweep`` runs several offered-QPS levels).

Usage error convention as everywhere: combinations the stack cannot
honor exit 2 loudly.

Examples::

    mpi-knn serve --data sift:100000 --k 10 --bucket 512 --port 8080
    mpi-knn serve --data synthetic:8192x64c10 --port 0 \
        --ready-file /tmp/knn.url --flight-record flight.jsonl
    mpi-knn loadgen --url http://127.0.0.1:8080 --tenants 8 \
        --qps 50 --requests 40 --rows 16 --report curve.json
    mpi-knn loadgen --url http://127.0.0.1:8080 --sweep 10,50,200
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time

import numpy as np

from mpi_knn_tpu.config import (
    BACKENDS,
    METRICS,
    PRECISION_POLICIES,
    KNNConfig,
)


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi-knn serve",
        description="multi-tenant HTTP serving front end: async request "
        "coalescing into the bucketed AOT executable cache, SLO-aware "
        "admission, queue-driven degradation ladder",
    )
    d = p.add_argument_group("data / index")
    d.add_argument("--data", default="mnist",
                   help="corpus spec (run-driver forms: 'mnist', 'digits', "
                   "'synthetic:MxDcC', 'sift:M', *.fvecs/bvecs, .mat)")
    d.add_argument("--limit", type=int, default=None)
    d.add_argument("--k", type=int, default=30)
    d.add_argument("--metric", choices=METRICS, default="l2",
                   help="distance of the served index, as `mpi-knn query "
                   "--metric` (cosine: the index keeps its rows' inverse "
                   "norms; ip: exact maximum inner product, answered as "
                   "the negated score ascending, no norms, nothing "
                   "centred, the dense serial layout on one device and "
                   "frozen; both refused loudly by the clustered layouts)")
    d.add_argument("--backend", choices=BACKENDS, default="auto")
    d.add_argument("--devices", type=int, default=None,
                   help="ring size for distributed backends")
    d.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16", "float64", "uint8"],
                   help="what the dense index rests as. uint8: whole-"
                   "number rows in [0, 255] one byte an element, lossless "
                   "(d + 8 resident bytes a row, 4 d + 8 as float32), "
                   "widened in every tile step: the float32 index's "
                   "answers. A .bvecs --data then reaches the build as "
                   "bytes, a block at a time; any other corpus is checked "
                   "on the device and refused with the first row that "
                   "holds a fraction or leaves the range, never rounded. "
                   "L2 on the serial layout of one device, frozen; "
                   "refused with --metric cosine/ip, --partitions, --tags, "
                   "--precision-policy mixed, a ring and headroom")
    d.add_argument("--query-tile", type=int, default=1024)
    d.add_argument("--corpus-tile", type=int, default=2048)
    d.add_argument("--precision-policy", choices=list(PRECISION_POLICIES),
                   default="exact")
    d.add_argument("--bucket", type=int, default=1024,
                   help="base row bucket of the executable cache; batches "
                   "pad to bucket*2^j rows")
    d.add_argument("--dispatch-depth", type=int, default=2)
    d.add_argument("--partitions", type=int, default=None,
                   help="serve a CLUSTERED (IVF) index: train this many "
                   "k-means partitions at startup (sublinear probing; "
                   "enables the background compactor for live mutation)")
    d.add_argument("--nprobe", type=int, default=None,
                   help="partitions probed per query (None with "
                   "--partitions = recall-targeted auto-tune)")
    d.add_argument("--bucket-headroom", type=float, default=0.0,
                   help="fractional spare capacity per bucket/tile for "
                   "LIVE mutation (POST /upsert, /delete — ISSUE 14): "
                   "pre-allocated free slots the donated in-place "
                   "scatters fill without a recompile. 0.0 (default) = "
                   "zero-rent frozen corpus; 0.25-0.5 for mutable ones "
                   "(headroom rows ride the fixed-shape FLOPs)")
    d.add_argument("--range-cap", type=int, default=0,
                   help="answer RANGE search too (0: k-NN alone): POST "
                   "/query with a radius (JSON `radius`, or the header "
                   "X-Radius beside a raw body; one squared L2 distance a "
                   "request) is answered with EVERY corpus row strictly "
                   "under it for each query row, in the big-ann suite's "
                   "range format (`lims` of rows + 1 offsets, flat `dists` "
                   "and `ids`); this is the most one query row may be "
                   "answered with — a row with more fails its request "
                   "with 422 naming the row and its true count, never "
                   "cut. The dense serial index under L2 over whole-"
                   "number rows (--dtype uint8, or float32 pixels), at "
                   "most 256 wide, frozen (no /upsert, /delete); /query "
                   "without a radius answers top-k as ever")
    d.add_argument("--tags", default=None, metavar="NPZ",
                   help="a bag of tag ids a corpus row, as a CSR in an "
                   ".npz (arrays `indptr` of rows + 1 offsets and "
                   "`indices`): POST /query may then carry up to "
                   "--max-query-tags tags a row (JSON `filters`, or int32 "
                   "after the rows of a raw body under the header "
                   "X-Filter-Tags) and a row is answered among the corpus "
                   "rows whose bag holds them all. The dense serial layout "
                   "only; the index is frozen (no /upsert, /delete)")
    d.add_argument("--max-query-tags", type=int, default=2,
                   help="tags a query row may carry (with --tags)")
    d.add_argument("--mutation-bucket", type=int, default=256,
                   help="base row bucket of the mutation executables "
                   "(chunks pad to mutation_bucket*2^j)")
    d.add_argument("--compactor-interval-s", type=float, default=0.25,
                   help="background compactor trigger-poll period for "
                   "clustered indices; 0 disables the compactor")
    d.add_argument("--compact-fill-threshold", type=float, default=0.9)
    d.add_argument("--compact-tombstone-fraction", type=float,
                   default=0.3)
    d.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="persistent AOT executable cache "
                   "(serve/aotcache.py; also via TKNN_AOT_CACHE): a "
                   "restarted server revives every executable it has "
                   "ever compiled from disk instead of re-paying XLA — "
                   "the second start against one dir warms with zero "
                   "backend compiles. Stale/corrupt entries fall back "
                   "to a real compile loudly; the dir is safe to share "
                   "between concurrent processes (atomic-rename writes)")
    d.add_argument("--warm-threads", type=int, default=None,
                   help="thread-pool width of the start-up warm "
                   "(default: auto = min(cells, cpu count); 1 forces "
                   "the sequential walk)")

    f = p.add_argument_group("front end (coalescing / SLO)")
    f.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="coalescing deadline: no request waits longer "
                   "than this for co-travelers before its batch "
                   "dispatches ragged")
    f.add_argument("--max-batch-rows", type=int, default=None,
                   help="coalesced batch row target (default: --bucket, "
                   "so steady-state fill batches land in one executable)")
    f.add_argument("--max-queue-rows", type=int, default=8192,
                   help="per-tenant queued-row ceiling; beyond it "
                   "requests are refused with a structured 429")
    f.add_argument("--tenant-qps", type=float, default=None,
                   help="per-tenant admission rate limit (token bucket "
                   "of --burst); default unlimited")
    f.add_argument("--burst", type=int, default=32)
    f.add_argument("--shed-queue-rows", type=int, default=None,
                   help="total queued rows that, sustained for "
                   "--shed-hold-ms, walk the serving degradation ladder "
                   "one rung down (recovery restores it); default: never "
                   "shed")
    f.add_argument("--shed-hold-ms", type=float, default=50.0)
    f.add_argument("--recover-hold-ms", type=float, default=250.0)

    n = p.add_argument_group("network / output")
    n.add_argument("--host", default="127.0.0.1")
    n.add_argument("--port", type=int, default=8080,
                   help="0 = ephemeral (printed, and written to "
                   "--ready-file)")
    n.add_argument("--request-timeout-s", type=float, default=30.0)
    n.add_argument("--ready-file", default=None, metavar="PATH",
                   help="write the listening URL here once ready (script "
                   "rendezvous)")
    n.add_argument("--flight-record", default=None, metavar="JSONL",
                   help="span flight record (coalesce events, batch "
                   "spans with tenant composition, shed/restore walks)")
    n.add_argument("--metrics-out", default=None, metavar="JSON",
                   help="write the metrics-registry snapshot at shutdown")
    n.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                   default="auto")
    n.add_argument("-q", "--quiet", action="store_true")
    return p


def serve_main(argv=None) -> int:
    args = build_serve_parser().parse_args(argv)
    if args.max_wait_ms < 0:
        print("error: --max-wait-ms must be >= 0", file=sys.stderr)
        return 2
    if args.port < 0:
        print("error: --port must be >= 0", file=sys.stderr)
        return 2
    if args.recover_hold_ms < 0 or args.shed_hold_ms < 0:
        print("error: hold times must be >= 0", file=sys.stderr)
        return 2
    if args.shed_queue_rows is None and (
        args.shed_hold_ms != 50.0 or args.recover_hold_ms != 250.0
    ):
        # the serve-CLI inert-knob convention: hold times only matter
        # once a shed threshold exists
        print("error: --shed-hold-ms/--recover-hold-ms without "
              "--shed-queue-rows: no shed threshold is set, so the "
              "knobs would be silently inert", file=sys.stderr)
        return 2

    if args.warm_threads is not None and args.warm_threads < 1:
        print("error: --warm-threads must be >= 1", file=sys.stderr)
        return 2

    if args.flight_record:
        from mpi_knn_tpu.obs.spans import FlightRecorder, set_recorder

        set_recorder(FlightRecorder(args.flight_record, fresh=True))

    if args.cache_dir:
        from mpi_knn_tpu.serve import aotcache

        aotcache.set_cache_dir(args.cache_dir)

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        force_platform(
            args.platform,
            n_devices=(args.devices if args.platform == "cpu" else None),
        )
    use_compile_cache()

    from mpi_knn_tpu.cli import load_corpus
    from mpi_knn_tpu.frontend.scheduler import SLOPolicy
    from mpi_knn_tpu.frontend.server import Frontend, FrontendHTTPServer
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import (
        ServeSession,
        build_index,
        build_index_blocks,
    )

    blocks = None
    if args.dtype == "uint8" and args.data.endswith(".bvecs"):
        # the file's own bytes, a block at a time: nothing widened on the
        # host, the corpus never held beside its stack
        from mpi_knn_tpu.data.vecs import bvecs_blocks

        try:
            shape, blocks = bvecs_blocks(args.data, limit=args.limit)
        except (FileNotFoundError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        source = args.data
    else:
        X, _, source = load_corpus(args.data, limit=args.limit)
        shape = X.shape
    try:
        cfg = KNNConfig(
            k=args.k,
            metric=args.metric,
            backend=args.backend,
            dtype=args.dtype,
            query_tile=args.query_tile,
            corpus_tile=args.corpus_tile,
            precision_policy=args.precision_policy,
            num_devices=args.devices,
            query_bucket=args.bucket,
            dispatch_depth=args.dispatch_depth,
            partitions=args.partitions,
            nprobe=args.nprobe,
            bucket_headroom=args.bucket_headroom,
            mutation_bucket=args.mutation_bucket,
            compact_fill_threshold=args.compact_fill_threshold,
            compact_tombstone_fraction=args.compact_tombstone_fraction,
            max_query_tags=args.max_query_tags,
            range_cap=args.range_cap,
        )
        policy = SLOPolicy(
            max_batch_rows=args.max_batch_rows or args.bucket,
            max_wait_s=args.max_wait_ms / 1e3,
            max_queue_rows=max(
                args.max_queue_rows, args.max_batch_rows or args.bucket
            ),
            max_tenant_qps=args.tenant_qps,
            burst=args.burst,
            shed_queue_rows=args.shed_queue_rows,
            shed_hold_s=args.shed_hold_ms / 1e3,
            recover_hold_s=args.recover_hold_ms / 1e3,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    try:
        if args.partitions is not None:
            # the clustered index serves through the same engine/front
            # end (it duck-types CorpusIndex) — and is the layout the
            # background compactor supervises
            from mpi_knn_tpu.ivf import build_ivf_index

            index = build_ivf_index(
                X, cfg, tags=args.tags and np.load(args.tags))
        elif blocks is not None:
            if args.tags:
                raise ValueError("an index with tags holds float32 rows "
                                 "(--dtype uint8 takes no --tags)")
            index = build_index_blocks(shape, blocks, cfg)
        else:
            index = build_index(
                X, cfg, tags=args.tags and np.load(args.tags))
        # a ResiliencePolicy (even the default) builds the degradation
        # ladder the queue-driven shed walks; without one the session
        # would have only its full rung
        session = ServeSession(index, resilience=ResiliencePolicy())
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    # cold-start order (ISSUE 12): bind the port and write the ready
    # file BEFORE warming — the warm-up runs on a background thread and
    # /healthz reports its buckets-ready/total progress, so time-to-
    # listening is index load, not the compile matrix; traffic is
    # admitted per bucket as executables land (a not-yet-ready bucket
    # gets a structured 503 "warming", never a hung socket)
    frontend = Frontend(session, policy)
    frontend.start(
        background=True, warm_parallel=args.warm_threads,
    )
    server = FrontendHTTPServer(
        frontend, host=args.host, port=args.port,
        request_timeout_s=args.request_timeout_s, quiet=args.quiet,
    ).start()
    build_s = time.perf_counter() - t0
    if not args.quiet:
        print(
            f"[mpi-knn serve] {source} shape={list(shape)} "
            f"backend={index.backend} k={cfg.k} bucket={cfg.query_bucket} "
            f"max_wait={args.max_wait_ms}ms (index+bind {build_s:.2f}s, "
            "warming in background)"
        )
        if getattr(index, "tags", None) is not None:
            print(f"[mpi-knn serve] tags {json.dumps(index.tags.summary())}")
        print(f"[mpi-knn serve] listening on {server.url}", flush=True)
    if args.ready_file:
        # atomic publish (utils.atomicio, host-lint rule H4): the CI
        # gate polls this file from another process while it is being
        # written — it must read nothing or the full URL, never a
        # truncated prefix
        from mpi_knn_tpu.utils.atomicio import atomic_write_text

        atomic_write_text(args.ready_file, server.url + "\n")

    def _report_warm():
        frontend._serving_ready.wait()
        rep = session.warm_report or {}
        if not args.quiet and rep:
            print(
                f"[mpi-knn serve] warm done in {rep.get('wall_s')}s: "
                f"{rep.get('cells')} cells ({rep.get('compiled')} "
                f"compiled, {rep.get('loaded')} from cache, "
                f"{rep.get('deduped')} deduped)"
                + (f" cache={args.cache_dir}" if args.cache_dir else ""),
                flush=True,
            )

    threading.Thread(target=_report_warm, daemon=True,
                     name="warm-report").start()

    # background compaction (ISSUE 14): clustered indices get the
    # trigger-driven re-cluster/compact worker (heartbeat/flight-
    # recorded, deferred while the session sheds load); the dense
    # layouts reclaim tombstones in place and need none
    compactor = None
    if args.compactor_interval_s > 0 and index.backend in (
        "ivf", "ivf-sharded"
    ):
        compactor = session.start_compactor(
            interval_s=args.compactor_interval_s
        )

    stop = threading.Event()

    def _sig(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        if compactor is not None:
            compactor.stop()
        server.stop()
        frontend.stop()
        if args.metrics_out:
            from mpi_knn_tpu.obs.metrics import get_registry
            from mpi_knn_tpu.utils.atomicio import atomic_write_text

            atomic_write_text(
                args.metrics_out,
                json.dumps(get_registry().snapshot(), indent=1) + "\n",
            )
        if not args.quiet:
            st = frontend.stats()
            print(
                f"[mpi-knn serve] shutdown: {st['queries_served']} query "
                f"rows in {st['batches_retired']} batches, "
                f"{st['rejected']} rejected, rung={st['rung']}"
            )
    return 0


# ---------------------------------------------------------------------------


def build_loadgen_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi-knn loadgen",
        description="open-loop multi-tenant load generator for a running "
        "`mpi-knn serve` (throughput-vs-p50/p99 rows; open loop so an "
        "overloaded server shows growing latency, not a slowing client)",
    )
    p.add_argument("--url", default=None,
                   help="server base URL (e.g. http://127.0.0.1:8080)")
    p.add_argument("--targets", default=None, metavar="URL1,URL2,...",
                   help="drive several endpoints at once (tenant i pins "
                   "to target i mod N — the router drill's multi-replica "
                   "direct baseline); replaces --url")
    p.add_argument("--connect", choices=["reuse", "per-request"],
                   default="reuse",
                   help="HTTP transport: 'reuse' = fixed worker pool "
                   "with persistent keep-alive connections (default); "
                   "'per-request' = legacy fresh connect + thread per "
                   "request")
    p.add_argument("--connections", type=int, default=4,
                   help="keep-alive connections per tenant stream "
                   "(reuse mode)")
    p.add_argument("--tenants", type=int, default=4,
                   help="concurrent tenant streams")
    p.add_argument("--qps", type=float, default=20.0,
                   help="offered request rate PER TENANT stream")
    p.add_argument("--sweep", default=None, metavar="Q1,Q2,...",
                   help="sweep these offered per-tenant QPS levels "
                   "instead of the single --qps")
    p.add_argument("--requests", type=int, default=20,
                   help="requests per tenant per level")
    p.add_argument("--rows", type=int, default=16,
                   help="query rows per request")
    p.add_argument("--timeout-s", type=float, default=30.0)
    p.add_argument("--report", default=None, help="write JSON rows here")
    p.add_argument("-q", "--quiet", action="store_true")
    return p


def loadgen_main(argv=None) -> int:
    args = build_loadgen_parser().parse_args(argv)
    if args.tenants < 1 or args.requests < 1 or args.rows < 1:
        print("error: --tenants/--requests/--rows must be >= 1",
              file=sys.stderr)
        return 2
    if args.qps <= 0:
        print("error: --qps must be > 0", file=sys.stderr)
        return 2
    if args.connections < 1:
        print("error: --connections must be >= 1", file=sys.stderr)
        return 2
    targets = None
    if args.targets:
        targets = [u.strip() for u in args.targets.split(",") if u.strip()]
        if not targets:
            print(f"error: bad --targets {args.targets!r}",
                  file=sys.stderr)
            return 2
    if targets is None and not args.url:
        print("error: one of --url / --targets is required",
              file=sys.stderr)
        return 2
    levels = [args.qps]
    if args.sweep:
        try:
            levels = [float(v) for v in args.sweep.split(",") if v.strip()]
        except ValueError:
            levels = []
        if not levels or any(v <= 0 for v in levels):
            print(f"error: bad --sweep {args.sweep!r}: want a "
                  "comma-separated list of positive QPS levels",
                  file=sys.stderr)
            return 2

    from mpi_knn_tpu.frontend import loadgen

    probe_url = targets[0] if targets else args.url
    try:
        health = loadgen.probe_server(probe_url, timeout_s=args.timeout_s)
    except OSError as e:
        print(f"error: cannot reach {probe_url}: {e}", file=sys.stderr)
        return 2
    if not args.quiet:
        print(
            f"[mpi-knn loadgen] {probe_url}"
            + (f" (+{len(targets) - 1} more)"
               if targets and len(targets) > 1 else "")
            + f": backend={health['backend']} "
            f"dim={health['dim']} k={health['k']} "
            f"max_batch_rows={health['max_batch_rows']} "
            f"connect={args.connect}"
        )
    rows_out = []
    for qps in sorted(levels):
        rep = loadgen.run_http(
            args.url, targets=targets, tenants=args.tenants, qps=qps,
            n_requests=args.requests, rows=args.rows,
            timeout_s=args.timeout_s, connect=args.connect,
            connections=args.connections,
        )
        rows_out.append(rep)
        if not args.quiet:
            print(
                f"  offered {rep['offered_qps_total']:g} req/s "
                f"({args.tenants} tenants): achieved "
                f"{rep['achieved_rps']} req/s "
                f"({rep['achieved_qps_rows']} rows/s), "
                f"p50 {rep['p50_ms']}ms p99 {rep['p99_ms']}ms, "
                f"rejected {rep['rejected']}, errors {rep['errors']}"
            )
    if any(r["errors"] for r in rows_out):
        print("error: load run saw serving errors (not 200/429)",
              file=sys.stderr)
        return 1
    if args.report:
        from mpi_knn_tpu.utils.atomicio import atomic_write_text

        atomic_write_text(args.report, json.dumps({
            "schema": "mpi_knn_tpu.frontend.loadgen/1",
            "url": probe_url,
            "targets": targets,
            "connect": args.connect,
            "health": health,
            "rows": rows_out,
        }, indent=1) + "\n")
        if not args.quiet:
            print(f"report written to {args.report}")
    return 0


# ---------------------------------------------------------------------------


def build_router_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi-knn router",
        description="replicated serving tier (ISSUE 18): a jax-free "
        "router fronting N `mpi-knn serve` replicas of one artifact — "
        "health-gated membership, tenant-affine (rendezvous-hash) "
        "spread with least-queued spill, sequenced mutation fan-out "
        "with bounded replay, optional supervised replica spawning",
        epilog="with --spawn, arguments after `--` are passed through "
        "to every `mpi-knn serve` child (e.g. `mpi-knn router --spawn 3 "
        "--cache-dir /tmp/aot -- --data synthetic:4096x32c4 --k 10`)",
    )
    m = p.add_argument_group("fleet")
    m.add_argument("--replicas", default=None, metavar="URL1,URL2,...",
                   help="static fleet: base URLs of running replicas "
                   "(named r0, r1, ... in probe order)")
    m.add_argument("--spawn", type=int, default=None, metavar="N",
                   help="launch and supervise N `mpi-knn serve` children "
                   "(resilience/worker.py: crashed replicas restart and "
                   "are health-gated back in); serve flags follow `--`")
    m.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="shared AOT executable cache for spawned "
                   "replicas: replica cold start rides the cache, so "
                   "second-and-later replicas compile zero programs")
    m.add_argument("--workdir", default=None, metavar="DIR",
                   help="spawn mode: ready-file directory (default: a "
                   "fresh temp dir)")

    r = p.add_argument_group("membership / routing")
    r.add_argument("--probe-interval-ms", type=float, default=500.0,
                   help="health-poll period (the router's own clock)")
    r.add_argument("--evict-after", type=int, default=3,
                   help="consecutive probe failures before eviction")
    r.add_argument("--rejoin-after", type=int, default=2,
                   help="consecutive ready probes before (re)join")
    r.add_argument("--spill-queue-rows", type=int, default=4096,
                   help="/healthz queue depth beyond which the affine "
                   "replica spills to the least-queued one")
    r.add_argument("--replay-buffer", type=int, default=4096,
                   help="bounded mutation replay buffer (entries); a "
                   "replica whose gap falls off it is quarantined until "
                   "cold-reloaded")

    n = p.add_argument_group("network / output")
    n.add_argument("--host", default="127.0.0.1")
    n.add_argument("--port", type=int, default=8090,
                   help="0 = ephemeral (printed, and written to "
                   "--ready-file)")
    n.add_argument("--request-timeout-s", type=float, default=30.0)
    n.add_argument("--ready-file", default=None, metavar="PATH",
                   help="write the router URL here once listening")
    n.add_argument("--flight-record", default=None, metavar="JSONL",
                   help="span flight record (membership transitions, "
                   "replica exits)")
    n.add_argument("--metrics-out", default=None, metavar="JSON",
                   help="write the metrics-registry snapshot at shutdown")
    n.add_argument("-q", "--quiet", action="store_true")
    p.add_argument("serve_args", nargs=argparse.REMAINDER,
                   help="after `--`: flags for every spawned `mpi-knn "
                   "serve` child")
    return p


def router_main(argv=None) -> int:
    args = build_router_parser().parse_args(argv)
    if (args.replicas is None) == (args.spawn is None):
        print("error: exactly one of --replicas / --spawn is required",
              file=sys.stderr)
        return 2
    if args.spawn is not None and args.spawn < 1:
        print("error: --spawn must be >= 1", file=sys.stderr)
        return 2
    if args.replicas is not None and (args.cache_dir or args.workdir):
        print("error: --cache-dir/--workdir only apply to --spawn "
              "(a static fleet owns its own caches)", file=sys.stderr)
        return 2
    serve_args = list(args.serve_args)
    if serve_args and serve_args[0] == "--":
        serve_args = serve_args[1:]
    if serve_args and args.spawn is None:
        print("error: serve pass-through args require --spawn",
              file=sys.stderr)
        return 2

    if args.flight_record:
        from mpi_knn_tpu.obs.spans import FlightRecorder, set_recorder

        set_recorder(FlightRecorder(args.flight_record, fresh=True))

    from mpi_knn_tpu.frontend.router import (
        ReplicaSupervisor,
        Router,
        RouterHTTPServer,
        RouterPolicy,
    )

    try:
        policy = RouterPolicy(
            probe_interval_s=args.probe_interval_ms / 1e3,
            evict_after=args.evict_after,
            rejoin_after=args.rejoin_after,
            spill_queue_rows=args.spill_queue_rows,
            replay_buffer=args.replay_buffer,
            request_timeout_s=args.request_timeout_s,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    supervisor = None
    replicas = None
    if args.spawn is not None:
        if args.cache_dir:
            serve_args += ["--cache-dir", args.cache_dir]
        workdir = args.workdir
        if workdir is None:
            import tempfile

            workdir = tempfile.mkdtemp(prefix="tknn-router-")
        try:
            supervisor = ReplicaSupervisor(
                args.spawn, serve_args, workdir=workdir
            ).start()
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    else:
        urls = [u.strip() for u in args.replicas.split(",") if u.strip()]
        if not urls:
            print(f"error: bad --replicas {args.replicas!r}",
                  file=sys.stderr)
            return 2
        replicas = {f"r{i}": u for i, u in enumerate(urls)}

    router = Router(
        replicas, policy=policy, supervisor=supervisor
    ).start()
    server = RouterHTTPServer(
        router, host=args.host, port=args.port, quiet=args.quiet
    ).start()
    if not args.quiet:
        fleet = (
            f"{args.spawn} spawned replicas" if supervisor is not None
            else f"{len(replicas)} static replicas"
        )
        print(f"[mpi-knn router] fronting {fleet}; "
              f"listening on {server.url}", flush=True)
    if args.ready_file:
        from mpi_knn_tpu.utils.atomicio import atomic_write_text

        atomic_write_text(args.ready_file, server.url + "\n")

    stop = threading.Event()

    def _sig(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        server.stop()
        router.stop()
        if supervisor is not None:
            supervisor.stop()
        if args.metrics_out:
            from mpi_knn_tpu.obs.metrics import get_registry
            from mpi_knn_tpu.utils.atomicio import atomic_write_text

            atomic_write_text(
                args.metrics_out,
                json.dumps(get_registry().snapshot(), indent=1) + "\n",
            )
        if not args.quiet:
            st = router.stats()
            print(
                f"[mpi-knn router] shutdown: seq={st['seq']} "
                f"rotation={st['rotation']}"
            )
    return 0


if __name__ == "__main__":
    sys.exit(serve_main())
