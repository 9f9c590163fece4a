"""``mpi-knn query`` — build a resident corpus index, stream query
batches, report per-batch latency and end-to-end throughput.

The serving counterpart of the one-shot run driver: the corpus is loaded
and indexed ONCE (tiles/shards + norms + centering mean on device), then
query batches stream through the bucketed AOT executable cache with
bounded dispatch-ahead (``mpi_knn_tpu.serve``). Steady state issues zero
recompiles; the summary line reports how many executables the whole run
compiled so that claim is visible per invocation.

Flag combinations the engine cannot honor are refused with a loud exit 2
(the ``BENCH_RING_SCHEDULE`` convention: never silently measure a
different configuration than the one requested) — e.g. a mixed-precision
query config over a bf16-compressed index, or a blocking-ring index on a
multi-axis mesh.

Examples::

    mpi-knn query --data synthetic:8192x64c10 --synthetic 4096 --batch 512
    mpi-knn query --data corpus.mat --queries q.npy --backend ring-overlap
    mpi-knn query --data sift:100000 --synthetic 10000 --bucket 1024 \
        --dispatch-depth 4 --report serve.json
    mpi-knn query --data sift:100000 --synthetic 10000 \
        --batch-deadline-ms 50 --retries 2    # resilient serving: deadline,
        # transient-retry, NaN sentinel, degradation ladder (see --help)
    mpi-knn query --data sift:100000 --synthetic 10000 \
        --flight-record flight.jsonl --metrics-out metrics.json \
        --profile-batches 8    # observability (mpi_knn_tpu.obs): span
        # flight record, metrics snapshot, device-time split in --report
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from mpi_knn_tpu.config import (
    BACKENDS,
    MERGE_SCHEDULES,
    METRICS,
    PRECISION_POLICIES,
    RING_SCHEDULES,
    TOPK_METHODS,
    KNNConfig,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi-knn query",
        description="streamed query serving against a device-resident "
        "corpus index (bucketed AOT executable cache, zero steady-state "
        "recompiles)",
    )
    d = p.add_argument_group("data")
    d.add_argument("--data", default="mnist",
                   help="corpus spec (same forms as the run driver: "
                   "'mnist', 'digits', 'synthetic:MxDcC', 'sift:M', "
                   "*.fvecs/bvecs, or a .mat file)")
    d.add_argument("--limit", type=int, default=None,
                   help="use first N corpus rows only")
    d.add_argument("--index-load", default=None, metavar="PATH.npz",
                   help="serve a saved clustered (IVF) index "
                   "(`mpi-knn build-index`) instead of building a dense "
                   "CorpusIndex from --data; --data is then only the "
                   "source of --synthetic query statistics")
    d.add_argument("--nprobe", type=int, default=None,
                   help="with --index-load: partitions probed per query "
                   "(default: the index's tuned value)")
    d.add_argument("--route-cap", type=int, default=None,
                   help="with --index-load --backend ring: static "
                   "per-(home, owner)-shard route capacity of the "
                   "candidate exchange per query tile (default: the safe "
                   "cap q_tile*nprobe — no probe ever drops); smaller "
                   "caps bound exchange memory and DROP overflow probes "
                   "(counted in the metrics/report, never wrong answers)")
    q = p.add_mutually_exclusive_group()
    q.add_argument("--queries", default=None,
                   help=".npy/.mat/.fvecs file of query points, streamed "
                   "in --batch-row chunks")
    q.add_argument("--synthetic", type=int, default=None, metavar="N",
                   help="serve N synthetic query rows (corpus-distributed "
                   "noise, corpus dim) instead of a file")
    d.add_argument("--batch", type=int, default=256,
                   help="rows per streamed batch (the final batch may be "
                   "ragged; it pads to its bucket)")

    k = p.add_argument_group("kNN / serving")
    k.add_argument("--k", type=int, default=30)
    k.add_argument("--metric", choices=METRICS, default="l2")
    k.add_argument("--backend", choices=BACKENDS, default="auto")
    k.add_argument("--devices", type=int, default=None,
                   help="ring size for distributed backends")
    # corpus-side knobs default to None so --index-load can tell an
    # explicitly passed flag (refused loudly if it conflicts with the
    # saved index) from an untouched default; the dense build path
    # resolves None to the documented defaults below
    k.add_argument("--dtype", default=None,
                   choices=["float32", "bfloat16", "float64"],
                   help="resident/compute dtype (default float32); "
                   "bfloat16 stores the index compressed at half width")
    k.add_argument("--query-tile", type=int, default=1024)
    k.add_argument("--corpus-tile", type=int, default=None,
                   help="corpus tile rows (default 2048); baked into a "
                   "loaded index's layout")
    k.add_argument("--precision-policy", choices=list(PRECISION_POLICIES),
                   default="exact")
    k.add_argument("--topk-method", choices=list(TOPK_METHODS),
                   default="exact")
    k.add_argument("--merge-schedule", choices=list(MERGE_SCHEDULES),
                   default="twolevel")
    k.add_argument("--ring-schedule", choices=list(RING_SCHEDULES),
                   default=None,
                   help="ring rotation schedule (default uni); "
                   "meaningless for a loaded clustered index")
    k.add_argument("--ring-transfer-dtype",
                   choices=["bfloat16", "float32", "int8"], default=None,
                   help="dtype of the corpus block on the rotation wire "
                   "(ring backends): bfloat16 halves ICI bytes per hop; "
                   "int8 is the block-scaled quantized level (~4x fewer "
                   "bytes, requires --precision-policy mixed; the "
                   "resident index holds codes + scales, so HBM shrinks "
                   "too — the --report ring_transfer block carries the "
                   "static wire bytes)")
    k.add_argument("--bucket", type=int, default=1024,
                   help="base row bucket: batches pad to bucket*2^j rows "
                   "and each (bucket, config) compiles exactly once")
    k.add_argument("--range-cap", type=int, default=0,
                   help="answer RANGE search too (0: k-NN alone): with "
                   "--radius every query row gets EVERY corpus row at a "
                   "squared L2 distance strictly under it; this is the "
                   "most one row may be answered with — a row with more "
                   "is refused by name, never cut. The dense serial index "
                   "under L2 over whole-number rows (uint8, or float32 "
                   "pixels), at most 256 wide, frozen")
    k.add_argument("--radius", type=float, default=None,
                   help="with --range-cap: stream the batches as range "
                   "batches at this squared radius (whole-number query "
                   "rows in [0, 255])")
    k.add_argument("--dispatch-depth", type=int, default=2,
                   help="max batches in flight (2 = double buffering)")
    k.add_argument("--no-donate", action="store_true",
                   help="disable per-batch scratch donation (debugging)")
    k.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="persistent AOT executable cache "
                   "(serve/aotcache.py; also via TKNN_AOT_CACHE): "
                   "executables this process compiles are serialized "
                   "here and revived on the next run — a repeated query "
                   "run against one dir warms with zero XLA backend "
                   "compiles (the summary/report carry the hit/miss "
                   "story). Stale or corrupt entries recompile loudly")

    r = p.add_argument_group(
        "resilience (mpi_knn_tpu.resilience: deadline, retry, sentinel, "
        "degradation ladder)"
    )
    r.add_argument("--batch-deadline-ms", type=float, default=None,
                   metavar="MS",
                   help="per-batch latency deadline (dispatch→sync); on "
                   "--degrade-after consecutive breaches the session "
                   "sheds load one rung down the degradation ladder "
                   "(smaller nprobe → mixed precision → smaller bucket), "
                   "stamping every degraded batch in the records and the "
                   "report")
    r.add_argument("--retries", type=int, default=None, metavar="N",
                   help="bounded exponential-backoff retries of a batch "
                   "dispatch on transient failures (default 2 when a "
                   "resilience policy is active)")
    r.add_argument("--degrade-after", type=int, default=None, metavar="N",
                   help="consecutive deadline breaches before shedding "
                   "one ladder rung (default 2 when a resilience policy "
                   "is active)")
    r.add_argument("--no-nan-sentinel", action="store_true",
                   help="disable the NaN/all-inf sentinel on returned "
                   "top-k (on by default with a resilience policy; trips "
                   "loudly with batch provenance)")

    o = p.add_argument_group("output / observability (mpi_knn_tpu.obs)")
    o.add_argument("--tenant", default=None, metavar="NAME",
                   help="attribute this stream to a tenant id: per-tenant "
                   "counters (serve_tenant_queries_total{tenant=...}) in "
                   "the metrics registry, tenant composition on the batch "
                   "flight spans, and a per-tenant block in --report — "
                   "the single-stream form of the serving front end's "
                   "multi-tenant accounting (mpi-knn serve)")
    o.add_argument("--report", default=None, help="write JSON report here")
    o.add_argument("--flight-record", default=None, metavar="JSONL",
                   help="record structured trace spans (index build, "
                   "per-bucket compiles, per-batch dispatch→retire, "
                   "retry/degradation events) to this append-only JSONL "
                   "ring file, written incrementally so the record "
                   "survives a killed process; inspect/validate/export "
                   "with `mpi-knn metrics --flight`")
    o.add_argument("--metrics-out", default=None, metavar="JSON",
                   help="write the process metrics-registry snapshot "
                   "(batch latency histogram, compile counters, "
                   "resilience counters) at exit; render as Prometheus "
                   "text with `mpi-knn metrics`")
    o.add_argument("--profile-batches", type=int, default=None, metavar="N",
                   help="after the stream, profile N extra steady-state "
                   "batches under jax.profiler and embed the per-category "
                   "device busy split (matmul/sort-topk/collective/copy/"
                   "other + overlap fraction) in the report next to "
                   "p50/p99")
    o.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="with --profile-batches: keep the raw trace here "
                   "(default: a temp dir)")
    o.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                   default="auto")
    o.add_argument("-q", "--quiet", action="store_true")
    return p


def _resilience_policy(args):
    """A ResiliencePolicy when any resilience flag was given, else None
    (the zero-overhead legacy session). A policy-shaping knob WITHOUT a
    policy-activating one is refused, not silently inert — the serve
    CLI's convention for knobs that would not apply."""
    if args.degrade_after is not None and args.batch_deadline_ms is None:
        # degradation is deadline-driven: without a deadline the counter
        # can never trigger, whatever else is active
        raise ValueError(
            "--degrade-after without --batch-deadline-ms: degradation "
            "is triggered by deadline breaches, so the knob would be "
            "silently inert"
        )
    if args.batch_deadline_ms is None and args.retries is None:
        if args.no_nan_sentinel:
            raise ValueError(
                "--no-nan-sentinel without --batch-deadline-ms or "
                "--retries: no resilience policy is active, so the knob "
                "would be silently inert"
            )
        return None
    from mpi_knn_tpu.resilience import ResiliencePolicy

    return ResiliencePolicy(
        batch_deadline_s=(
            args.batch_deadline_ms / 1e3
            if args.batch_deadline_ms is not None else None
        ),
        max_retries=args.retries if args.retries is not None else 2,
        degrade_after=(
            args.degrade_after if args.degrade_after is not None else 2
        ),
        nan_sentinel=not args.no_nan_sentinel,
    )


def _load_query_stream(args, X):
    """(total_rows, iterator of np batches) from --queries / --synthetic."""
    if args.synthetic is not None:
        rng = np.random.default_rng(1)
        dim = X.shape[1]
        total = args.synthetic
        lo, hi = float(np.min(X)), float(np.max(X))

        def gen():
            left = total
            while left > 0:
                n = min(args.batch, left)
                yield rng.uniform(lo, hi, size=(n, dim)).astype(np.float32)
                left -= n

        return total, gen()
    from mpi_knn_tpu.cli import _load_queries

    Q = np.asarray(_load_queries(args.queries))
    if Q.ndim != 2 or Q.shape[1] != X.shape[1]:
        raise SystemExit(
            f"error: queries shape {Q.shape} does not match corpus dim "
            f"{X.shape[1]}"
        )

    def gen():
        for s in range(0, len(Q), args.batch):
            yield Q[s: s + args.batch]

    return len(Q), gen()


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.queries is None and args.synthetic is None:
        print("error: provide a query stream (--queries FILE or "
              "--synthetic N)", file=sys.stderr)
        return 2
    if args.batch < 1:
        print("error: --batch must be >= 1", file=sys.stderr)
        return 2
    if args.synthetic is not None and args.synthetic < 1:
        # a zero/negative stream would "succeed" with 0 queries served —
        # a silent no-op where the convention demands a loud usage error
        print("error: --synthetic must be >= 1", file=sys.stderr)
        return 2
    if args.tenant is not None and (
        not args.tenant
        or any(c in args.tenant for c in ('"', "\\", "\n", "\r"))
    ):
        # the tenant id becomes a metrics label: refuse at the flag, not
        # with a mid-stream traceback at the first batch retire
        print("error: --tenant must be non-empty with no quotes, "
              "backslashes, or newlines (it becomes a metrics label)",
              file=sys.stderr)
        return 2

    try:
        policy = _resilience_policy(args)
    except ValueError as e:
        # invalid resilience knobs (negative deadline, degrade-after 0…):
        # the loud exit-2 usage-error convention
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.profile_dir is not None and args.profile_batches is None:
        # the inert-knob refusal convention: a kept trace dir without a
        # profiling pass would silently record nothing
        print("error: --profile-dir without --profile-batches: no "
              "profiling pass runs, so the knob would be silently inert",
              file=sys.stderr)
        return 2
    if args.profile_batches is not None and args.profile_batches < 1:
        print("error: --profile-batches must be >= 1", file=sys.stderr)
        return 2

    if args.flight_record:
        # install before any index/serve work so the index-build span and
        # the warm-up compiles land in the record; fresh=True — a new run
        # must not append to a previous run's story
        from mpi_knn_tpu.obs.spans import FlightRecorder, set_recorder

        set_recorder(FlightRecorder(args.flight_record, fresh=True))

    if args.cache_dir:
        # before any executable builds, so even the first bucket of the
        # stream can revive from (or land in) the persistent cache
        from mpi_knn_tpu.serve import aotcache

        aotcache.set_cache_dir(args.cache_dir)

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        # --platform cpu --devices N: size the virtual host mesh to the
        # request (a ring/sharded serve on a 1-CPU host would otherwise
        # fail with "only 1 visible" despite the explicit ask)
        force_platform(
            args.platform,
            n_devices=(args.devices if args.platform == "cpu" else None),
        )
    use_compile_cache()

    from mpi_knn_tpu.cli import load_corpus
    from mpi_knn_tpu.serve import ServeSession, build_index

    X, _, source = load_corpus(args.data, limit=args.limit)

    if args.index_load:
        return _serve_loaded_index(args, X, source, policy)

    if args.nprobe is not None:
        # the serve-CLI refusal convention: a probe count without a
        # clustered index would be silently ignored
        print("error: --nprobe requires --index-load (probing is a "
              "clustered-index knob)", file=sys.stderr)
        return 2
    if args.route_cap is not None:
        print("error: --route-cap requires --index-load --backend ring "
              "(the route cap bounds the sharded clustered candidate "
              "exchange)", file=sys.stderr)
        return 2

    try:
        cfg = KNNConfig(
            k=args.k,
            metric=args.metric,
            backend=args.backend,
            dtype=args.dtype or "float32",
            query_tile=args.query_tile,
            corpus_tile=args.corpus_tile or 2048,
            precision_policy=args.precision_policy,
            topk_method=args.topk_method,
            merge_schedule=args.merge_schedule,
            ring_schedule=args.ring_schedule or "uni",
            ring_transfer_dtype=args.ring_transfer_dtype,
            num_devices=args.devices,
            query_bucket=args.bucket,
            dispatch_depth=args.dispatch_depth,
            donate=not args.no_donate,
            range_cap=args.range_cap,
        )
        if args.radius is not None and not args.range_cap:
            raise ValueError("--radius wants --range-cap N: the most "
                             "results one query row may be answered with")
    except ValueError as e:
        # invalid knob combination (e.g. mixed policy over a non-f32
        # dtype): loud usage error, never a silently-adjusted run
        print(f"error: {e}", file=sys.stderr)
        return 2

    t_build0 = time.perf_counter()
    try:
        index = build_index(X, cfg)
        session = ServeSession(index, resilience=policy)
    except ValueError as e:
        # the engine cannot honor this combination (compressed index +
        # mixed policy, blocking ring on a 2-D mesh…)
        print(f"error: {e}", file=sys.stderr)
        return 2
    build_s = time.perf_counter() - t_build0
    return _stream_and_report(args, session, index, X, source, build_s)


def _serve_loaded_index(args, X, source, policy=None) -> int:
    """``--index-load``: serve a saved clustered (IVF) index through the
    same session/bucket-cache machinery — single-device by default, or
    SHARDED over the ring mesh with ``--backend ring`` (the shard layout
    is derived from ``--devices``; one artifact serves on any shard
    count). Corpus-side knobs come from the saved index; explicitly
    conflicting flags are refused with the standard loud exit 2 (never
    silently serve a different configuration than the one requested)."""
    from mpi_knn_tpu.ivf import load_ivf_index
    from mpi_knn_tpu.serve import ServeSession

    sharded = args.backend == "ring"
    if args.backend not in ("auto", "serial", "ring"):
        print(
            f"error: --index-load × --backend {args.backend} is not "
            "supported: a clustered index serves single-device (serial/"
            "auto) or sharded over the ring mesh (ring — the routed "
            "candidate exchange), and the exchange has no overlap "
            "schedule (use --backend ring, not ring-overlap)",
            file=sys.stderr,
        )
        return 2
    if args.metric != "l2":
        print(
            f"error: --index-load × --metric {args.metric} is not "
            "supported: the clustered index's k-means partitions and "
            "centroid score are L2 geometry",
            file=sys.stderr,
        )
        return 2
    if args.devices is not None and not sharded:
        print("error: --devices with --index-load requires --backend "
              "ring (the shard count of the distributed clustered "
              "index); the single-device clustered search cannot honor "
              "it", file=sys.stderr)
        return 2
    if args.route_cap is not None and not sharded:
        print("error: --route-cap with --index-load requires --backend "
              "ring: the route cap bounds the sharded candidate "
              "exchange — nothing is routed single-device",
              file=sys.stderr)
        return 2
    if args.corpus_tile is not None:
        print("error: --corpus-tile has no meaning with --index-load "
              "(the bucket layout was baked in at build time)",
              file=sys.stderr)
        return 2
    if args.ring_transfer_dtype is not None:
        print("error: --ring-transfer-dtype has no meaning with "
              "--index-load: the clustered search never rotates a ring, "
              "and the store's AT-REST compression (float32/bfloat16/"
              "int8/int4) was baked in at build time — rebuild with "
              "`mpi-knn build-index --dtype ...` to change it",
              file=sys.stderr)
        return 2
    if args.ring_schedule is not None:
        print("error: --ring-schedule has no meaning with --index-load "
              "(the clustered search never rotates a ring)",
              file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    try:
        index = load_ivf_index(args.index_load)
    except (OSError, KeyError, ValueError) as e:
        print(f"error: cannot load index {args.index_load!r}: {e}",
              file=sys.stderr)
        return 2
    if args.dtype is not None and args.dtype != index.cfg.dtype:
        print(
            f"error: --dtype {args.dtype} conflicts with the loaded "
            f"index's at-rest dtype ({index.cfg.dtype}); the dtype is "
            "baked in at build time",
            file=sys.stderr,
        )
        return 2
    if X.shape[1] != index.dim:
        print(
            f"error: --data {args.data!r} has dim {X.shape[1]} but the "
            f"loaded index was built at dim {index.dim}",
            file=sys.stderr,
        )
        return 2
    try:
        if sharded:
            # derive the shard layout over the mesh — the saved artifact
            # carries no layout, so the SAME .npz serves here at any
            # --devices count (bit-compatibly: every per-query dot shape
            # is shard-count-independent)
            from mpi_knn_tpu.ivf import shard_ivf_index

            index = shard_ivf_index(
                index, shards=args.devices, route_cap=args.route_cap
            )
        cfg = index.compatible_cfg(
            index.cfg.replace(
                k=args.k,
                query_tile=args.query_tile,
                precision_policy=args.precision_policy,
                topk_method=args.topk_method,
                merge_schedule=args.merge_schedule,
                nprobe=args.nprobe,  # None -> the index's tuned default
                query_bucket=args.bucket,
                dispatch_depth=args.dispatch_depth,
                donate=not args.no_donate,
            )
        )
        session = ServeSession(index, cfg, resilience=policy)
    except ValueError as e:
        # unhonorable combination (nprobe > partitions, mixed policy on a
        # bf16-at-rest index, more shards than devices, …)
        print(f"error: {e}", file=sys.stderr)
        return 2
    load_s = time.perf_counter() - t0
    return _stream_and_report(args, session, index, X, source, load_s)


def _stream_and_report(args, session, index, X, source, build_s) -> int:
    """Shared serving tail: stream the query batches, print per-batch
    latency lines, emit the summary/report."""
    from mpi_knn_tpu.serve.engine import index_peak_hbm_bytes

    cfg = session.cfg
    total, stream = _load_query_stream(args, X)

    t0 = time.perf_counter()
    n_batches = 0
    degraded_batches = 0
    for res in session.stream(stream, tenant=args.tenant,
                              radius=getattr(args, "radius", None)):
        n_batches += 1
        if res.degraded is not None:
            degraded_batches += 1
        if not args.quiet:
            # the per-batch resilience stamps ride the latency line: a
            # degraded/retried/breached batch must be visible where the
            # operator is already looking (the PR 4 "degraded" marker
            # convention)
            extra = ""
            if res.degraded is not None:
                extra += f" degraded={res.degraded}"
            if res.retries:
                extra += f" retries={res.retries}"
            if res.deadline_breached:
                extra += " DEADLINE-BREACH"
            # res.seq IS the printed batch number: sentinel/degradation
            # provenance (batch seq=N) must point at this exact line
            if res.range_out is not None:
                lims, _, _, refused = res.range_answer
                extra += f" results={int(lims[-1])}"
                if refused:
                    extra += f" REFUSED-OVER-CAP={len(refused)}"
            print(
                f"batch {res.seq}: rows={res.rows} "
                f"bucket={res.bucket} latency={res.latency_s * 1e3:.2f}ms"
                f"{extra}"
            )
    wall = time.perf_counter() - t0

    lats = np.asarray(session.latencies)
    summary = {
        "corpus": source,
        "shape": list(X.shape),
        "backend": index.backend,
        "k": cfg.k,
        "queries": session.queries_served,
        "batches": n_batches,
        "executables_compiled": len(index._cache),
        "index_build_s": round(build_s, 4),
        "wall_s": round(wall, 4),
        "throughput_qps": round(session.queries_served / wall, 2)
        if wall > 0 else None,
        "latency_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3)
        if len(lats) else None,
        "latency_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3)
        if len(lats) else None,
        # static peak HBM of the largest executable this run built
        # (ISSUE 15): PJRT buffer-assignment figure, zero device reads
        # — the serve_peak_hbm_bytes gauge's number, read next to the
        # throughput it bounds
        "peak_hbm_bytes": index_peak_hbm_bytes(index),
    }
    from mpi_knn_tpu.analysis.cost import detected_profile

    # the declared roofline inputs for this hardware (ISSUE 16): the
    # shipped device profile `mpi-knn plan` predicted q/s under, stamped
    # next to the measured throughput; null off the profile map
    summary["device_profile"] = detected_profile()
    if index.backend in ("ivf", "ivf-sharded"):
        summary["partitions"] = index.partitions
        summary["nprobe"] = cfg.nprobe
        summary["probe_fraction"] = round(
            cfg.nprobe / index.partitions, 4
        )
        # the compression-ladder story (ISSUE 9): the at-rest level and
        # the resident bytes it buys — read next to the recall/latency
        # this run measured, same numbers the ivf_at_rest_bytes gauge
        # stamps at lower time
        summary["at_rest"] = {
            "dtype": cfg.dtype,
            "resident_bytes": index.nbytes_resident,
            "probe_bytes_per_query": index.probe_bytes,
        }
    if index.backend in ("ring", "ring-overlap"):
        from mpi_knn_tpu.backends.ring import ring_wire_bytes_per_batch

        # the transfer level and the static per-batch rotation bytes at
        # the wire dtype (the ring_transfer_wire_bytes gauge's number)
        summary["ring_transfer"] = {
            "dtype": cfg.ring_transfer_dtype or cfg.dtype,
            "wire_bytes_per_batch": ring_wire_bytes_per_batch(
                cfg, index.corpus_sharded.shape[0], index.dim,
                index.ring_meta[3],
            ),
        }
    from mpi_knn_tpu.serve import aotcache as _aotcache

    _disk = _aotcache.active_cache()
    if _disk is not None:
        # the cold-start story next to the throughput it bought: cache
        # size plus this process's hit/miss/error counters (the same
        # numbers the registry exports as aot_cache_*_total)
        from mpi_knn_tpu.obs.metrics import get_registry

        _reg = get_registry()
        summary["aot_cache"] = {
            **_disk.stats(),
            "hits": int(_reg.counter("aot_cache_hits_total").snapshot()
                        ["value"]),
            "misses": int(_reg.counter("aot_cache_misses_total").snapshot()
                          ["value"]),
            "errors": int(_reg.counter("aot_cache_errors_total").snapshot()
                          ["value"]),
        }
    if session.tenant_stats:
        # the per-tenant window accumulators (first-class session state,
        # never reconstructed from deltas of the global blob): rows,
        # batches touched, latency sum/max — per tenant
        summary["tenants"] = {
            t: {
                "queries": st["queries"],
                "batches": st["batches"],
                "latency_sum_ms": round(st["latency_sum_s"] * 1e3, 3),
                "latency_max_ms": round(st["latency_max_s"] * 1e3, 3),
                **(
                    {"routed": round(st["routed"], 1)}
                    if "routed" in st else {}
                ),
            }
            for t, st in sorted(session.tenant_stats.items())
        }
    if session.exchange is not None:
        # the sharded candidate-exchange story, summarized where the
        # round is read: routed probe volume, the (counted, loud) probe-
        # cap overflow drops, static exchange bytes, and the per-shard
        # served-request load — the skew an operator tunes partitions/
        # route caps against
        summary["sharded"] = {
            "shards": session.exchange["shards"],
            "route_cap": cfg.ivf_route_cap,  # None = safe (no drops)
            "routed_total": session.exchange["routed_total"],
            "overflow_dropped_total": session.exchange["dropped_total"],
            "exchange_bytes_total":
                session.exchange["exchange_bytes_total"],
            "served_per_shard": session.exchange["served_per_shard"],
        }
    if args.profile_batches:
        # batches replay the stream's shape (--batch rows,
        # corpus-distributed synthetic noise); session.profile compiles
        # any bucket they still need BEFORE opening the trace (a short
        # --queries file may never have served a full --batch), so the
        # trace measures serving, not compilation.
        rng = np.random.default_rng(2)
        lo, hi = float(np.min(X)), float(np.max(X))
        prof_batches = [
            rng.uniform(lo, hi, size=(args.batch, X.shape[1]))
            .astype(np.float32)
            for _ in range(args.profile_batches)
        ]
        summary["device_time"] = session.profile(
            prof_batches, trace_dir=args.profile_dir
        )
    if session.policy is not None:
        # the degradation story, summarized where the round is read: how
        # often the deadline broke, what the ladder shed, where serving
        # ended up — mirroring the per-batch stamps above
        summary["resilience"] = {
            "batch_deadline_ms": (
                session.policy.batch_deadline_s * 1e3
                if session.policy.batch_deadline_s is not None else None
            ),
            "ladder": [label for label, _ in session.ladder],
            "final_rung": session.rung,
            "degraded_batches": degraded_batches,
            "deadline_breaches": session.deadline_breaches,
            "retries_total": session.retries_total,
            "degradations": session.degradations,
        }
    if not args.quiet:
        print(
            f"[mpi-knn query] {summary['queries']} queries in "
            f"{summary['batches']} batches: {summary['throughput_qps']} q/s "
            f"(p50 {summary['latency_p50_ms']}ms, "
            f"p99 {summary['latency_p99_ms']}ms, "
            f"{summary['executables_compiled']} executable(s) compiled, "
            f"index build {summary['index_build_s']}s)"
        )
    if not args.quiet and "device_time" in summary:
        dt = summary["device_time"]
        if "busy_ms" in dt:
            split = ", ".join(
                f"{k} {v}ms" for k, v in sorted(dt["busy_ms"].items())
            )
            print(
                f"[device-time] plane={dt['plane']} "
                f"busy={dt['busy_total_ms']}ms ({split}) "
                f"overlap-fraction={dt['overlap_fraction']}"
            )
        else:
            print(f"[device-time] {dt.get('error', 'no attribution')}")
    if args.metrics_out:
        from mpi_knn_tpu.obs.metrics import get_registry

        with open(args.metrics_out, "w") as f:
            json.dump(get_registry().snapshot(), f, indent=1)
            f.write("\n")
        if not args.quiet:
            print(f"metrics snapshot written to {args.metrics_out}")
    if args.flight_record and not args.quiet:
        print(f"flight record written to {args.flight_record}")
    if args.report:
        with open(args.report, "w") as f:
            json.dump(summary, f, indent=1)
            f.write("\n")
        if not args.quiet:
            print(f"report written to {args.report}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
