"""Command-line interface (SURVEY.md C12).

Everything the reference hardcodes — filename (``knn-serial.c:40``), k
(``#define NN 30``), class count (``#define max 10``), metric, process/thread
counts from bare argv (``mpi-knn-parallel_blocking.c:53-54``) — is a flag
here, with the reference's values as defaults. One binary, backend selected
by flag, replacing the reference's three separate programs.

Examples::

    python -m mpi_knn_tpu --data mnist --k 30 --loo
    python -m mpi_knn_tpu --data synthetic:2048x64c10 --backend ring-overlap
    python -m mpi_knn_tpu --data corpus.mat --svd 64 --k 10 --report out.json
    python -m mpi_knn_tpu query --data corpus.mat --queries q.npy  # serving
    python -m mpi_knn_tpu build-index --data sift:100000 --partitions 256 \
        --out sift.ivf.npz                       # clustered (IVF) index
    python -m mpi_knn_tpu query --data sift:100000 --index-load sift.ivf.npz \
        --synthetic 4096                         # sublinear serving
    python -m mpi_knn_tpu lint --serve                     # static analysis
    python -m mpi_knn_tpu metrics serve-metrics.json       # observability:
    python -m mpi_knn_tpu metrics --flight flight.jsonl --chrome trace.json
"""

from __future__ import annotations

import argparse
import re
import sys

import numpy as np

from mpi_knn_tpu.config import (
    BACKENDS,
    MERGE_SCHEDULES,
    METRICS,
    PRECISION_POLICIES,
    RING_SCHEDULES,
    TIE_BREAKS,
    TOPK_METHODS,
    KNNConfig,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi_knn_tpu",
        description="TPU-native brute-force kNN search + classification",
    )
    d = p.add_argument_group("data")
    d.add_argument(
        "--data",
        default="mnist",
        help="'mnist' (real if found, else synthetic), 'digits' (REAL "
        "handwritten digits, 1797x64, bundled offline), 'synthetic:MxDcC' "
        "(e.g. synthetic:4096x128c10), 'sift:M' (SIFT1M-shaped surrogate, "
        "e.g. sift:1000000), or a .mat file with train_X/train_labels in "
        "the reference layout",
    )
    d.add_argument("--limit", type=int, default=None, help="use first N rows only")
    d.add_argument("--svd", type=int, default=None, metavar="DIM",
                   help="reduce the corpus to DIM principal components first "
                   "(the mnist_train_svd configuration)")

    k = p.add_argument_group("kNN")
    k.add_argument("--k", type=int, default=30, help="neighbors (reference NN=30)")
    k.add_argument("--metric", choices=METRICS, default="l2")
    k.add_argument("--backend", choices=BACKENDS, default="auto")
    k.add_argument("--num-classes", type=int, default=10)
    k.add_argument("--tie-break", choices=TIE_BREAKS, default="nearest")
    k.add_argument("--devices", type=int, default=None,
                   help="ring size for distributed backends (default: all)")
    k.add_argument("--dp", type=int, default=1,
                   help="2-D mesh: data-parallel groups; devices/dp form the "
                   "corpus ring inside each group (queries shard over all "
                   "devices, corpus memory scales with the ring size)")
    k.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host: coordinator address (or set "
                   "JAX_COORDINATOR_ADDRESS); launch one process per host")
    k.add_argument("--num-processes", type=int, default=None,
                   help="multi-host: total process count (JAX_NUM_PROCESSES)")
    k.add_argument("--process-id", type=int, default=None,
                   help="multi-host: this process's id (JAX_PROCESS_ID)")
    k.add_argument("--query-tile", type=int, default=1024)
    k.add_argument("--corpus-tile", type=int, default=2048)
    k.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16", "float64", "uint8"],
                   help="compute dtype; uint8: the corpus rests one byte "
                   "an element (whole-number rows in [0, 255], checked, "
                   "never rounded) and every tile step widens its tile: "
                   "the float32 answers. L2, --backend serial, no "
                   "--checkpoint-dir")
    k.add_argument("--precision-policy", choices=list(PRECISION_POLICIES),
                   default="exact",
                   help="distance-pipeline precision: exact (one-pass "
                   "HIGHEST dot) or mixed (compress-and-rerank: single-pass "
                   "bf16 dot overfetches 4k candidates, exact HIGHEST "
                   "rerank of the survivors — the TPU-KNN recipe; requires "
                   "--dtype float32)")
    k.add_argument("--topk-method", choices=list(TOPK_METHODS), default="exact",
                   help="exact lax.top_k; approx_min_k partial reduction; or "
                   "block — exact narrow-sort two-level reduction (fastest "
                   "exact method on TPU, BASELINE.md r3)")
    k.add_argument("--topk-block", type=int, default=128,
                   help="first-level sort width for --topk-method=block")
    k.add_argument("--merge-schedule", choices=list(MERGE_SCHEDULES),
                   default="twolevel",
                   help="serial-core tile merge: stream (carry per tile) or "
                   "twolevel (local top-k per tile + one cascade merge)")
    k.add_argument("--ring-schedule", choices=list(RING_SCHEDULES),
                   default="uni",
                   help="ring rotation schedule: uni (the reference's "
                   "one-directional ring, P rounds) or bidir (full-duplex: "
                   "blocks circulate both torus directions at once, "
                   "floor(P/2)+1 rounds, same results bit-identically — "
                   "the comm critical path halves on real ICI)")
    k.add_argument("--ring-transfer-dtype",
                   choices=["bfloat16", "float32", "int8"],
                   default=None,
                   help="dtype of the corpus block while it rotates the "
                   "ring; bfloat16 halves ICI bytes per hop (cast once, "
                   "upcast per round — exact on integer-valued data); "
                   "int8 is the block-scaled quantized level (~4x fewer "
                   "wire bytes; requires --precision-policy mixed so the "
                   "exact rerank absorbs the quantization)")
    k.add_argument("--include-zero-dist", action="store_true",
                   help="keep zero-distance (duplicate) neighbors — the "
                   "reference excludes them (knn-serial.c:86)")
    k.add_argument("--include-self", action="store_true",
                   help="keep each point as its own neighbor in all-pairs mode")

    o = p.add_argument_group("output")
    o.add_argument("--loo", action="store_true",
                   help="leave-one-out classification (the reference's "
                   "workload); default when no --queries")
    o.add_argument("--queries", default=None,
                   help=".mat/.npy file of query points (query mode)")
    o.add_argument("--report", default=None, help="write JSON report here")
    o.add_argument("--save-neighbors", default=None, metavar="PATH.npz",
                   help="write the neighbor lists (dists + 0-based ids, and "
                   "predictions when voting ran) as NPZ — the reference "
                   "only ever printed to stdout (knn-serial.c:130)")
    o.add_argument("--one-based-ids", action="store_true",
                   help="print 1-based neighbor ids (reference parity)")
    o.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace for TensorBoard/XProf")
    o.add_argument("--checkpoint-dir", default=None,
                   help="round-granular checkpoint/resume state directory; "
                   "ring backends checkpoint the sharded carry per ring "
                   "round, serial per corpus-tile round")
    o.add_argument("--save-every", type=int, default=None,
                   help="checkpoint cadence: corpus tiles for the serial "
                   "path (default 8), ring rounds for ring backends "
                   "(default 1 — a ring has only as many rounds as devices)")
    o.add_argument("-q", "--quiet", action="store_true")
    o.add_argument("-v", "--verbose", action="count", default=0,
                   help="-v: INFO (phase/checkpoint events, per-host "
                   "prefixed), -vv: DEBUG (per-round progress)")
    o.add_argument("--recall-sample", type=int, default=256, metavar="N",
                   help="query sample size for --recall-vs-serial "
                   "(0 = all queries; default 256)")
    o.add_argument("--recall-vs-serial", action="store_true",
                   help="also run the serial backend and report recall@k of "
                   "the selected backend against it (the acceptance gate, "
                   "BASELINE.md)")
    o.add_argument("--platform", choices=["auto", "cpu", "tpu"], default="auto",
                   help="force a JAX platform; 'tpu' fails at start-up when "
                   "no TPU comes up, 'auto' takes whatever jax finds")
    return p


def load_corpus(spec: str, limit=None):
    """Resolve a corpus spec ('mnist', 'digits', 'synthetic:MxDcC',
    'sift:M', *.fvecs/bvecs, or a .mat path) to (X, labels_or_None,
    source). Shared by the run driver and the ``query`` serving
    subcommand (serve/cli.py)."""
    m = re.fullmatch(r"synthetic:(\d+)x(\d+)(?:c(\d+))?", spec)
    if m:
        from mpi_knn_tpu.data.synthetic import make_blobs

        rows, dim, classes = int(m[1]), int(m[2]), int(m[3] or 10)
        X, y = make_blobs(rows, dim, num_classes=classes, seed=0)
        return X, y, spec
    m = re.fullmatch(r"sift:(\d+)", spec)
    if m:
        from mpi_knn_tpu.data.synthetic import make_sift_like

        return make_sift_like(m=int(m[1])), None, spec
    if spec == "mnist":
        from mpi_knn_tpu.data.mnist import load_mnist

        X, y, src = load_mnist(m=limit or 60000)
        return X, y, f"mnist({src})"
    if spec == "digits":
        from mpi_knn_tpu.data.digits import load_digits

        X, y = load_digits()
        if limit:
            X, y = X[:limit], y[:limit]
        return X, y, "digits(real)"
    if spec.endswith((".fvecs", ".bvecs")):
        from mpi_knn_tpu.data.vecs import read_vecs

        try:
            return read_vecs(spec, limit=limit), None, spec
        except (FileNotFoundError, ValueError) as e:
            raise SystemExit(f"error: {e}")
    from mpi_knn_tpu.data.matfile import load_corpus_mat

    try:
        X, y = load_corpus_mat(spec, limit=limit)
    except FileNotFoundError:
        raise SystemExit(
            f"error: --data {spec!r} is not a file, 'mnist', a "
            "synthetic:MxDcC spec, or a sift:M spec"
        )
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    return X, y, spec


def _load_data(args):
    """Returns (X, labels_or_None, source)."""
    return load_corpus(args.data, limit=args.limit)


def _load_queries(path):
    if path.endswith(".npy"):
        return np.load(path)
    if path.endswith((".fvecs", ".bvecs")):
        from mpi_knn_tpu.data.vecs import read_vecs

        try:
            return read_vecs(path)
        except (FileNotFoundError, ValueError) as e:
            raise SystemExit(f"error: {e}")
    from mpi_knn_tpu.data.matfile import read_mat

    data = read_mat(path)
    for name in ("queries", "train_X"):
        if name in data:
            return data[name].astype(np.float32)
    raise SystemExit(f"{path}: no queries/train_X variable")


def _to_host(a) -> np.ndarray:
    """Fetch a result array to host numpy (multi-host gather handled by
    parallel.distributed.fetch_global — one implementation)."""
    from mpi_knn_tpu.parallel.distributed import fetch_global

    return fetch_global(a)


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "lint":
        # static-analysis subcommand: lowers every backend's program on
        # CPU and runs the HLO rule engine (mpi_knn_tpu.analysis). Routed
        # before the run parser so the two flag namespaces stay disjoint.
        from mpi_knn_tpu.analysis.cli import main as lint_main

        return lint_main(argv[1:])
    if argv and argv[0] == "query":
        # query-serving subcommand: build a device-resident CorpusIndex
        # and stream query batches through the bucketed AOT executable
        # cache (mpi_knn_tpu.serve). Same routing pattern as lint.
        from mpi_knn_tpu.serve.cli import main as query_main

        return query_main(argv[1:])
    if argv and argv[0] == "build-index":
        # clustered-index subcommand: train the k-means partitioner and
        # save an IVF index (.npz) for `query --index-load`
        # (mpi_knn_tpu.ivf). Same routing pattern as lint/query.
        from mpi_knn_tpu.ivf.cli import main as build_index_main

        return build_index_main(argv[1:])
    if argv and argv[0] == "metrics":
        # observability subcommand: render/check metrics snapshots and
        # span flight records (mpi_knn_tpu.obs) — jax-free, so it works
        # in supervisor processes and shell pipelines. Same routing
        # pattern as lint/query/build-index.
        from mpi_knn_tpu.obs.cli import main as metrics_main

        return metrics_main(argv[1:])
    if argv and argv[0] == "serve":
        # serving front-end subcommand: async request coalescing + SLO
        # admission over a ServeSession behind a thin multi-tenant HTTP
        # server (mpi_knn_tpu.frontend). Same routing pattern as query.
        from mpi_knn_tpu.frontend.cli import serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "loadgen":
        # open-loop multi-tenant load generator against a running
        # `mpi-knn serve` — throughput-vs-p50/p99 rows (jax-free client).
        from mpi_knn_tpu.frontend.cli import loadgen_main

        return loadgen_main(argv[1:])
    if argv and argv[0] == "router":
        # replicated serving tier (ISSUE 18): a jax-free router fronting
        # N `mpi-knn serve` replicas — health-gated membership, tenant-
        # affine spread, sequenced mutation fan-out, optional supervised
        # replica spawning. Same routing pattern as serve/loadgen.
        from mpi_knn_tpu.frontend.cli import router_main

        return router_main(argv[1:])
    if argv and argv[0] == "mutate":
        # live-mutation subcommand (ISSUE 14): upsert/delete/compact a
        # saved index artifact offline, or POST mutations to a running
        # `mpi-knn serve` front end. Same routing pattern as query.
        from mpi_knn_tpu.serve.mutate_cli import main as mutate_main

        return mutate_main(argv[1:])
    if argv and argv[0] == "plan":
        # capacity-planner subcommand (ISSUE 16): invert the committed
        # R7/R8 ledgers + bench calibration into a serving configuration
        # for a given corpus/recall/QPS/fleet, or refuse with the named
        # binding constraint (exit 2). jax-free — answers on any host.
        from mpi_knn_tpu.plan import main as plan_main

        return plan_main(argv[1:])
    if argv and argv[0] == "doctor":
        # preflight device-health subcommand: tiny jit + device_sync in a
        # heartbeat-supervised subprocess (mpi_knn_tpu.resilience), JSON
        # verdict on stdout, exit 0/1 — usable by operators before a
        # serving run and by bench (BENCH_DOCTOR=1). Same routing
        # pattern as lint/query/build-index.
        from mpi_knn_tpu.resilience.doctor import main as doctor_main

        return doctor_main(argv[1:])
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.save_every is not None and args.save_every <= 0:
        parser.error("--save-every must be a positive round count")

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        force_platform(args.platform)
    use_compile_cache()

    from mpi_knn_tpu.utils.logs import log, setup_logging

    setup_logging(args.verbose, quiet=args.quiet)

    import os

    if args.process_id is not None and not (
        args.coordinator or args.num_processes
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("JAX_NUM_PROCESSES")
    ):
        raise SystemExit(
            "error: --process-id requires --coordinator/--num-processes "
            "(or the JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES env vars); "
            "refusing to silently run single-host"
        )
    if (
        args.coordinator
        or args.num_processes
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("JAX_NUM_PROCESSES")
    ):
        from mpi_knn_tpu.parallel.distributed import init_multihost

        dist_info = init_multihost(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    else:
        dist_info = None

    from mpi_knn_tpu.api import all_knn, knn_classify, resolve_backend
    from mpi_knn_tpu.utils.report import RunReport
    from mpi_knn_tpu.utils.timing import PhaseTimer, profile_trace

    timer = PhaseTimer()
    with timer.phase("load"):
        X, labels, source = _load_data(args)
        log.info("loaded %s: shape=%s labels=%s", source, X.shape,
                 labels is not None)
        if args.limit:
            X = X[: args.limit]
            labels = labels[: args.limit] if labels is not None else None

    if args.dtype == "uint8" and args.checkpoint_dir:
        raise SystemExit(
            "error: --dtype uint8 with --checkpoint-dir: the resumable "
            "drivers re-tile a float corpus every round and keep no byte "
            "stack — drop one of the two")
    cfg = KNNConfig(
        k=args.k,
        metric=args.metric,
        backend=args.backend,
        num_classes=args.num_classes,
        tie_break=args.tie_break,
        query_tile=args.query_tile,
        corpus_tile=args.corpus_tile,
        dtype=args.dtype,
        precision_policy=args.precision_policy,
        topk_method=args.topk_method,
        topk_block=args.topk_block,
        merge_schedule=args.merge_schedule,
        ring_schedule=args.ring_schedule,
        ring_transfer_dtype=args.ring_transfer_dtype,
        exclude_zero=not args.include_zero_dist,
        exclude_self=not args.include_self,
        num_devices=args.devices,
    )

    queries = _load_queries(args.queries) if args.queries else None

    if args.svd:
        from mpi_knn_tpu.data.svd import svd_reduce

        with timer.phase("svd"):
            X_red, comps, mu = svd_reduce(X, args.svd)
            timer.block_on(X_red)
            X = np.asarray(X_red)
            if queries is not None:
                # project queries into the same principal subspace
                queries = (queries - np.asarray(mu)) @ np.asarray(comps)

    mesh = None
    if args.dp and args.dp > 1:
        import jax

        from mpi_knn_tpu.parallel.mesh import make_mesh2d

        if args.backend not in ("ring", "ring-overlap", "auto"):
            raise SystemExit(
                f"error: --dp requires a ring backend (got --backend "
                f"{args.backend}; serial ignores the mesh)"
            )
        if args.backend == "ring":
            # VERDICT r5 weak #3: on a dp×ring mesh the blocking barrier can
            # pin only the rotating block, so the "blocking" schedule would
            # silently run as the overlap schedule. Refuse at the flag level
            # (the backends raise the same error) — the 1-D ring is the only
            # defined blocking A/B object.
            raise SystemExit(
                "error: --dp with --backend ring (the blocking schedule) is "
                "undefined: the compute-then-send barrier cannot be "
                "expressed on a dp×ring mesh, so the run would silently use "
                "the overlap schedule. The 1-D ring is the only defined "
                "blocking A/B object — use --backend ring-overlap with "
                "--dp, or drop --dp."
            )
        total = args.devices or len(jax.devices())
        if total % args.dp:
            raise SystemExit(
                f"error: --dp {args.dp} must divide the device count {total}"
            )
        mesh = make_mesh2d(args.dp, total // args.dp)

    report = RunReport(
        config=vars(args),
        data_source=source,
        shape=tuple(X.shape),
        backend=resolve_backend(cfg),
        num_devices=cfg.num_devices or 1,
    )
    if dist_info is not None:
        report.notes["distributed"] = dist_info

    with profile_trace(args.profile):
        with timer.phase("knn"):
            if args.checkpoint_dir:
                from mpi_knn_tpu.types import KNNResult

                q_arr = queries if queries is not None else X
                q_ids = (
                    np.full(len(q_arr), -1, np.int32)
                    if queries is not None
                    else np.arange(len(X), dtype=np.int32)
                )
                resolved = resolve_backend(cfg, mesh)
                if resolved in ("ring", "ring-overlap"):
                    # distributed resume: carry checkpointed per ring round
                    from mpi_knn_tpu.backends.ring_resumable import (
                        all_knn_ring_resumable,
                    )

                    d, i = all_knn_ring_resumable(
                        X, q_arr, q_ids, cfg,
                        mesh=mesh,
                        overlap=(resolved == "ring-overlap"),
                        checkpoint_dir=args.checkpoint_dir,
                        save_every=(1 if args.save_every is None
                                    else args.save_every),
                    )
                else:
                    from mpi_knn_tpu.backends.resumable import (
                        all_knn_resumable,
                    )

                    d, i = all_knn_resumable(
                        X, q_arr, q_ids, cfg,
                        checkpoint_dir=args.checkpoint_dir,
                        save_every=(8 if args.save_every is None
                                    else args.save_every),
                    )
                result = KNNResult(dists=d, ids=i)
            else:
                result = all_knn(X, queries=queries, config=cfg, mesh=mesh)
            timer.block_on(result.dists)

        do_vote = labels is not None and (args.loo or queries is None)
        cls = None
        if do_vote:
            with timer.phase("vote"):
                cls = knn_classify(
                    result, labels, num_classes=args.num_classes,
                    tie_break=args.tie_break,
                )
                timer.block_on(cls.predictions)
            if queries is None:
                preds = _to_host(cls.predictions)
                report.matches = int((preds == np.asarray(labels)[: len(preds)]).sum())
                report.total = int(len(labels))
                report.accuracy = report.matches / report.total
            else:
                # query mode: the predictions ARE the output
                preds = _to_host(cls.predictions)
                report.notes["predictions"] = preds.tolist()

    if args.recall_vs_serial:
        if report.backend == "serial" or (
            args.checkpoint_dir
            and report.backend not in ("ring", "ring-overlap")
        ):
            # comparing serial math against itself is vacuous (the
            # non-ring checkpoint/resume driver runs the serial path); make
            # that visible instead of reporting a hollow 1.0 for a backend
            # that never ran. Ring backends DO run ring math under
            # --checkpoint-dir (ring_resumable), so those compare for real.
            report.recall_vs_baseline = 1.0
            if not args.quiet:
                why = ("resumable runs serial math"
                       if args.checkpoint_dir else "selected backend IS serial")
                print(f"recall-vs-serial: {why} (trivially 1.0); pick "
                      "--backend ring/ring-overlap to compare")
        else:
            from mpi_knn_tpu.utils.report import recall_at_k

            # sample the gate (default 256 queries, bench.py's pattern):
            # a full-corpus baseline + full id fetch at SIFT scale proves
            # nothing more than the sample does
            nq_total = int(result.ids.shape[0])
            ns = args.recall_sample
            full = ns <= 0 or ns >= nq_total
            sample = (
                np.arange(nq_total, dtype=np.int64)
                if full
                else np.linspace(0, nq_total - 1, num=ns, dtype=np.int64)
            )
            with timer.phase("recall_baseline"):
                # the baseline must be EXACT serial ground truth — inheriting
                # an approx topk_method would let shared approximation error
                # cancel and overstate recall
                base_cfg = cfg.replace(backend="serial", topk_method="exact")
                if queries is None and full:
                    # all-pairs baseline as-is; a sample == arange copy of
                    # the corpus would upload the whole corpus twice
                    base = all_knn(X, config=base_cfg)
                elif queries is None:
                    # all-pairs mode: sampled rows keep their corpus identity
                    # so self-exclusion matches the full run
                    base = all_knn(
                        X,
                        queries=np.asarray(X)[sample],
                        query_ids=sample,
                        config=base_cfg,
                    )
                else:
                    base = all_knn(
                        X, queries=np.asarray(queries)[sample], config=base_cfg
                    )
                timer.block_on(base.dists)
            got = _to_host(result.ids[sample])
            report.recall_vs_baseline = recall_at_k(got, _to_host(base.ids))
            report.notes["recall_sample"] = int(len(sample))

    report.phase_seconds = dict(timer.seconds)

    if not args.quiet:
        # reference-parity lines (knn-serial.c:98,130) plus a real summary
        print(f"Clock time = {timer.seconds['knn']:.6f}")
        if report.matches is not None:
            print(f"Matches: {report.matches}")
        if cls is not None and queries is not None:
            print(f"predictions ({len(preds)} queries): {preds[:20].tolist()}"
                  + (" ..." if len(preds) > 20 else ""))
        print(
            f"[mpi_knn_tpu] backend={report.backend} shape={report.shape} "
            f"k={args.k} metric={args.metric} "
            + (f"accuracy={report.accuracy:.4f} " if report.accuracy is not None else "")
            + (
                f"recall-vs-serial={report.recall_vs_baseline:.4f} "
                if report.recall_vs_baseline is not None
                else ""
            )
            + f"knn={timer.seconds['knn']:.3f}s"
        )
        if args.one_based_ids:
            ids = _to_host(result.one_based())
            print("neighbor ids (1-based, first 5 queries):")
            print(ids[:5])

    if args.save_neighbors:
        out = {
            "dists": _to_host(result.dists),
            "ids": _to_host(result.ids),
        }
        if cls is not None:
            out["predictions"] = _to_host(cls.predictions)
        # np.savez appends .npz itself when absent; normalize so the
        # printed path names the file that actually exists
        nn_path = args.save_neighbors
        if not nn_path.endswith(".npz"):
            nn_path += ".npz"
        np.savez(nn_path, **out)
        if not args.quiet:
            print(f"neighbors written to {nn_path}")

    if args.report:
        report.save(args.report)
        if not args.quiet:
            print(f"report written to {args.report}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
