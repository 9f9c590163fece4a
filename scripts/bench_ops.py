"""Op-level microbenchmarks: the two primitives that own the all-kNN
budget, timed in isolation so a per-op perf trajectory exists even when the
full driver bench watchdogs (BENCH_WATCHDOG_S fires on a wedged device
transport and reports only the timeout).

Two families, one JSON artifact:

- ``pairwise_sq_l2`` at each precision configuration: the three explicit
  dot precisions (``default``/``high``/``highest``) plus the two
  ``precision_policy`` pipelines — ``policy-exact`` (one HIGHEST pass +
  exact top-k, the library default end to end) and ``policy-mixed`` (the
  compress-and-rerank two-pass pipeline, ops/rerank.py) — so the mixed
  policy's headline claim (compress FLOPs at single-pass rate buying back
  the HIGHEST multi-pass cost) is measurable per-op. The policy rows time
  distance+selection together (the policy changes where selection work
  happens, so distance-only timings of it would mislead); the bare
  precision rows time the distance tile alone.
- ``smallest_k`` at each method (``exact``/``approx``/``approx-rerank``/
  ``block``/``bf16``) over a fixed pre-computed distance tile.
- ``ring_allknn``: the ring-schedule 2×2 (uni vs bidir × blocking/overlap)
  end to end on a virtual CPU mesh (``--ring-devices``, default 8; 0
  disables the rows). The cells measure schedule mechanics (collectives
  are memcpys), pinning the per-PR trajectory. This script is CPU-only
  and refuses any other platform: its parent process runs jax and then
  spawns the cold-start children, which on an accelerator would find the
  device already held.
- ``query_knn``: steady-state serving throughput over a resident
  ``CorpusIndex`` (``mpi_knn_tpu.serve``) at three row buckets — per-batch
  p50/p99 latency and queries/sec, measured strictly AFTER warm-up so the
  rows pin the recompile-free steady state the engine promises (the
  compile-free property itself is gated in tests/test_serve.py; these
  rows pin its speed).
- ``ring_xfer`` / ``ivf_at_rest``: the COMPRESSION AXIS (ISSUE 9) —
  the ring at each transfer level (f32/bf16/int8, one mixed policy so
  rows differ only in wire bytes) and the clustered store at each
  at-rest level (f32/bf16/int8/int4, fixed probe count), every row
  carrying the measured recall@k (and resident bytes for at-rest) so
  the 2×/4×/8× cuts are committed NEXT TO what they pay — the
  bytes-vs-recall ladder DESIGN.md tabulates is generated here.
- ``frontend_qps`` / ``frontend_seq_baseline``: the serving FRONT END
  (``mpi_knn_tpu.frontend``, ISSUE 11) — open-loop multi-tenant load
  through the request coalescer at two tenant counts × an offered-QPS
  sweep, each row carrying p50/p99 and achieved rows/s, next to the
  per-stream depth-1 sequential-dispatch baseline over the SAME index
  (each lone 16-row request padding to the full bucket — the pad waste
  coalescing reclaims). The acceptance ratio (coalesced ≥ 2× sequential
  at an equal p99 bound) is gated in tests/test_frontend_serve.py; these
  rows pin its size per PR.
- ``router_qps``: the REPLICATED serving tier (ISSUE 18) — one offered
  load (330 req/s, 12 tenants) against a single MODELED replica direct
  (no router: the proxy-overhead baseline), then the health-gated
  router at 1/2/3 replicas. Modeled service (frontend/modelreplica.py:
  capacity spent sleeping, the real wire protocol) because the 1-CPU CI
  host can run three of those concurrently where three real jax
  replicas would time-slice one core; the ≥2.5× n=3/n=1 acceptance bar
  is gated in tests/test_router.py — these rows pin its size per PR.
- ``kmeans`` / ``ivf_query``: the clustered-index path (``mpi_knn_tpu.
  ivf``) on a SIFT-shaped corpus (uniform random data is clusterless and
  would only measure the method failing its preconditions) — one k-means
  training-time row (the single-executable Lloyd trainer), then
  steady-state probed serving at nprobe ∈ {1, 4, 16} with p50/p99/qps
  AND the measured recall@k vs a local f64 oracle on each row: the
  sublinear speedup and the recall it buys are one artifact, so a probe
  count can never look fast without showing what it paid.

- ``ivf_mutation``: the LIVE-MUTATION path (ISSUE 14) — steady-state
  upsert and delete rows/s through the warm mutation executables
  (freelist plan + donated in-place scatter), query p99 DURING sustained
  background churn next to the quiesced p99 on the same session (the 2×
  acceptance bound), one compact-pass wall time, and the comparison row
  the tentpole is measured against: rebuild-per-batch (full k-means
  retrain + build per mutation batch — the pre-PR "mutation"), in rows/s
  over the same batch so the ≥10× bar reads directly off the artifact.

- ``--scan-step`` (alone, on whatever platform jax has — on the chip: one
  process, no children): the SCAN micro-benchmark (PERF.md §6, PRs 35, 37
  and 40: the go / no-go number of a change to the tile step). One
  ``merge_tiles_into_carry`` of a ``--q`` x ``--d`` query tile over
  ``--tiles`` tiles of ``--c`` clustered whole-number rows under a true
  one-pass verdict, as the scan of XLA tile steps (``backends/serial.py
  fused_rule`` answering None) and as the program the rule gives (the
  kernel that walks the stack, where it engages), answers asserted equal
  bit for bit and the kernel's chunk count equal to the bounded scan's;
  rows ``scan_step`` with ``us_per_step`` = median / tiles. Where the
  certified screen engages at the shapes (``--d`` on the lane grid), two
  more rows over the same law's FRACTIONAL rows at ``highest``: ``screen``
  (the XLA scan of three-pass tile steps) and ``fused_screen`` (the
  rule's program: the kernel's three-pass form where
  ``fused_screen_rule`` engages), answers asserted equal. With ``--tags
  <a filtered configuration's file>`` (``benchmark/configs/
  yfcc10m-192-l2-filter.json``) the MASKED scan instead (PERF.md §6, PR
  55), at that cell's own law: rows and bags of its generator
  (``benchmark/datagen/clustered_u8_tags.py``, the file's ``data``), the
  threshold ``serve/tags.py`` derives, and a query tile of the first
  ``--q`` rows of the cell's pool that the plan sends to the scan regime,
  with their words made inside the timed program (``filter_words``); rows
  ``scan`` (the masked XLA steps), ``rule`` (the rule's program: the
  kernel with the predicate inside) and ``rule_no_sift`` (the kernel with
  the bit left out of the test beside its dot), lists and chunk counts
  asserted equal, the tile's matched share of the slots beside them.

CPU numbers say nothing absolute about the TPU — what they pin is the
RELATIVE trajectory per op across PRs, on the platform CI always has
(the same rationale as ring_scaling_cpu.py). On a real chip the same
script measures the real thing.

Usage::

    python scripts/bench_ops.py [--out measurements/bench_ops.json]
        [--q 1024] [--c 8192] [--d 784] [--k 10] [--reps 5]

Output: one JSON document with environment metadata and a ``results`` list
of ``{op, variant, median_s, min_s, reps_s}`` rows.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import statistics
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _time(fn, reps: int):
    """Median/min wall-clock of ``fn`` (jitted; first call compiles and is
    discarded). ``fn`` must return a device array to synchronize on."""
    fn().block_until_ready()  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    return times


def _cold_start_child(spec: dict) -> int:
    """One fresh-process cold-start measurement (the ``cold_start`` rows'
    child body): build the index, warm the full ladder through the
    persistent AOT cache at ``spec["cache_dir"]``, serve one batch, and
    print a single JSON line with the wall times and the warm report.
    Run twice against one cache dir by the parent: the first call IS the
    cold start, the second the populated-cache start — fresh processes,
    so the in-memory caches can never flatter the numbers."""
    import numpy as np

    from mpi_knn_tpu.utils.platform import force_platform

    force_platform("cpu", n_devices=spec["devices"])

    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession, aotcache, build_index

    aotcache.set_cache_dir(spec["cache_dir"])
    rng = np.random.default_rng(0)
    d, k = spec["d"], spec["k"]
    if spec["backend"] == "serial":
        X = rng.standard_normal((spec["m"], d)).astype(np.float32)
        index = build_index(
            X, KNNConfig(k=k, query_bucket=128, corpus_tile=2048)
        )
    else:
        from mpi_knn_tpu.ivf import build_ivf_index, shard_ivf_index

        cents = rng.standard_normal((16, d)).astype(np.float32) * 4
        assign = rng.integers(0, 16, size=spec["m"])
        X = (cents[assign]
             + rng.standard_normal((spec["m"], d))).astype(np.float32)
        index = shard_ivf_index(
            build_ivf_index(
                X, KNNConfig(k=k, partitions=16, nprobe=4,
                             query_bucket=128)
            ),
            shards=spec["devices"],
        )
    # the default-policy ladder (full → [nprobe/2 →] mixed → bucket/2)
    # is the production serve CLI's warm set: several distinct cells,
    # with the dedupe visible in the report
    sess = ServeSession(index, resilience=ResiliencePolicy())
    t0 = time.perf_counter()
    rep = sess.warm([128, 256])
    warm_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    batch = X[:128]
    sess.submit(batch)
    done = sess.drain()
    _ = done[0].dists  # materialized on host — the honest first result
    first_result_s = time.perf_counter() - t1
    print(json.dumps({
        "warm_s": round(warm_s, 4),
        "first_result_s": round(first_result_s, 4),
        **rep,
    }))
    return 0


def _masked_scan_step(args) -> int:
    """The ``--scan-step --tags`` rows (the module docstring has what they
    are)."""
    import types
    import unittest.mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.datagen import clustered_u8_tags as gen
    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.ops import fused_scan as kernel
    from mpi_knn_tpu.ops.distance import sq_norms
    from mpi_knn_tpu.serve.tags import build_tag_index

    q, c, d, tiles = args.q, args.c, args.d, args.tiles
    config = json.loads(pathlib.Path(args.tags).read_text())
    spec = config["data"]
    cfg = KNNConfig(**{**config["knn"], "backend": "serial", "query_tile": q,
                       "corpus_tile": c})
    k, rows = cfg.k, tiles * c
    which, indptr, indices, _ = gen.bags(rows, spec)
    cen = jnp.asarray(gen.centres(0, spec, d))

    @jax.jit
    def make(key, which):
        # ``gen.device_corpus``'s law (row i around centre ``which[i]``,
        # rounded and clipped to a byte), centred by a whole number — bf16
        # numbers — and made a tile at a time into the stack's own shape:
        # no (rows, d) array beside it
        def tile(args):
            i, w = args
            x = cen[w] + float(spec["sigma"]) * jax.random.normal(
                jax.random.fold_in(key, i), (c, d), jnp.float32)
            return jnp.clip(jnp.rint(x), 0.0, 255.0) - 128.0

        return jax.lax.map(tile, (jnp.arange(tiles), which.reshape(tiles, c)))

    stack = make(jax.random.key(0, impl="rbg"), jnp.asarray(which))
    tags = build_tag_index(
        types.SimpleNamespace(cfg=cfg, m=rows, tiles=stack, dim=d),
        (indptr, indices), row_major_copy=False)
    pool, filters = gen.query_pool(0, 8 * q, spec, d, which, indptr, indices)
    plan = tags.plan(filters)
    if len(plan.scan_rows) < q:
        raise SystemExit(f"the pool's scan regime holds {len(plan.scan_rows)}"
                         f" rows of {8 * q}: fewer than --q")
    mine = plan.scan_rows[:q]
    matched = gen.match_counts(indptr, indices, filters[mine]) / rows
    ids = jnp.arange(rows, dtype=jnp.int32).reshape(tiles, c)
    operands = (jnp.asarray(pool[mine] - 128.0), jnp.full(q, -1, jnp.int32),
                stack, ids, serial._stack_norms(stack, "l2"), tags.tag_bits,
                jnp.asarray(plan.scan_tags[:q]), jnp.asarray(True))

    def program(module=None, **patched):
        """The merge's program, lowered with ``module``'s names replaced
        by ``patched`` (none: the rules' own program)."""
        with (unittest.mock.patch.multiple(module, **patched) if module
              else contextlib.nullcontext()):

            @jax.jit
            def run(q_x, q_ids, stack, ids, sqs, tag_bits, q_tags, onepass):
                return serial.merge_tiles_into_carry(
                    q_x, q_ids, sq_norms(q_x), stack, ids, sqs,
                    *serial.init_topk(q, k), cfg, onepass,
                    serial.filter_words(tag_bits, q_tags))

            return run.lower(*operands).compile()

    whole = kernel.fused_scan
    variants = {
        "scan": program(serial, fused_rule=lambda *a, **kw: None),
        "rule": program(),
        "rule_no_sift": program(
            kernel, fused_scan=lambda *a, **kw: whole(*a, **kw, sift=False)),
    }
    block = serial.fused_rule(cfg, q, c, d, filtered=True)
    results, outs = [], {}
    for name, run in variants.items():
        times = _time(lambda: run(*operands)[0], args.reps)
        outs[name] = out = jax.tree.map(np.asarray, run(*operands))
        med = statistics.median(times)
        results.append({
            "op": "masked_scan_step", "variant": name, "q": q, "c": c,
            "d": d, "tiles": tiles, "block": None if name == "scan" else block,
            "median_s": round(med, 6), "min_s": round(min(times), 6),
            "us_per_step": round(med / tiles * 1e6, 2),
            "rescanned": bool(out[2]), "chunks": out[3].tolist(),
        })
        print(json.dumps(results[-1]), flush=True)
    for name in ("rule", "rule_no_sift"):
        for what, a, b in zip(("vals", "ids", "rescanned", "chunks"),
                              outs["scan"], outs[name]):
            np.testing.assert_array_equal(a, b, err_msg=f"{name}: {what}")
    doc = {"device": str(jax.devices()[0].device_kind),
           "platform": jax.default_backend(), "equal": True,
           "threshold": tags.threshold, "bitsets": tags.n_bitsets,
           "matched_share": {"min": float(matched.min()),
                             "median": float(np.median(matched)),
                             "max": float(matched.max())},
           "results": results}
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=1) + "\n")
    return 0


def _scan_step(args) -> int:
    """The ``--scan-step`` rows (the module docstring has what they are)."""
    if args.tags:
        return _masked_scan_step(args)
    import unittest.mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_knn_tpu.backends import serial
    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.ops.distance import sq_norms

    q, c, d, k, tiles = args.q, args.c, args.d, args.k, args.tiles
    cfg = KNNConfig(k=k, backend="serial", matmul_precision="high",
                    query_tile=q, corpus_tile=c, exclude_self=True)

    @functools.partial(jax.jit, static_argnames=("fractional",))
    def make(key, fractional=False):
        # MNIST-shaped rows (benchmark/datagen/clustered_u8.py's law: a
        # class centre plus noise, whole numbers in [0, 255]), centred by
        # a whole number, a tile at a time: no transient beside the stack
        # (``fractional``: the same law's rows as they are drawn)
        cen = jnp.rint(jax.random.uniform(key, (10, d)) * 255.0)

        def tile(i):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            x = cen[jax.random.randint(k1, (c,), 0, 10)] + 25.0 * (
                jax.random.normal(k2, (c, d), jnp.float32))
            if fractional:
                return x - 128.0
            return jnp.clip(jnp.rint(x), 0.0, 255.0) - 128.0

        return jax.lax.map(tile, jnp.arange(tiles))

    ids = jnp.arange(tiles * c, dtype=jnp.int32).reshape(tiles, c)

    def operands_of(stack, *verdict):
        # the query tile is rows of the corpus under their own ids, as a
        # slice of the all-pairs job is
        return (stack[tiles // 2, :q], ids[tiles // 2, :q], stack, ids,
                serial._stack_norms(stack, "l2"), *verdict)

    def program(cfg, operands, **rules):
        """The merge's program for ``operands``, lowered with ``serial``'s
        rules replaced by ``rules`` (none: the rules' own program)."""
        with (unittest.mock.patch.multiple(serial, **rules) if rules
              else contextlib.nullcontext()):
            @jax.jit
            def run(q_x, q_ids, stack, ids, sqs, *onepass):
                return serial.merge_tiles_into_carry(
                    q_x, q_ids, sq_norms(q_x), stack, ids, sqs,
                    *serial.init_topk(q, k), cfg, *onepass)

            return run.lower(*operands).compile()

    def no_kernel(*a, **kw):
        return None

    results, outs = [], {}

    def measure(variants, operands, blocks):
        for name, run in variants.items():
            times = _time(lambda: run(*operands)[0], args.reps)
            outs[name] = out = jax.tree.map(np.asarray, run(*operands))
            med = statistics.median(times)
            results.append({
                "op": "scan_step", "variant": name, "q": q, "c": c, "d": d,
                "tiles": tiles, "block": blocks.get(name),
                "median_s": round(med, 6), "min_s": round(min(times), 6),
                "us_per_step": round(med / tiles * 1e6, 2),
                "rescanned": bool(out[2]),
                "chunks": None if out[3] is None else out[3].tolist(),
                "screened": None if out[4] is None else out[4].tolist(),
            })
            print(json.dumps(results[-1]), flush=True)

    stack = make(jax.random.key(0, impl="rbg"))
    # (the verdict is an operand: a constant would fold the rule's
    # conditional out of the scan it is part of)
    operands = operands_of(stack, jnp.asarray(True))
    measure({"scan": program(cfg, operands, fused_rule=no_kernel),
             "rule": program(cfg, operands)},
            operands, {"rule": serial.fused_rule(cfg, q, c, d)})
    for name, a, b in zip(("vals", "ids", "rescanned"),
                          outs["scan"], outs["rule"]):
        np.testing.assert_array_equal(a, b, err_msg=name)
    if serial.fused_rule(cfg, q, c, d) and outs["scan"][3] is None:
        # the count's witness: the scan of tile steps under the bound
        bounded = jax.tree.map(np.asarray, program(
            cfg, operands, fused_rule=no_kernel,
            lane_bin_bound_rides=lambda *a: True)(*operands))
        for a, b in zip(bounded[:4], outs["rule"]):
            np.testing.assert_array_equal(a, b)

    # the SCREENED scan (ISSUE 51): the same law's rows left fractional, at
    # ``highest`` and with no one-pass verdict, as the XLA scan of
    # three-pass tile steps (``fused_screen_rule`` answering None) and as
    # the rule's program — the kernel's three-pass form where it engages.
    # Every returned distance is the six-pass finish's in both, so the
    # answers are asserted equal; the chunk counts may differ by the few
    # chunks whose screen values straddle a bound in the last bits.
    exact = cfg.replace(matmul_precision="highest")
    if serial.screen_rule(exact, q, c, d) is not None:
        del operands, stack  # the whole-number stack goes first
        stack = make(jax.random.key(0, impl="rbg"), fractional=True)
        operands = operands_of(stack)
        measure({"screen": program(exact, operands,
                                   fused_screen_rule=no_kernel),
                 "fused_screen": program(exact, operands)},
                operands, {"fused_screen": serial.fused_screen_rule(
                    exact, q, c, d)})
        for name, a, b in zip(("vals", "ids"),
                              outs["screen"], outs["fused_screen"]):
            np.testing.assert_array_equal(a, b, err_msg=name)
    doc = {"device": str(jax.devices()[0].device_kind),
           "platform": jax.default_backend(), "equal": True,
           "results": results}
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=1) + "\n")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="measurements/bench_ops.json")
    ap.add_argument("--q", type=int, default=1024)
    ap.add_argument("--c", type=int, default=8192)
    ap.add_argument("--d", type=int, default=784)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--ring-devices", type=int, default=8,
                    help="virtual CPU mesh size for the ring-schedule rows; "
                    "0 disables them (and the CPU forcing they need)")
    ap.add_argument("--cold-start-child", default=None,
                    help=argparse.SUPPRESS)  # JSON spec; see _cold_start_child
    ap.add_argument("--scan-step", action="store_true",
                    help="the scan micro-benchmark alone, on the platform "
                    "jax has (the chip)")
    ap.add_argument("--tiles", type=int, default=192,
                    help="corpus tiles of the --scan-step stack")
    ap.add_argument("--tags", default=None, metavar="CONFIG.json",
                    help="with --scan-step: the masked scan at the law of "
                    "this filtered configuration's data")
    args = ap.parse_args(argv)

    if args.scan_step:
        return _scan_step(args)

    if args.cold_start_child:
        # fresh-process measurement body — must run before any platform
        # forcing or jax initialization in THIS process
        return _cold_start_child(json.loads(args.cold_start_child))

    if args.ring_devices:
        # the ring rows need a multi-device mesh, which on a CPU host means
        # forcing the virtual-device platform BEFORE jax initializes
        from mpi_knn_tpu.utils.platform import force_platform

        force_platform("cpu", n_devices=args.ring_devices)

    import jax

    if jax.default_backend() != "cpu":
        print(
            f"bench_ops: refusing platform {jax.default_backend()!r} — "
            "this process holds the device and later spawns "
            "--cold-start-child processes; one process per chip. Run with "
            "JAX_PLATFORMS=cpu (every row here is a CPU trajectory row).",
            file=sys.stderr,
        )
        return 2
    import jax.numpy as jnp
    import numpy as np

    from mpi_knn_tpu.config import TOPK_METHODS, KNNConfig
    from mpi_knn_tpu.ops.distance import pairwise_sq_l2, sq_norms
    from mpi_knn_tpu.ops.rerank import compress_rerank_tile, mixed_applies
    from mpi_knn_tpu.ops.topk import mask_tile, smallest_k

    q, c, d, k, reps = args.q, args.c, args.d, args.k, args.reps
    rng = np.random.default_rng(0)
    # integer-pixel magnitudes, centered — the headline workload's regime,
    # where bf16 compression is genuinely lossy (see BASELINE.md precision
    # A/B); zero-noise data would flatter the mixed pipeline
    X = np.rint(rng.random((c, d)) * 255.0).astype(np.float32)
    X -= X.mean(axis=0)
    Q = jax.device_put(jnp.asarray(X[:q]))
    C = jax.device_put(jnp.asarray(X))
    q_ids = jnp.arange(q, dtype=jnp.int32)
    c_ids = jnp.arange(c, dtype=jnp.int32)
    q_sq = sq_norms(Q).block_until_ready()
    c_sq = sq_norms(C).block_until_ready()

    results = []

    # the committed peak-HBM ledger (ISSUE 15: artifacts/lint/
    # memory_ledger.json, regenerated by `mpi-knn lint --memory`) — the
    # serving rows carry the corresponding lint cell's certified peak
    # next to their throughput, so the trajectory artifact reads
    # bytes-vs-speed in one place. The figure is the LINT-shape cell's
    # (the certified program family), stamped with its cell label so
    # nobody mistakes it for this run's corpus shapes.
    def ledger_peak(cell_label):
        try:
            from mpi_knn_tpu.analysis.memory import (
                DEFAULT_LEDGER,
                load_ledger,
            )

            doc = load_ledger(REPO / DEFAULT_LEDGER)
        except Exception:
            doc = None
        if not doc:
            return {}
        cell = doc["cells"].get(cell_label)
        if cell is None:
            return {}
        return {"peak_hbm_bytes": cell["peak_bytes"],
                "peak_hbm_cell": cell_label}

    # R8's predicted q/s (ISSUE 16, committed artifacts/lint/
    # cost_ledger.json, regenerated by `mpi-knn lint --cost`) rides the
    # same convention: the LINT cell's roofline under the default
    # profile, stamped with its cell label — every bench round,
    # including the pending TPU round, auto-reports predicted-vs-
    # measured without new plumbing.
    def ledger_roofline(cell_label):
        try:
            from mpi_knn_tpu.analysis.cost import (
                DEFAULT_COST_LEDGER,
                load_cost_ledger,
            )

            doc = load_cost_ledger(REPO / DEFAULT_COST_LEDGER)
        except Exception:
            doc = None
        if not doc:
            return {}
        cell = doc["cells"].get(cell_label)
        if cell is None:
            return {}
        return {"predicted_qps": round(cell["roofline"]["qps"], 1),
                "roofline_cell": cell_label}

    def record(op, variant, times):
        row = {
            "op": op,
            "variant": variant,
            "median_s": round(statistics.median(times), 6),
            "min_s": round(min(times), 6),
            "reps_s": [round(t, 6) for t in times],
        }
        results.append(row)
        print(f"{op:16s} {variant:16s} median {row['median_s']}s", flush=True)

    # Every device array is an explicit jit ARGUMENT — a device array
    # captured in a jit closure is a compile-time constant, and XLA
    # constant-folds the whole benchmark body into the executable (observed:
    # a "7 µs" top-k that was really a table lookup).

    # -- distance tile at each explicit dot precision (tile only) ---------
    @functools.partial(jax.jit, static_argnames=("prec",))
    def dist_at(Q, C, qs, cs, prec):
        return pairwise_sq_l2(Q, C, x_sq=qs, y_sq=cs, precision=prec)

    for prec in ("default", "high", "highest"):
        record(
            "pairwise_sq_l2",
            f"precision-{prec}",
            _time(lambda: dist_at(Q, C, q_sq, c_sq, prec=prec), reps),
        )

    # -- the two precision POLICIES, distance + selection end to end ------
    exact_cfg = KNNConfig(k=k, query_tile=q, corpus_tile=c)
    mixed_cfg = exact_cfg.replace(precision_policy="mixed")
    if not mixed_applies(k, c):
        print(f"note: 4k={4 * k} >= c={c}; policy-mixed degenerates to "
              "exact at these shapes", file=sys.stderr)

    @jax.jit
    def policy_exact(Q, C, qs, cs, q_ids, c_ids):
        dist = pairwise_sq_l2(Q, C, x_sq=qs, y_sq=cs, precision=None)
        dist = mask_tile(dist, c_ids, query_ids=q_ids,
                         scale=qs[:, None] + cs[None, :])
        return smallest_k(dist, c_ids, k, method="exact")[0]

    @jax.jit
    def policy_mixed(Q, C, qs, cs, q_ids, c_ids):
        return compress_rerank_tile(
            Q, q_ids, qs, C, c_ids, cs, mixed_cfg
        )[0]

    for name, fn in (("policy-exact", policy_exact),
                     ("policy-mixed", policy_mixed)):
        record(
            "dist_topk_tile", name,
            _time(lambda: fn(Q, C, q_sq, c_sq, q_ids, c_ids), reps),
        )

    # -- smallest_k at every method over a fixed masked tile --------------
    dist_fixed = jax.jit(
        lambda Q, C, qs, cs, c_ids, q_ids: mask_tile(
            pairwise_sq_l2(Q, C, x_sq=qs, y_sq=cs),
            c_ids,
            query_ids=q_ids,
            scale=qs[:, None] + cs[None, :],
        )
    )(Q, C, q_sq, c_sq, c_ids, q_ids).block_until_ready()

    @functools.partial(jax.jit, static_argnames=("method",))
    def select(dist, c_ids, method):
        return smallest_k(dist, c_ids, k, method=method,
                          recall_target=0.95)[0]

    for method in TOPK_METHODS:
        record(
            "smallest_k", method,
            _time(lambda: select(dist_fixed, c_ids, method=method), reps),
        )

    # -- ring schedule 2×2: uni vs bidir × blocking/overlap ---------------
    if args.ring_devices:
        from mpi_knn_tpu import all_knn
        from mpi_knn_tpu.parallel.mesh import make_ring_mesh

        mesh = make_ring_mesh(args.ring_devices)
        # query subset over the full corpus: enough work per round for the
        # schedule difference to register, small enough that four cells add
        # seconds, not minutes, to the artifact
        n_ring_q = min(256, c)
        Qr = np.asarray(X[:n_ring_q])
        for sched in ("uni", "bidir"):
            for name, backend in (("blocking", "ring"),
                                  ("overlap", "ring-overlap")):
                rcfg = KNNConfig(k=k, backend=backend, ring_schedule=sched,
                                 query_tile=min(128, n_ring_q),
                                 corpus_tile=min(1024, c))
                record(
                    "ring_allknn", f"{sched}-{name}",
                    _time(
                        lambda: all_knn(
                            np.asarray(X), queries=Qr, config=rcfg, mesh=mesh
                        ).dists,
                        reps,
                    ),
                )

        # -- compression axis, transfer side (ISSUE 9): the ring at each
        # wire level under ONE policy (mixed — int8 requires the rerank,
        # and a policy change between rows would confound the byte
        # effect), with the measured recall@k each level pays riding the
        # row. Queries are HELD OUT (fresh rows from the same integer-
        # pixel distribution), NOT corpus rows: a corpus-row query's own
        # stored row sits at exactly zero distance only in the f32 cell —
        # a quantized store reconstructs it with noise, zero-exclusion
        # stops firing, and every quantized row would eat a spurious
        # self-hit the oracle excluded (a measurement artifact, not
        # recall).
        from mpi_knn_tpu.utils.report import recall_at_k

        # held-out = jittered corpus rows (already in the centered frame;
        # the jitter keeps every query strictly off the corpus so no
        # level sees an exact-zero match)
        Qh = (
            np.asarray(X[:n_ring_q])
            + np.random.default_rng(7)
            .normal(0.0, 2.0, (n_ring_q, d))
            .astype(np.float32)
        )
        X64o = np.asarray(X).astype(np.float64)
        od_x = (
            (Qh.astype(np.float64) ** 2).sum(1)[:, None]
            + (X64o**2).sum(1)[None, :]
            - 2.0 * (Qh.astype(np.float64) @ X64o.T)
        )
        oracle_x = np.argsort(od_x, axis=1, kind="stable")[:, :k]
        for xname, xfer in (("f32", None), ("bf16", "bfloat16"),
                            ("int8", "int8")):
            xcfg = KNNConfig(
                k=k, backend="ring-overlap", precision_policy="mixed",
                ring_transfer_dtype=xfer, exclude_zero=False,
                query_tile=min(128, n_ring_q), corpus_tile=min(1024, c),
            )
            res = all_knn(np.asarray(X), queries=Qh, config=xcfg, mesh=mesh)
            xrecall = recall_at_k(res.ids, oracle_x)
            times = _time(
                lambda: all_knn(
                    np.asarray(X), queries=Qh, config=xcfg, mesh=mesh
                ).dists,
                reps,
            )
            row = {
                "op": "ring_xfer",
                "variant": f"mixed-{xname}",
                "median_s": round(statistics.median(times), 6),
                "min_s": round(min(times), 6),
                "reps_s": [round(t, 6) for t in times],
                "recall_at_k": round(float(xrecall), 4),
            }
            results.append(row)
            print(f"{'ring_xfer':16s} {row['variant']:16s} "
                  f"median {row['median_s']}s  recall@{k} "
                  f"{row['recall_at_k']}", flush=True)

    # -- query_knn serving throughput at three buckets (resident index) ---
    from mpi_knn_tpu.serve import ServeSession, build_index

    serve_cfg = KNNConfig(k=k, backend="serial", query_tile=min(1024, q),
                          corpus_tile=min(8192, c), query_bucket=128)
    index = build_index(X, serve_cfg)
    for bucket in (128, 256, 512):
        if bucket > c:
            # no silent caps: a probe bucket wider than the corpus would
            # quietly re-measure the widest real bucket under a bigger
            # label (and warm an executable no batch ever uses)
            print(f"note: skipping query_knn bucket {bucket} > corpus "
                  f"rows {c}", file=sys.stderr)
            continue
        n_batches = max(reps, 4)
        batches = [X[(i * bucket) % max(1, c - bucket):][:bucket]
                   for i in range(n_batches)]
        session = ServeSession(index)
        session.warm([bucket])
        # one full warm cycle through the session so the steady-state
        # rows measure serving, not first-touch compilation
        session.submit(batches[0])
        session.drain()
        session.reset_stats()
        t0 = time.perf_counter()
        for b in batches:
            session.submit(b)
        session.drain()
        wall = time.perf_counter() - t0
        lats = sorted(session.latencies)
        row = {
            "op": "query_knn",
            "variant": f"serial-bucket{bucket}",
            "median_s": round(statistics.median(lats), 6),
            "min_s": round(min(lats), 6),
            "reps_s": [round(t, 6) for t in lats],
            "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
            # np.percentile, same estimator as serve/cli.py — at the
            # default rep count this is an interpolated tail, honest
            # about the small sample rather than one rank below p99
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
            "queries_per_s": round(session.queries_served / wall, 1),
            **ledger_peak("serial/l2/float32/serve"),
            **ledger_roofline("serial/l2/float32/serve"),
        }
        results.append(row)
        print(f"{'query_knn':16s} {row['variant']:16s} "
              f"median {row['median_s']}s  {row['queries_per_s']} q/s",
              flush=True)

    # -- serving front end: coalesced multi-tenant vs sequential dispatch -
    # (mpi_knn_tpu.frontend, ISSUE 11) over the SAME resident serial
    # index as the query_knn rows — the comparison isolates coalescing.
    # Open loop at two tenant counts × two offered per-tenant rates; the
    # sequential baseline serves the identical request population one
    # 16-row request at a time at dispatch depth 1.
    from mpi_knn_tpu.frontend import Frontend, SLOPolicy
    from mpi_knn_tpu.frontend import loadgen as fe_loadgen
    from mpi_knn_tpu.resilience import ResiliencePolicy

    fe_rows, fe_requests = 16, 12
    lo_fe, hi_fe = float(np.min(X)), float(np.max(X))
    seq_session = ServeSession(
        index, config=index.cfg.replace(dispatch_depth=1)
    )
    seq_session.submit(np.zeros((128, d), np.float32))
    seq_session.drain()
    seq_session.reset_stats()
    seq = fe_loadgen.run_sequential_baseline(
        seq_session, tenants=8, n_requests=fe_requests, rows=fe_rows,
        lo=lo_fe, hi=hi_fe,
    )
    row = {
        "op": "frontend_seq_baseline",
        "variant": f"t8-depth1-rows{fe_rows}",
        "median_s": round(statistics.median(
            sorted(seq_session.latencies)), 6) if seq_session.latencies
        else None,
        "min_s": round(min(seq_session.latencies), 6)
        if seq_session.latencies else None,
        "reps_s": [],
        "p50_ms": seq["p50_ms"],
        "p99_ms": seq["p99_ms"],
        "queries_per_s": seq["achieved_qps_rows"],
        "requests_per_s": seq["achieved_rps"],
    }
    results.append(row)
    print(f"{'frontend':16s} {row['variant']:20s} "
          f"{row['queries_per_s']} rows/s  p99 {row['p99_ms']}ms",
          flush=True)
    for fe_tenants in (2, 8):
        for fe_qps in (100.0, 2000.0):
            session = ServeSession(index, resilience=ResiliencePolicy())
            fe = Frontend(session, SLOPolicy(
                max_batch_rows=128, max_wait_s=0.002,
                max_queue_rows=65536,
            )).start()
            rep = fe_loadgen.run_inprocess(
                fe, tenants=fe_tenants, qps=fe_qps,
                n_requests=fe_requests, rows=fe_rows, lo=lo_fe, hi=hi_fe,
            )
            fe.stop()
            row = {
                "op": "frontend_qps",
                "variant": f"t{fe_tenants}-q{fe_qps:g}-rows{fe_rows}",
                "median_s": None,
                "min_s": None,
                "reps_s": [],
                "offered_qps_total": rep["offered_qps_total"],
                "p50_ms": rep["p50_ms"],
                "p99_ms": rep["p99_ms"],
                "queries_per_s": rep["achieved_qps_rows"],
                "requests_per_s": rep["achieved_rps"],
                "rejected": rep["rejected"],
            }
            results.append(row)
            print(f"{'frontend_qps':16s} {row['variant']:20s} "
                  f"{row['queries_per_s']} rows/s  p50 {row['p50_ms']}ms "
                  f"p99 {row['p99_ms']}ms", flush=True)

    # -- replicated serving tier (ISSUE 18): router scaling trajectory ----
    # The health-gated router (frontend/router.py) over MODELED replicas
    # (frontend/modelreplica.py: ``lanes`` service lanes of ``service_s``
    # each, capacity spent sleeping — the 1-CPU CI host can genuinely run
    # three of those concurrently, where three real jax replicas would
    # time-slice one core and the aggregate could never legitimately
    # exceed one replica's; the wire protocol is the real serve surface).
    # ONE offered load (330 req/s across 12 tenants, each replica capped
    # at 100 req/s) against: the single replica DIRECT — no router, the
    # proxy-overhead baseline — then the router at 1/2/3 replicas. The
    # n=3 vs n=1 ratio is the ISSUE 18 acceptance bar (>= 2.5x at the
    # p99 bound), gated in tests/test_router.py; these rows pin its size
    # per PR. Labeled modeled-service so nobody reads them as jax rows.
    from mpi_knn_tpu.frontend.modelreplica import ModelReplica
    from mpi_knn_tpu.frontend.router import (
        Router,
        RouterHTTPServer,
        RouterPolicy,
    )

    def _router_leg(n_replicas, via_router):
        reps_r = [ModelReplica(dim=8, k=3, service_s=0.01, lanes=1).start()
                  for _ in range(n_replicas)]
        router = srv = None
        try:
            if via_router:
                router = Router(
                    {f"r{i}": r.url for i, r in enumerate(reps_r)},
                    policy=RouterPolicy(probe_interval_s=0.05,
                                        rejoin_after=1,
                                        spill_queue_rows=2),
                ).start()
                if not router.wait_rotation(n_replicas, timeout_s=10):
                    raise RuntimeError("router rotation never filled")
                srv = RouterHTTPServer(router).start()
                url = srv.url
            else:
                url = reps_r[0].url
            return fe_loadgen.run_http(
                url, tenants=12, qps=330.0 / 12, n_requests=25, rows=4,
                timeout_s=30, connections=6,
            )
        finally:
            if srv is not None:
                srv.stop()
            if router is not None:
                router.stop()
            for r in reps_r:
                r.stop()

    router_rps = {}
    for variant, nrep, via in (("direct-1replica", 1, False),
                               ("router-1replica", 1, True),
                               ("router-2replicas", 2, True),
                               ("router-3replicas", 3, True)):
        leg = _router_leg(nrep, via)
        row = {
            "op": "router_qps",
            "variant": variant,
            "median_s": None,
            "min_s": None,
            "reps_s": [],
            "offered_rps": 330.0,
            "p50_ms": leg["p50_ms"],
            "p99_ms": leg["p99_ms"],
            "requests_per_s": leg["achieved_rps"],
            "queries_per_s": leg["achieved_qps_rows"],
            "errors": leg["errors"],
            "service_model": "modeled-1lane-10ms",
        }
        if via and nrep > 1 and "router-1replica" in router_rps:
            row["scaling_vs_router1"] = round(
                leg["achieved_rps"] / router_rps["router-1replica"], 2
            )
        router_rps[variant] = leg["achieved_rps"]
        results.append(row)
        extra = (f"  scaling {row['scaling_vs_router1']}x"
                 if "scaling_vs_router1" in row else "")
        print(f"{'router_qps':16s} {variant:20s} "
              f"{row['requests_per_s']} req/s  p99 {row['p99_ms']}ms"
              f"{extra}", flush=True)

    # -- clustered (IVF) path: kmeans train + probed serving vs recall ----
    # On a SIFT-shaped corpus — NOT the uniform-pixel tile above: uniform
    # random data in high dim is genuinely clusterless (neighbors spread
    # evenly over partitions), so IVF rows there would only ever measure
    # the method failing its preconditions. The clustered rows pin the
    # trajectory on the workload the index targets (the ANN-benchmarks
    # shape the paper evaluates), same rows, honest recall column.
    from mpi_knn_tpu.data.synthetic import make_sift_like
    from mpi_knn_tpu.ivf import build_ivf_index, search_ivf
    from mpi_knn_tpu.ivf.kmeans import kmeans as kmeans_fit
    from mpi_knn_tpu.utils.report import recall_at_k

    Xi = make_sift_like(m=c, d=128, seed=0).astype(np.float32)
    Ci = jax.device_put(jnp.asarray(Xi))
    P = max(2, min(64, c // 128))
    record(
        "kmeans", f"train-p{P}",
        _time(lambda: kmeans_fit(Ci, P, iters=10, seed=0).centroids, reps),
    )
    ivf_index = build_ivf_index(
        Xi, KNNConfig(k=k, partitions=P, nprobe=P, query_tile=min(1024, q),
                      query_bucket=128)
    )
    # f64 oracle for the measured-recall column: corpus rows as queries,
    # zero-distance self-hit excluded (the same rule the library applies)
    ns = min(256, c)
    sample = np.linspace(0, c - 1, num=ns, dtype=np.int64)
    Xs64 = Xi.astype(np.float64)
    od = (
        (Xs64[sample] ** 2).sum(1)[:, None]
        + (Xs64**2).sum(1)[None, :]
        - 2.0 * (Xs64[sample] @ Xs64.T)
    )
    od[od <= 1e-9] = np.inf
    oracle_ids = np.argsort(od, axis=1, kind="stable")[:, :k]
    for nprobe in (1, 4, 16):
        if nprobe > P:
            # no silent caps: a probe count beyond the partition count
            # would quietly re-measure the full scan under a smaller label
            print(f"note: skipping ivf_query nprobe {nprobe} > partitions "
                  f"{P}", file=sys.stderr)
            continue
        got = search_ivf(ivf_index, Xi[sample], nprobe=nprobe)[1]
        recall = recall_at_k(got, oracle_ids)
        session = ServeSession(ivf_index, nprobe=nprobe)
        bucket = 128
        n_batches = max(reps, 4)
        batches = [Xi[(i * bucket) % max(1, c - bucket):][:bucket]
                   for i in range(n_batches)]
        session.warm([bucket])
        session.submit(batches[0])
        session.drain()
        session.reset_stats()
        t0 = time.perf_counter()
        for b in batches:
            session.submit(b)
        session.drain()
        wall = time.perf_counter() - t0
        lats = sorted(session.latencies)
        row = {
            "op": "ivf_query",
            "variant": f"p{P}-nprobe{nprobe}",
            "median_s": round(statistics.median(lats), 6),
            "min_s": round(min(lats), 6),
            "reps_s": [round(t, 6) for t in lats],
            "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
            "queries_per_s": round(session.queries_served / wall, 1),
            "recall_at_k": round(float(recall), 4),
            "probe_fraction": round(nprobe / P, 4),
            **ledger_peak("ivf/l2/float32/serve"),
            **ledger_roofline("ivf/l2/float32/serve"),
        }
        results.append(row)
        print(f"{'ivf_query':16s} {row['variant']:16s} "
              f"median {row['median_s']}s  {row['queries_per_s']} q/s  "
              f"recall@{k} {row['recall_at_k']}", flush=True)

    # -- compression axis, at-rest side (ISSUE 9): the clustered store at
    # every residency level (f32 → bf16 → int8 → int4) at ONE fixed probe
    # count, with the measured recall@k and the resident bytes on each
    # row — the 2×/4×/8× cuts and what each costs are one committed
    # artifact, so a level can never look cheap without showing what it
    # paid. Same SIFT-shaped corpus and oracle as the ivf_query rows.
    at_rest_nprobe = min(4, P)
    for store in ("float32", "bfloat16", "int8", "int4"):
        sidx_q = build_ivf_index(
            Xi, KNNConfig(k=k, partitions=P, nprobe=at_rest_nprobe,
                          query_tile=min(1024, q), query_bucket=128,
                          dtype=store)
        )
        # query_ids → id-based self-exclusion: a quantized store's own
        # row reconstructs at nonzero distance, so zero-exclusion alone
        # would let every corpus-row query count a spurious self-hit the
        # oracle excluded
        got = search_ivf(
            sidx_q, Xi[sample], query_ids=sample.astype(np.int32)
        )[1]
        recall = recall_at_k(got, oracle_ids)
        session = ServeSession(sidx_q)
        bucket = 128
        n_batches = max(reps, 4)
        batches = [Xi[(i * bucket) % max(1, c - bucket):][:bucket]
                   for i in range(n_batches)]
        session.warm([bucket])
        session.submit(batches[0])
        session.drain()
        session.reset_stats()
        t0 = time.perf_counter()
        for b in batches:
            session.submit(b)
        session.drain()
        wall = time.perf_counter() - t0
        lats = sorted(session.latencies)
        row = {
            "op": "ivf_at_rest",
            "variant": f"p{P}-nprobe{at_rest_nprobe}-{store}",
            "median_s": round(statistics.median(lats), 6),
            "min_s": round(min(lats), 6),
            "reps_s": [round(t, 6) for t in lats],
            "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
            "queries_per_s": round(session.queries_served / wall, 1),
            "recall_at_k": round(float(recall), 4),
            "at_rest_bytes": sidx_q.nbytes_resident,
        }
        results.append(row)
        print(f"{'ivf_at_rest':16s} {row['variant']:24s} "
              f"median {row['median_s']}s  {row['queries_per_s']} q/s  "
              f"recall@{k} {row['recall_at_k']}  "
              f"{row['at_rest_bytes']} B", flush=True)

    # -- LIVE MUTATION (ISSUE 14): steady-state churn vs rebuild ----------
    # The write path's trajectory rows: upsert/delete rows/s at steady
    # state (warm mutation executables, freelist reuse), query p99 DURING
    # sustained churn next to the quiesced p99 on the same session (the
    # 2× acceptance bound), one compact-pass wall time, and the
    # comparison row the tentpole is measured against — rebuild-per-batch
    # (a full k-means retrain + build_ivf_index per mutation batch, the
    # only way to "mutate" before this PR). Same SIFT-shaped corpus.
    from mpi_knn_tpu.serve import mutate as serve_mutate

    mcfg = KNNConfig(
        k=k, partitions=P, nprobe=at_rest_nprobe,
        query_tile=min(1024, q), query_bucket=128, mutation_bucket=128,
        bucket_headroom=0.5,  # the mutable configuration pays its rent
        # here, next to the zero-headroom ivf_query rows — both visible
    )
    midx = build_ivf_index(Xi, mcfg)
    msession = ServeSession(midx)
    mbucket = 128
    msession.warm([mbucket])
    serve_mutate.warm_mutation(midx, msession.cfg, sizes=[mbucket])
    B = 128
    next_id = [10_000_000]

    def churn_cycle(timed: str | None):
        """One upsert+delete cycle of B rows (occupancy-neutral);
        returns the wall seconds of the `timed` half."""
        ids = np.arange(next_id[0], next_id[0] + B, dtype=np.int64)
        next_id[0] += B
        rows_b = Xi[(int(ids[0]) // B * B) % max(1, c - B):][:B]
        t0 = time.perf_counter()
        msession.upsert(ids, rows_b)
        t_up = time.perf_counter() - t0
        t0 = time.perf_counter()
        msession.delete(ids)
        t_del = time.perf_counter() - t0
        return t_up if timed == "upsert" else t_del

    churn_cycle(None)  # warm the eager helpers outside the timed region
    cycles = max(reps, 4)
    for half in ("upsert", "delete"):
        times = [churn_cycle(half) for _ in range(cycles)]
        row = {
            "op": "ivf_mutation",
            "variant": f"{half}-steady-b{B}",
            "median_s": round(statistics.median(times), 6),
            "min_s": round(min(times), 6),
            "reps_s": [round(t, 6) for t in times],
            "rows_per_s": round(B / statistics.median(times), 1),
        }
        results.append(row)
        print(f"{'ivf_mutation':16s} {row['variant']:20s} "
              f"median {row['median_s']}s  {row['rows_per_s']} rows/s",
              flush=True)

    def serve_p99(label, churn: bool):
        """p99 of one serving pass over the standard batches, with an
        optional background churn thread interleaving upsert/delete
        chunks through the same mutation lock the dispatch takes."""
        import threading as _threading

        batches = [Xi[(i * mbucket) % max(1, c - mbucket):][:mbucket]
                   for i in range(max(4 * reps, 16))]
        msession.submit(batches[0])
        msession.drain()
        msession.reset_stats()
        stop = _threading.Event()

        def _churn():
            while not stop.is_set():
                churn_cycle(None)

        t = None
        if churn:
            t = _threading.Thread(target=_churn, daemon=True)
            t.start()
        t0 = time.perf_counter()
        for b in batches:
            msession.submit(b)
        msession.drain()
        wall = time.perf_counter() - t0
        if t is not None:
            stop.set()
            t.join(30)
        lats = sorted(msession.latencies)
        row = {
            "op": "ivf_mutation",
            "variant": label,
            "median_s": round(statistics.median(lats), 6),
            "min_s": round(min(lats), 6),
            "reps_s": [round(x, 6) for x in lats],
            "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
            "queries_per_s": round(msession.queries_served / wall, 1),
        }
        results.append(row)
        print(f"{'ivf_mutation':16s} {row['variant']:20s} "
              f"p99 {row['p99_ms']}ms  {row['queries_per_s']} q/s",
              flush=True)
        return row

    quiesced = serve_p99("query-quiesced", churn=False)
    churned = serve_p99("query-under-churn", churn=True)
    print(f"{'ivf_mutation':16s} p99 churn/quiesced ratio "
          f"{churned['p99_ms'] / max(1e-9, quiesced['p99_ms']):.2f}",
          flush=True)

    t0 = time.perf_counter()
    msession.compact(reason="bench")
    compact_wall = time.perf_counter() - t0
    results.append({
        "op": "ivf_mutation",
        "variant": "compact",
        "median_s": round(compact_wall, 6),
        "min_s": round(compact_wall, 6),
        "reps_s": [round(compact_wall, 6)],
    })
    print(f"{'ivf_mutation':16s} {'compact':20s} "
          f"wall {compact_wall:.3f}s", flush=True)

    # the comparison row: absorbing a B-row batch by REBUILDING the
    # index (retrain + rebucket — the pre-PR "mutation"), denominated in
    # rows/s over the same B so the tentpole's ≥10× bar reads directly
    t0 = time.perf_counter()
    build_ivf_index(Xi, mcfg)
    rebuild_wall = time.perf_counter() - t0
    results.append({
        "op": "ivf_mutation",
        "variant": f"rebuild-per-batch-b{B}",
        "median_s": round(rebuild_wall, 6),
        "min_s": round(rebuild_wall, 6),
        "reps_s": [round(rebuild_wall, 6)],
        "rows_per_s": round(B / rebuild_wall, 1),
    })
    print(f"{'ivf_mutation':16s} {'rebuild-per-batch':20s} "
          f"wall {rebuild_wall:.3f}s  {B / rebuild_wall:.1f} rows/s",
          flush=True)

    # -- SHARDED clustered path: routed candidate exchange over the mesh --
    # The same trained index distributed over 2- and 4-device ring meshes
    # (ivf/sharded.py) at nprobe ∈ {1, 4}, next to the single-device
    # ivf_query rows above and the dense ring_allknn rows — one artifact
    # answers "what does sharding the bucket store cost per query, and
    # what recall does each probe count buy". On CPU the all-to-alls are
    # memcpys (the ring-row rationale): the rows pin exchange-machinery
    # overhead per PR, not ICI; each row carries the routed/dropped
    # exchange story so a skewed routing table is visible in the artifact.
    if args.ring_devices:
        from mpi_knn_tpu.ivf import search_ivf_sharded, shard_ivf_index

        for shards in (2, 4):
            if shards > args.ring_devices:
                # no silent caps: a "4-shard" row on a smaller mesh would
                # measure a different layout under the bigger label
                print(f"note: skipping ivf_sharded_query shards {shards} "
                      f"> --ring-devices {args.ring_devices}",
                      file=sys.stderr)
                continue
            sidx = shard_ivf_index(ivf_index, shards=shards)
            for nprobe in (1, 4):
                if nprobe > P:
                    print(f"note: skipping ivf_sharded_query nprobe "
                          f"{nprobe} > partitions {P}", file=sys.stderr)
                    continue
                got = search_ivf_sharded(
                    sidx, Xi[sample], nprobe=nprobe
                )[1]
                recall = recall_at_k(got, oracle_ids)
                session = ServeSession(sidx, nprobe=nprobe)
                bucket = 128
                n_batches = max(reps, 4)
                batches = [Xi[(i * bucket) % max(1, c - bucket):][:bucket]
                           for i in range(n_batches)]
                session.warm([bucket])
                session.submit(batches[0])
                session.drain()
                session.reset_stats()
                t0 = time.perf_counter()
                for b in batches:
                    session.submit(b)
                session.drain()
                wall = time.perf_counter() - t0
                lats = sorted(session.latencies)
                row = {
                    "op": "ivf_sharded_query",
                    "variant": f"p{P}-s{shards}-nprobe{nprobe}",
                    "median_s": round(statistics.median(lats), 6),
                    "min_s": round(min(lats), 6),
                    "reps_s": [round(t, 6) for t in lats],
                    "p50_ms": round(
                        float(np.percentile(lats, 50)) * 1e3, 3),
                    "p99_ms": round(
                        float(np.percentile(lats, 99)) * 1e3, 3),
                    "queries_per_s": round(
                        session.queries_served / wall, 1),
                    "recall_at_k": round(float(recall), 4),
                    "probe_fraction": round(nprobe / P, 4),
                    "routed_total": session.exchange["routed_total"],
                    "overflow_dropped_total":
                        session.exchange["dropped_total"],
                    "exchange_bytes_total":
                        session.exchange["exchange_bytes_total"],
                    **ledger_peak("ivf-sharded/l2/float32/serve"),
            **ledger_roofline("ivf-sharded/l2/float32/serve"),
                }
                results.append(row)
                print(f"{'ivf_sharded_query':16s} {row['variant']:20s} "
                      f"median {row['median_s']}s  "
                      f"{row['queries_per_s']} q/s  "
                      f"recall@{k} {row['recall_at_k']}", flush=True)

    # -- cold_start: the persistent AOT executable cache (ISSUE 12) ------
    # fresh SUBPROCESSES, twice per backend against one cache dir: the
    # first child is the cold start (every cell a real XLA compile), the
    # second the populated-cache start (every cell revived from disk) —
    # in-process re-measurement would let the jit caches flatter the
    # cached number. Each row banks warm() wall seconds and the
    # dispatch→first-result time; the cached row carries the speedup the
    # ISSUE 12 acceptance bound (≥ 3× on CPU) is read from.
    import os
    import subprocess
    import tempfile

    for cs_backend in ("serial", "ivf-sharded"):
        with tempfile.TemporaryDirectory(prefix="bench-aot-") as td:
            spec = {
                "backend": cs_backend,
                "cache_dir": os.path.join(td, "aot"),
                "m": min(c, 8192),
                "d": d,
                "k": k,
                "devices": 4,
            }
            outs = {}
            for mode in ("cold", "cached"):
                child = subprocess.run(
                    [sys.executable, __file__,
                     "--cold-start-child", json.dumps(spec)],
                    capture_output=True, text=True, timeout=900,
                )
                line = child.stdout.strip().splitlines()[-1] \
                    if child.stdout.strip() else ""
                try:
                    outs[mode] = json.loads(line)
                except (json.JSONDecodeError, IndexError):
                    print(f"note: cold_start {cs_backend} {mode} child "
                          f"failed (rc={child.returncode}): "
                          f"{child.stderr.strip()[-300:]}",
                          file=sys.stderr)
                    break
            if len(outs) != 2:
                continue  # loudly skipped above, never silently
            for mode, doc_c in outs.items():
                row = {
                    "op": "cold_start",
                    "variant": f"{cs_backend}-{mode}",
                    "median_s": doc_c["warm_s"],
                    "min_s": doc_c["warm_s"],
                    "reps_s": [doc_c["warm_s"]],
                    "first_result_s": doc_c["first_result_s"],
                    "cells": doc_c["cells"],
                    "deduped": doc_c["deduped"],
                    "compiled": doc_c["compiled"],
                    "loaded": doc_c["loaded"],
                }
                if mode == "cached":
                    row["warm_speedup"] = round(
                        outs["cold"]["warm_s"] / doc_c["warm_s"], 2
                    )
                results.append(row)
                extra = (f"  speedup {row['warm_speedup']}x"
                         if mode == "cached" else "")
                print(f"{'cold_start':16s} {row['variant']:20s} "
                      f"warm {row['median_s']}s  first-result "
                      f"{row['first_result_s']}s{extra}", flush=True)

    doc = {
        "schema": "bench_ops.v1",
        "platform": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "jax_version": jax.__version__,
        "shapes": {"q": q, "c": c, "d": d, "k": k},
        "reps": reps,
        "results": results,
    }
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
