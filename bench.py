"""Headline benchmark: MNIST-60k×784 all-kNN, k=10 (BASELINE.md north star:
< 1 s on a v5e-8 at recall@10 parity with the serial reference semantics).

Prints ONE JSON line per series:
    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Architecture (ISSUE 6): ``python bench.py`` is a SUPERVISOR. Every series
of a round runs in its own child subprocess under the resilience worker
runner (``mpi_knn_tpu.resilience``): the child writes monotonic heartbeat
progress, and the supervisor kills on *beat starvation* (a hung device
stops the beats immediately) with wall-clock as the outer bound only. One
hung series can therefore never take down its siblings. The supervisor
itself never imports jax (asserted before every spawn): a chip belongs to
one process at a time, and a parent that had touched it would starve each
child of the device.

- a completed series banks its real measurement line, always;
- a hung/crashed series banks a structured ``"failed": true`` line
  under its own series name — with ``"value": null`` and the kill time
  in an explicit ``time_until_kill_s`` field, never a ``vs_baseline``
  number (a kill is not a measurement; ISSUE 7), plus the child's banked
  span flight record (``mpi_knn_tpu.obs.spans``) so the round keeps the
  story of where the time went;
- the process exits 0 whenever at least one series banked;
- a round in which NO series banked exits non-zero and prints no
  measurement line. There is no re-run on another platform: a number is
  from the device the series asked for, or it does not exist.

Series come from ``BENCH_SERIES``: a JSON list of env-overlay objects,
each overlaid on this process's environment for one child (optional
``"name"`` key labels supervisor notes). Unset = one series from the
ambient knobs, which is the PR-driver contract (exactly one stdout line).
``BENCH_DOCTOR=1`` runs the ``mpi-knn doctor`` preflight probe first and
banks every series as failed without starting it if the device does not
answer.

Methodology (mirrors the reference, which times ONLY the distance/top-k
phase — ``/root/reference/knn-serial.c:70,94-98`` — not I/O or voting):

- the corpus is placed on device once, outside the timed region;
- each timed rep runs the full ``all_knn`` API path on the device-resident
  corpus and synchronizes with ``device_sync`` (``block_until_ready`` on
  every output);
- value = MEDIAN rep wall-clock of the all-kNN phase (all reps plus the
  min are reported on stderr);
- recall@10 is checked against a float64 host oracle on a 256-query sample
  (computed in matmul form, chunk-free at this sample size); a recall miss
  (<0.999) zeroes vs_baseline rather than reporting a fast-but-wrong number.

vs_baseline: north_star_seconds / value, scaled by the fraction of the
8-chip target this host provides (1 chip => target is 8 s), so >1.0 beats
the north star at equal silicon.

Environment knobs: BENCH_M (default 60000), BENCH_BACKEND (serial|ring|ring-overlap),
BENCH_REPS, BENCH_QT/BENCH_CT (tiles), BENCH_TOPK (exact|approx),
BENCH_PRECISION (default|high|highest), BENCH_PRECISION_POLICY
(exact|mixed — mixed is the compress-and-rerank pipeline and owns both dot
precisions, so it overrides BENCH_PRECISION), BENCH_IVF_PARTITIONS /
BENCH_IVF_NPROBE (clustered-index path: k-means partitions trained
outside the timed region, per-query probed scan timed; the series name
carries the knobs and the gate is the configured recall_target — the
clustered rung's own acceptance bar), BENCH_IVF_SHARDS (the SHARDED
clustered path, mpi_knn_tpu.ivf.sharded: the bucket store distributed
over that many ring-mesh devices with the routed all-to-all candidate
exchange; requires BENCH_IVF_PARTITIONS, series name carries the shard
count), BENCH_WATCHDOG_S (per-series wall
bound, 0 disables), BENCH_BEAT_TIMEOUT_S (per-series beat-starvation
bound, 0 disables), BENCH_SERIES / BENCH_DOCTOR (supervisor, above),
BENCH_PLATFORM (forces the platform through utils.platform.force_platform;
``tpu`` fails the series when no TPU comes up), TKNN_MNIST (real data path;
synthetic surrogate otherwise), TKNN_FAULTS (fault injection — see
mpi_knn_tpu/resilience/faults.py; the bench series fault site is
``bench-series``).

The recall gate is FIXED at 0.999 regardless of knobs — it is the north
star's acceptance bar, not a tunable. Setting BENCH_RT below it tunes
approx_min_k to a recall the gate will reject, zeroing vs_baseline by
design (speed bought with recall does not count).
"""

import json
import os
import sys
import time

import numpy as np


NORTH_STAR_SECONDS = 1.0  # on 8 chips (v5e-8)
NORTH_STAR_CHIPS = 8
RECALL_GATE = 0.999


def metric_name(env=None) -> str:
    """One construction of the series name, shared by the success and
    failure paths so a failure always lands in the real series — and
    computable by the supervisor from a child's env when the child died
    before printing anything. The IVF knobs are part of the name: a
    clustered run measures a different computation (sublinear probed scan
    at a measured recall target) and must never masquerade as the exact
    full-scan series."""
    env = os.environ if env is None else env
    m = int(env.get("BENCH_M", "60000"))
    k = int(env.get("BENCH_K", "10"))
    ivf = ""
    if env.get("BENCH_IVF_PARTITIONS"):
        p = env["BENCH_IVF_PARTITIONS"]
        n = env.get("BENCH_IVF_NPROBE", "auto")
        ivf = f"_ivf{p}p{n}"
        if env.get("BENCH_IVF_SHARDS"):
            # a sharded run measures a different program (routed exchange
            # over the mesh) and must never masquerade as the
            # single-device clustered series
            ivf += f"s{env['BENCH_IVF_SHARDS']}"
    return f"mnist{m // 1000}k_allknn_k{k}{ivf}_seconds"


def oracle_topk(X: np.ndarray, sample: np.ndarray, k: int) -> np.ndarray:
    """f64 ground-truth neighbor ids for the sampled queries, matmul form
    (no (q, m, d) broadcast — that would be ~100 GB at MNIST scale)."""
    Xs = X.astype(np.float64)
    Q = Xs[sample]
    d = (
        (Q**2).sum(1)[:, None]
        + (Xs**2).sum(1)[None, :]
        - 2.0 * (Q @ Xs.T)
    )
    # reference zero-exclusion (SURVEY.md Q3) + exact self-exclusion
    d[d <= 1e-9] = np.inf
    d[np.arange(len(sample)), sample] = np.inf
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def main() -> int:
    """ONE series measurement — always a supervised child process
    (``TKNN_BENCH_CHILD=1``). Heartbeats bracket every step that can
    hang, so the supervisor's beat-starvation kill names the hung
    step; the injectable ``bench-series`` fault site stands in for a
    hung device in tier-1."""
    from mpi_knn_tpu.obs.spans import span as flight_span
    from mpi_knn_tpu.resilience.faults import fault_point
    from mpi_knn_tpu.resilience.heartbeat import maybe_beat

    maybe_beat("start")
    fault_point("bench-series")
    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if os.environ.get("BENCH_PLATFORM"):
        # a sharded clustered series needs a real multi-device mesh: on
        # the forced-CPU platform that means virtual host devices, sized
        # to the shard count BEFORE the backend comes up
        _shards = os.environ.get("BENCH_IVF_SHARDS")
        force_platform(
            os.environ["BENCH_PLATFORM"],
            n_devices=(int(_shards)
                       if _shards and _shards.isdigit()
                       and os.environ["BENCH_PLATFORM"] == "cpu"
                       else None),
        )
    use_compile_cache()
    maybe_beat("platform")

    import jax
    import jax.numpy as jnp

    maybe_beat("jax-import")

    m = int(os.environ.get("BENCH_M", "60000"))
    k = int(os.environ.get("BENCH_K", "10"))
    reps = int(os.environ.get("BENCH_REPS", "3"))
    if reps < 1:
        # median([]) would silently emit NaN as the headline value
        print(json.dumps({"error": "BENCH_REPS must be >= 1"}),
              file=sys.stderr)
        return 2
    backend = os.environ.get("BENCH_BACKEND", "serial")
    # BENCH_PRECISION_POLICY=mixed: the compress-and-rerank pipeline — the
    # O(q·c·d) dot runs single-pass bf16 MXU, only the 4k-overfetched
    # survivors are reranked at HIGHEST. The policy owns both dot
    # precisions, so combining it with an explicit BENCH_PRECISION is a
    # usage error — refuse loudly rather than silently ignore one knob
    # (an A/B sweep over BENCH_PRECISION would otherwise record identical
    # mixed runs mislabeled as precision variants).
    precision_policy = os.environ.get("BENCH_PRECISION_POLICY", "exact")
    if precision_policy == "mixed" and os.environ.get("BENCH_PRECISION"):
        print(
            json.dumps({
                "error": "BENCH_PRECISION conflicts with "
                "BENCH_PRECISION_POLICY=mixed (the policy owns both dot "
                "precisions: DEFAULT compress, HIGHEST rerank)"
            }),
            file=sys.stderr,
        )
        return 2
    # BENCH_RING_SCHEDULE=bidir: full-duplex ring rotation (both torus
    # directions at once, floor(P/2)+1 rounds). The knob only means anything
    # on a ring backend — setting it with a single-device backend would
    # silently measure an identical program under a different label, so the
    # conflicting combination is refused loudly (same treatment as
    # BENCH_PRECISION × BENCH_PRECISION_POLICY above).
    ring_schedule = os.environ.get("BENCH_RING_SCHEDULE", "uni")
    if ring_schedule != "uni" and backend not in ("ring", "ring-overlap"):
        print(
            json.dumps({
                "error": f"BENCH_RING_SCHEDULE={ring_schedule} conflicts "
                f"with BENCH_BACKEND={backend}: the ring schedule only "
                "exists on ring/ring-overlap backends — an A/B sweep here "
                "would record identical single-device runs mislabeled as "
                "schedule variants"
            }),
            file=sys.stderr,
        )
        return 2
    # BENCH_IVF_PARTITIONS=P: the clustered (IVF) path — the corpus is
    # k-means-partitioned once OUTSIDE the timed region (index build is
    # the amortized half, like the data upload), and each timed rep is
    # the full all-pairs query sweep probing only BENCH_IVF_NPROBE
    # partitions per query (unset = auto-tuned to cfg.recall_target). The
    # series name carries the knobs, and the recall gate for IVF rows is
    # the configured recall_target, not the exact path's 0.999 — the
    # clustered rung's acceptance bar IS its measured recall target
    # (DESIGN.md ladder rung 4); vs_baseline still zeroes on a miss.
    ivf_partitions = os.environ.get("BENCH_IVF_PARTITIONS")
    ivf_nprobe = os.environ.get("BENCH_IVF_NPROBE")
    ivf_shards = os.environ.get("BENCH_IVF_SHARDS")
    if ivf_shards and not ivf_partitions:
        print(
            json.dumps({
                "error": "BENCH_IVF_SHARDS without BENCH_IVF_PARTITIONS: "
                "sharding distributes a clustered index's partition "
                "buckets over the mesh — a shard count without "
                "partitions would be silently ignored"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_shards and not ivf_shards.isdigit():
        # a typo'd knob must be a usage refusal (never banked), not an
        # uncaught crash the supervisor books as a failed series
        print(
            json.dumps({
                "error": f"BENCH_IVF_SHARDS={ivf_shards!r} is not a "
                "positive integer"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_shards and int(ivf_shards) > len(jax.devices()):
        print(
            json.dumps({
                "error": f"BENCH_IVF_SHARDS={ivf_shards} exceeds the "
                f"{len(jax.devices())} visible device(s): the sharded "
                "clustered index places one bucket slice per device — "
                "set BENCH_PLATFORM=cpu for virtual host devices, or "
                "lower the shard count"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_shards and os.environ.get("BENCH_RING_XFER"):
        print(
            json.dumps({
                "error": "BENCH_RING_XFER conflicts with "
                "BENCH_IVF_SHARDS: the candidate exchange moves bucket "
                "rows at the at-rest dtype (BENCH_DTYPE=bfloat16 halves "
                "exchange bytes) — there is no ring rotation to re-dtype, "
                "so the knob would be silently ignored"
            }),
            file=sys.stderr,
        )
        return 2
    if (
        os.environ.get("BENCH_RING_XFER") == "int8"
        and precision_policy != "mixed"
    ):
        # same refusal the config itself raises, surfaced as the bench's
        # structured exit-2 so a sweep script reads WHY instead of a
        # traceback: int8 transfer has no rerank to absorb the
        # quantization under the exact policy — the run would silently
        # degrade every banked distance, not just the preselect keys
        print(
            json.dumps({
                "error": "BENCH_RING_XFER=int8 requires "
                "BENCH_PRECISION_POLICY=mixed: the block-scaled int8 "
                "transfer is dequantized into the compress dot and the "
                "exact HIGHEST rerank absorbs the quantization noise — "
                f"policy {precision_policy!r} has no rerank, so the "
                "banked recall would silently carry full quantization "
                "error"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_nprobe and not ivf_partitions:
        print(
            json.dumps({
                "error": "BENCH_IVF_NPROBE without BENCH_IVF_PARTITIONS: "
                "nprobe selects how many of a clustered index's "
                "partitions to scan — a probe count without partitions "
                "would be silently ignored"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_partitions and backend != "serial":
        print(
            json.dumps({
                "error": f"BENCH_IVF_PARTITIONS conflicts with "
                f"BENCH_BACKEND={backend}: the clustered search is a "
                "single-device serial-math path — an A/B sweep here would "
                "record identical serial runs mislabeled as backend "
                "variants"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_partitions and os.environ.get("BENCH_PRECISION"):
        print(
            json.dumps({
                "error": "BENCH_PRECISION conflicts with "
                "BENCH_IVF_PARTITIONS: the clustered search owns its dot "
                "precisions (HIGHEST centroid score + rerank; DEFAULT "
                "compress under BENCH_PRECISION_POLICY=mixed)"
            }),
            file=sys.stderr,
        )
        return 2
    if ivf_partitions and (
        os.environ.get("BENCH_TOPK") or os.environ.get("BENCH_SCHEDULE")
    ):
        # the probed path always finishes with the exact rerank top-k and
        # has no tile-merge schedule — a banked line whose metadata names
        # a selection method / schedule that never ran would be a
        # mislabeled measurement (the library refuses the same knobs)
        print(
            json.dumps({
                "error": "BENCH_TOPK/BENCH_SCHEDULE conflict with "
                "BENCH_IVF_PARTITIONS: the clustered search always "
                "finishes with the exact rerank top-k and has no "
                "tile-merge schedule — the knobs would be silently "
                "ignored and the measurement mislabeled"
            }),
            file=sys.stderr,
        )
        return 2
    # BENCH_CENTER=0: skip mean-centering — read ONCE; the zero_eps pairing
    # below derives from the same bool so the two can never desync
    center = os.environ.get("BENCH_CENTER", "1") != "0"

    from mpi_knn_tpu import KNNConfig, all_knn
    from mpi_knn_tpu.data.mnist import load_mnist
    from mpi_knn_tpu.utils.report import recall_at_k
    from mpi_knn_tpu.utils.timing import device_sync

    X, _, source = load_mnist(m=m)
    maybe_beat("data")
    cfg = KNNConfig(
        k=k,
        backend=backend,
        query_tile=int(os.environ.get("BENCH_QT", "4096")),
        # corpus tile capped at 8192: exact lax.top_k over very wide
        # (~60k-col) concats hung the device on an earlier transport
        # (not re-tested on this machine — ROADMAP Speed 6); the
        # whole-corpus tiling stays reachable via BENCH_CT.
        corpus_tile=int(os.environ.get("BENCH_CT", "8192")),
        topk_method=os.environ.get("BENCH_TOPK", "exact"),
        merge_schedule=os.environ.get("BENCH_SCHEDULE", "twolevel"),
        topk_block=int(os.environ.get("BENCH_BLOCK", "128")),
        recall_target=float(os.environ.get("BENCH_RT", "0.999")),
        dtype=os.environ.get("BENCH_DTYPE", "float32"),
        precision_policy=precision_policy,
        # BENCH_RING_XFER=bfloat16 halves ICI bytes per ring hop (the knob
        # only matters for BENCH_BACKEND=ring/ring-overlap)
        ring_transfer_dtype=os.environ.get("BENCH_RING_XFER") or None,
        ring_schedule=ring_schedule,
        # uncentered mode exists because raw MNIST pixels are small integers
        # — exactly representable even in bf16 — where *centered* values lose
        # mantissa bits. The relative zero-exclusion threshold is calibrated
        # for centered data (ops/topk.py), so uncentered runs switch to an
        # absolute epsilon: above the fp noise of a true duplicate at these
        # magnitudes (≲16 in squared space), orders below genuine MNIST
        # neighbor distances (~1e5).
        center=center,
        zero_eps=0.0 if center else 64.0,
        partitions=int(ivf_partitions) if ivf_partitions else None,
        nprobe=int(ivf_nprobe) if ivf_nprobe else None,
        ivf_shards=int(ivf_shards) if ivf_shards else None,
        # bench default HIGH (3-pass bf16): measured recall 1.0 on the
        # integer-pixel corpus with ~4% median win over HIGHEST (r3 A/B,
        # BASELINE.md). The LIBRARY default stays HIGHEST — the bench knows
        # its data; the library does not. BENCH_PRECISION overrides;
        # BENCH_PRECISION_POLICY=mixed takes the knob over entirely and the
        # ivf search path fixes its own dot precisions (both conflicting
        # combinations were rejected above).
        matmul_precision=None if (ivf_partitions or
                                  precision_policy == "mixed")
        else os.environ.get("BENCH_PRECISION") or "high",
    )

    if ivf_partitions:
        from mpi_knn_tpu.ivf import build_ivf_index
        from mpi_knn_tpu.ivf.search import (
            prepare_query_tiles,
            run_query_tiles,
        )

        # index build (k-means train + nprobe tune) is the amortized
        # half — outside the timed region, like the corpus upload below;
        # the queries are likewise centered/padded/tiled and put on
        # device ONCE, so the timed region is probe compute + sync only
        # (the dense series' timer placement — a per-rep host centering
        # pass would make the two series incomparable)
        # build_ivf_index dispatches on cfg.ivf_shards: the sharded form
        # trains the same single-device k-means then distributes the
        # bucket store over the ring mesh (ivf/sharded.py) — either way
        # the build is the amortized half, outside the timed region
        index = build_ivf_index(X, cfg)
        maybe_beat("index-build")
        rcfg = index.compatible_cfg(index.cfg)
        qids = np.arange(m, dtype=np.int32)
        if ivf_shards:
            from mpi_knn_tpu.ivf.sharded import (
                prepare_sharded_tiles,
                run_sharded_tiles,
            )

            q_tiles, qid_tiles, q_pad, _, route_cap = prepare_sharded_tiles(
                index, X, qids, rcfg
            )

            def run_ivf():
                d, i, _ = run_sharded_tiles(
                    index, q_tiles, qid_tiles, rcfg, route_cap
                )
                return d, i
        else:
            q_tiles, qid_tiles, q_pad, _ = prepare_query_tiles(
                index, X, qids, rcfg
            )

            def run_ivf():
                return run_query_tiles(index, q_tiles, qid_tiles, rcfg)
        device_sync(q_tiles)
        with flight_span("warm", cat="bench", backend=index.backend):
            d, i = run_ivf()  # warm
            device_sync(d, i)
        maybe_beat("warm")
        times = []
        for r in range(reps):
            with flight_span("rep", cat="bench", rep=r):
                t0 = time.perf_counter()
                d, i = run_ivf()
                device_sync(d, i)
                times.append(time.perf_counter() - t0)
            maybe_beat(f"rep{r}")
        got_ids = np.asarray(
            jax.device_get(i)
        ).reshape(q_pad, rcfg.k)[:m]
    else:
        # data to device ONCE — the timed region is the all-kNN phase,
        # matching the reference's timer placement
        Xd = jax.device_put(jnp.asarray(X, dtype=jnp.dtype(cfg.dtype)))
        device_sync(Xd)

        # compile + warm up
        with flight_span("warm", cat="bench", backend=backend):
            result = all_knn(Xd, config=cfg)
            device_sync(result.dists)
        maybe_beat("warm")

        times = []
        for r in range(reps):
            with flight_span("rep", cat="bench", rep=r):
                t0 = time.perf_counter()
                result = all_knn(Xd, config=cfg)
                device_sync(result.dists, result.ids)
                times.append(time.perf_counter() - t0)
            maybe_beat(f"rep{r}")
    # median is the headline; min stays visible on stderr for best-case
    # comparisons
    value = float(np.median(times))

    sample = np.linspace(0, m - 1, num=min(256, m), dtype=np.int64)
    want = oracle_topk(X, sample, k)
    if ivf_partitions:
        got = got_ids[sample]
    else:
        got = np.asarray(jax.device_get(result.ids[jnp.asarray(sample)]))
    recall = recall_at_k(got, want)
    maybe_beat("oracle")

    n_chips = jax.local_device_count() if jax.default_backend() == "tpu" else 1
    target_here = NORTH_STAR_SECONDS * (NORTH_STAR_CHIPS / n_chips)
    gate = cfg.recall_target if ivf_partitions else RECALL_GATE
    vs = (target_here / value) if recall >= gate else 0.0

    line = {
        "metric": metric_name(),
        "value": round(value, 4),
        "unit": "s",
        "vs_baseline": round(vs, 3),
    }
    print(json.dumps(line), flush=True)
    # context for humans / the judge, on stderr so stdout stays one line
    print(
        json.dumps(
            {
                "backend": backend,
                "data": source,
                "shape": list(X.shape),
                "recall_at_k_vs_oracle": round(float(recall), 5),
                "times": [round(t, 4) for t in times],
                "min_seconds": round(min(times), 4),
                "chips": n_chips,
                "platform": jax.default_backend(),
                "target_seconds_at_this_chip_count": target_here,
                "topk_method": cfg.topk_method,
                "precision_policy": cfg.precision_policy,
                "partitions": cfg.partitions,
                "nprobe": (index.nprobe if ivf_partitions else None),
                "ivf_shards": cfg.ivf_shards,
                "recall_gate": gate,
                "merge_schedule": cfg.merge_schedule,
                "tiles": [cfg.query_tile, cfg.corpus_tile],
            }
        ),
        file=sys.stderr,
    )
    return 0


# ---------------------------------------------------------------------------
# Supervisor: one child subprocess per series, heartbeat-watchdogged


def _note(msg: str) -> None:
    # never JSON-shaped: harness tooling reads the LAST '{'-prefixed
    # stderr line as the measurement context object
    print(f"bench-supervisor: {msg}", file=sys.stderr, flush=True)


def _parse_series():
    """BENCH_SERIES (JSON list of env-overlay objects) → list of dicts;
    unset = one series from the ambient knobs. Malformed input is a loud
    usage error (None return → supervisor exits 2): a typo'd round spec
    silently measuring the default series would be a mislabeled round."""
    raw = os.environ.get("BENCH_SERIES")
    if not raw:
        return [{}]
    try:
        doc = json.loads(raw)
        if not isinstance(doc, list) or not doc or not all(
            isinstance(s, dict) for s in doc
        ):
            raise ValueError("want a non-empty JSON list of objects")
    except (json.JSONDecodeError, ValueError) as e:
        print(
            json.dumps({
                "error": f"bad BENCH_SERIES: {e} — want a JSON list of "
                'env-overlay objects, e.g. [{"name": "exact"}, '
                '{"name": "mixed", "BENCH_PRECISION_POLICY": "mixed"}]'
            }),
            file=sys.stderr,
        )
        return None
    return doc


def _series_label(i: int, overlay: dict) -> str:
    return str(overlay.get("name") or f"series{i}")


def _child_env(overlay: dict) -> dict:
    env = dict(os.environ)
    # children never recurse into supervision, and never re-run preflight
    for k in ("BENCH_SERIES", "BENCH_DOCTOR"):
        env.pop(k, None)
    for k, v in overlay.items():
        if k == "name":
            continue
        env[k] = str(v)
    env["TKNN_BENCH_CHILD"] = "1"
    return env


def _measurement_line(stdout: str):
    """The LAST metric/value JSON line of a child's stdout, or None."""
    found = None
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(doc, dict) and "metric" in doc and "value" in doc:
            found = doc
    return found


def _failed_line(metric: str, series: str, status: str,
                 time_until_kill_s: float | None = None,
                 flight: dict | None = None) -> dict:
    """The structured line a failed series banks (ISSUE 7 shape):
    ``value`` is null — a watchdog kill is NOT a measurement, and a
    numeric value here gets read as one (an early round banked its
    timeout as ``value: 480.0, vs_baseline: 0.0``, a kill stamped as a
    zero-regression data point). The kill time lives in the explicit
    ``time_until_kill_s`` field instead, the line NEVER carries
    ``vs_baseline``, and the child's span flight-record summary (open
    spans name the step the kill interrupted) rides along when the
    worker recorded one."""
    doc = {
        "metric": metric,
        "value": None,
        "unit": "s",
        "failed": True,
        "series": series,
        "status": status,
        "time_until_kill_s": time_until_kill_s,
    }
    if flight is not None:
        doc["flight"] = flight
    return doc


def _is_usage_error(res) -> bool:
    """A child that refused its knobs (loud exit-2 convention): a
    configuration bug, not a device failure — it must NOT be banked as a
    failed measurement (the series name would be lying)."""
    return (
        res.status == "crashed"
        and res.returncode == 2
        and '"error"' in (res.stderr_tail + res.stdout)
    )


def _series_timeouts(env: dict):
    """Per-series watchdog bounds, read from the child's (overlaid) env:
    a series overlay may tighten or loosen the ambient knobs — a
    hang-prone configuration gets a short leash while its healthy
    siblings keep the full first-compile allowance. 0 disables."""
    beat = float(env.get("BENCH_BEAT_TIMEOUT_S", "240"))
    wall = float(env.get("BENCH_WATCHDOG_S", "480"))
    return (beat if beat > 0 else None, wall if wall > 0 else None)


def supervise() -> int:
    from mpi_knn_tpu.resilience.worker import run_supervised

    series = _parse_series()
    if series is None:
        return 2

    preflight_ok = True
    if os.environ.get("BENCH_DOCTOR") == "1":
        from mpi_knn_tpu.resilience.doctor import run_probe

        verdict = run_probe(
            platform=os.environ.get("BENCH_PLATFORM", "auto"),
            env={
                k: v for k, v in os.environ.items()
                if k != "TKNN_FAULTS" or "doctor" in v
            },
        )
        _note(f"doctor preflight: {json.dumps(verdict)}")
        preflight_ok = verdict["ok"]
        if not preflight_ok:
            _note("device failed preflight; skipping every series")

    banked_real = 0
    failed = []  # failure docs, in series order
    for i, overlay in enumerate(series):
        label = _series_label(i, overlay)
        env = _child_env(overlay)
        if not preflight_ok:
            # the series never started: 0 s until the (preflight) kill
            failed.append(_failed_line(
                metric_name(env), label, "preflight",
                time_until_kill_s=0.0,
            ))
            continue
        beat_timeout, wall_timeout = _series_timeouts(env)
        if "jax" in sys.modules:
            # one process per chip: a supervisor holding the device would
            # make every child fail or hang at backend start-up
            raise RuntimeError(
                "bench supervisor imported jax before spawning a series"
            )
        res = run_supervised(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            beat_timeout_s=beat_timeout,
            wall_timeout_s=wall_timeout,
        )
        if res.stderr_tail:
            # child context (its last '{'-line is the series' context
            # object) — forwarded verbatim, supervisor notes stay non-JSON
            sys.stderr.write(res.stderr_tail)
            if not res.stderr_tail.endswith("\n"):
                sys.stderr.write("\n")
        doc = _measurement_line(res.stdout) if res.ok else None
        if doc is not None:
            # print the moment it is earned: a supervisor-level kill
            # while a later series runs must not erase this one's signal
            banked_real += 1
            print(json.dumps(doc), flush=True)
            _note(f"series {label!r}: banked {doc['metric']} = "
                  f"{doc['value']}{doc['unit']}")
            continue
        if _is_usage_error(res):
            _note(f"series {label!r}: usage error (exit 2) — not banked; "
                  "fix the knobs")
            continue
        # hung (beat starvation / wall kill) or crashed or silent-ok:
        # a structured failed line under the series' real name, with the
        # banked flight record telling where the time went. Buffered:
        # failed lines go to stdout only beside at least one real
        # measurement — an all-failed round prints them on stderr.
        status = res.status if res.status != "ok" else "crashed"
        failed.append(_failed_line(
            metric_name(env), label, status,
            time_until_kill_s=round(res.duration_s, 1),
            flight=res.flight,
        ))
        _note(
            f"series {label!r}: {status}"
            + (f" ({res.reason})" if res.reason else "")
            + f" after {res.duration_s:.1f}s at beat {res.beats} "
            f"{res.last_beat_label!r}; banked a failed line"
        )

    for doc in failed:
        print(json.dumps(doc), flush=True,
              file=sys.stdout if banked_real else sys.stderr)
    if banked_real > 0:
        return 0
    if failed:
        _note("no series banked a measurement (failed lines above)")
    return 2


if __name__ == "__main__":
    if os.environ.get("TKNN_BENCH_CHILD") == "1":
        sys.exit(main())
    sys.exit(supervise())
