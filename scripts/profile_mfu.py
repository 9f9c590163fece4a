"""Hardware utilization evidence: per-variant wall-clock, phase split, MFU,
and an XProf trace for the MNIST-scale all-kNN workload.

The reference "proved" its perf story by running and printing one timer
(``/root/reference/knn-serial.c:94-98``). This harness is the rebuild's
equivalent done properly (VERDICT r2 next-step #2): for each execution
variant it measures

- steady-state wall-clock of the full all-kNN phase (device-synced);
- the distance-compute-only time (same tiling, top-k replaced by a fused
  min-reduction) — the matmul+HBM share of the pipeline, isolating how much
  of the budget the top-k reduction consumes;
- MFU: useful distance FLOPs (2·q·m·d for the −2XYᵀ term) / time / peak.
  Reported against the bf16 MXU peak, with the multi-pass factor of the
  matmul precision noted (HIGHEST f32 ≈ 6 bf16 passes, HIGH ≈ 3, DEFAULT=1)
  so "delivered" MXU work can be read off the same row;
- optionally a ``jax.profiler.trace`` of one rep per variant
  (``--profile-dir``), inspectable with XProf/TensorBoard — and
  attributed in-row through the library (``mpi_knn_tpu.obs.attribution``,
  ISSUE 7): each profiled row carries the per-category device busy split
  (matmul / sort-topk / collective / copy / other + overlap fraction),
  the same numbers `mpi-knn query --profile-batches` embeds in its
  report, so this script is a thin CLI over the shared parser instead of
  leaving raw trace dirs to a second tool.

Usage:
    python scripts/profile_mfu.py [--m 60000] [--d 784] [--k 10]
        [--variants dist,twolevel,stream]
        [--reps 3] [--profile-dir profiles] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

PASS_FACTOR = {"highest": 6.0, "high": 3.0, "default": 1.0}

def peak_flops(args) -> float:
    """bf16 peak FLOP/s of the device this process runs on: the override,
    else the shipped profile for the detected ``device_kind``
    (``analysis/device_profiles.json``). A device with no shipped profile
    is an error — a utilization is never quoted against a guessed peak."""
    if args.peak_tflops:
        return args.peak_tflops * 1e12
    from mpi_knn_tpu.analysis.cost import detected_profile

    profile = detected_profile()
    if profile is None:
        raise SystemExit(
            "profile_mfu: no shipped device profile for this device; pass "
            "--peak-tflops or add it to analysis/device_profiles.json"
        )
    return float(profile["peak_flops"])


def build_cfg(variant: str, args):
    from mpi_knn_tpu import KNNConfig

    base = dict(
        k=args.k,
        query_tile=args.query_tile,
        corpus_tile=args.corpus_tile,
        matmul_precision=args.precision,
        topk_method=args.topk,
    )
    if variant in ("twolevel", "stream"):
        return KNNConfig(backend="serial", merge_schedule=variant, **base)
    raise SystemExit(f"unknown variant {variant!r}")


def time_reps(fn, sync, reps):
    fn()  # compile + warm
    sync()
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        sync()
        out.append(time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--m", type=int, default=60000)
    ap.add_argument("--d", type=int, default=784)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--query-tile", type=int, default=4096)
    ap.add_argument("--corpus-tile", type=int, default=8192)
    ap.add_argument("--precision", default=None,
                    choices=[None, "default", "high", "highest"])
    ap.add_argument("--topk", default="exact")
    ap.add_argument("--variants", default="dist,twolevel,stream",
                    help="comma list; 'dist' is the distance-only phase "
                         "(run it in its own process first: a later variant "
                         "wedging the device must not take its data down)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="override the bf16 peak (default: the shipped "
                         "profile of the detected device)")
    ap.add_argument("--profile-dir", default=None)
    ap.add_argument("--json", default=None)
    ap.add_argument("--append-jsonl", default=None,
                    help="append each row the moment it is measured — the "
                         "durable partial-results channel for wedge-prone "
                         "hardware (the r3 mfu step lost 30 min of rows to "
                         "an end-of-process-only write)")
    ap.add_argument("--fresh-jsonl", action="store_true",
                    help="truncate --append-jsonl at start: this run begins "
                         "a new measurement epoch (done here, not by the "
                         "caller, so a suite step that never starts cannot "
                         "destroy the prior epoch's rows)")
    ap.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                    default="auto")
    ap.add_argument("--dist-s", type=float, default=None,
                    help="distance-only median from a prior process, for "
                         "topk_share_est when 'dist' is not in --variants")
    args = ap.parse_args(argv)

    if args.fresh_jsonl and args.append_jsonl:
        # truncate BEFORE any JAX/device work: a wedge during device init
        # must not leave the prior epoch's rows posing as this epoch's
        open(args.append_jsonl, "w").close()

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        force_platform(args.platform)
    use_compile_cache()

    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu import all_knn
    from mpi_knn_tpu.backends.serial import (
        effective_tiles,
        masked_dist_tile,
        prepare_tiles,
    )
    from mpi_knn_tpu.ops.distance import sq_norms
    from mpi_knn_tpu.utils.timing import device_sync

    rng = np.random.default_rng(0)
    X = (rng.random((args.m, args.d)) * 255.0).astype(np.float32)
    Xd = jax.device_put(jnp.asarray(X))
    device_sync(Xd)

    peak = peak_flops(args)
    # useful work: the −2·X·Yᵀ term of every (query, corpus) pair
    useful_flop = 2.0 * args.m * args.m * args.d

    results = []

    def emit(row, final=True):
        row = {
            **row,
            # each row carries its workload/peak context so downstream folds
            # never have to assume the defaults (ADVICE r3: a run with
            # non-default --m or --peak-tflops must not fold under a wrong
            # header)
            "m": args.m,
            "d": args.d,
            "k": args.k,
            "useful_tflop": round(useful_flop / 1e12, 3),
            "peak_bf16_tflops": peak / 1e12,
            "ts": round(time.time(), 1),  # rows outlive re-runs;
        }
        if final:                         # the stamp dates them
            results.append(row)
        print(json.dumps(row), flush=True)
        if args.append_jsonl:
            with open(args.append_jsonl, "a") as f:
                f.write(json.dumps(row) + "\n")

    variants = [v for v in args.variants.split(",") if v]

    # ---- distance-only pseudo-variant: identical tiling and masking, but
    # the per-tile reduction is a fused min — the pipeline minus its top-k.
    # Prior dist_s from an earlier process can be passed via --dist-s so the
    # per-variant processes still report topk_share_est.
    dist_s = args.dist_s
    if "dist" in variants:
        cfg0 = build_cfg("twolevel", args)
        q_tile, c_tile = effective_tiles(cfg0, args.m, args.m)
        q_tiles, qid_tiles, c_tiles, c_ids, _ = prepare_tiles(
            Xd, Xd, np.arange(args.m, dtype=np.int32), cfg0, q_tile, c_tile
        )

        import functools

        @functools.partial(jax.jit, static_argnames=("cfg",))
        def distances_only(q_tiles, qid_tiles, c_tiles, c_ids, cfg):
            c_sq = jax.vmap(sq_norms)(c_tiles)

            def per_qt(argsq):
                q_x, q_ids = argsq
                q_sq = sq_norms(q_x)

                def step(_, tile):
                    blk, blk_ids, blk_sq = tile
                    dmin = jnp.min(
                        masked_dist_tile(
                            q_x, q_ids, q_sq, blk, blk_ids, blk_sq, cfg
                        ),
                        axis=-1,
                    )
                    return None, dmin

                _, mins = jax.lax.scan(step, None, (c_tiles, c_ids, c_sq))
                return jnp.min(mins, axis=0)

            return jax.lax.map(per_qt, (q_tiles, qid_tiles))

        def run_dist():
            distances_only(q_tiles, qid_tiles, c_tiles, c_ids, cfg0)

        def sync_dist():
            device_sync(
                distances_only(q_tiles, qid_tiles, c_tiles, c_ids, cfg0)
            )

        dist_times = time_reps(run_dist, sync_dist, args.reps)
        dist_s = float(np.median(dist_times))
        emit(
            {
                "variant": "distance-only",
                "median_s": round(dist_s, 4),
                "times": [round(t, 4) for t in dist_times],
                "mfu_vs_bf16_peak": round(useful_flop / dist_s / peak, 4),
            }
        )

    for variant in [v for v in variants if v != "dist"]:
        cfg = build_cfg(variant, args)

        holder = {}

        def run():
            holder["res"] = all_knn(Xd, config=cfg)

        def sync():
            device_sync(holder["res"].dists, holder["res"].ids)

        times = time_reps(run, sync, args.reps)
        med = float(np.median(times))
        prec = args.precision or "highest"
        row = {
            "variant": variant,
            "median_s": round(med, 4),
            "times": [round(t, 4) for t in times],
            "mfu_vs_bf16_peak": round(useful_flop / med / peak, 4),
            "precision": prec,
            "mxu_pass_factor": PASS_FACTOR.get(prec, 1.0),
        }
        if dist_s is not None:
            row["topk_share_est"] = round(max(0.0, 1.0 - dist_s / med), 3)
        if args.profile_dir:
            # emit to the durable channel BEFORE the trace capture: if the
            # profiler wedges the device, the timed numbers must survive it.
            # The post-trace emit re-writes the row with trace_dir (fold_r3
            # keeps the last row per variant).
            emit(dict(row), final=False)
            tdir = str(Path(args.profile_dir) / variant)
            with jax.profiler.trace(tdir):
                run()
                sync()
            row["trace_dir"] = tdir
            # per-category device-time split off the captured trace, via
            # the shared library parser (a failed parse lands as an
            # {"error": ...} block, never a zero-filled split)
            from mpi_knn_tpu.obs.attribution import attribute_trace

            row["device_time"] = attribute_trace(tdir)
        emit(row)

    summary = {
        "workload": f"all-kNN m={args.m} d={args.d} k={args.k}",
        "useful_tflop": round(useful_flop / 1e12, 3),
        "platform": jax.default_backend(),
        "peak_bf16_tflops": peak / 1e12,
        "results": results,
    }
    print(json.dumps(summary))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
