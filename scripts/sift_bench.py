"""SIFT1M-scale single-chip benchmark: wall-clock + sampled recall for the
`BASELINE.json` configs[4] shape (1M × 128), L2 and cosine, exact and
approx top-k (VERDICT r2 next-step #3).

One JSON line per measurement on stdout; a watchdog thread emits an honest
failure line and hard-exits if the device stops responding (same rationale
as bench.py). Scale up with --m; checkpointing is exercised separately by
the resume tests — here the corpus is synthetic and regenerable, so the
watchdog-kill-and-rerun loop is the failure plan.

Usage:
    python scripts/sift_bench.py --m 100000 --metric l2 --topk exact
    python scripts/sift_bench.py --m 1000000 --metric cosine --topk approx
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_DONE = threading.Event()


def oracle_sample(X: np.ndarray, sample: np.ndarray, k: int, metric: str):
    """f64 host ground truth for the sampled queries, corpus-chunked."""
    Q = X[sample].astype(np.float64)
    m = X.shape[0]
    best_d = np.full((len(sample), 0), np.inf)
    best_i = np.zeros((len(sample), 0), dtype=np.int64)
    if metric == "cosine":
        qn = Q / np.linalg.norm(Q, axis=1, keepdims=True)
    for lo in range(0, m, 200_000):
        C = X[lo : lo + 200_000].astype(np.float64)
        if metric == "l2":
            d = (
                (Q**2).sum(1)[:, None]
                + (C**2).sum(1)[None, :]
                - 2.0 * (Q @ C.T)
            )
            d[d <= 1e-9] = np.inf  # reference zero-exclusion (SURVEY Q3)
        else:
            cn = C / np.linalg.norm(C, axis=1, keepdims=True)
            d = 1.0 - qn @ cn.T
            d[d <= 1e-12] = np.inf
        ids = np.arange(lo, lo + C.shape[0])[None, :].repeat(len(sample), 0)
        # exact self-exclusion for sampled corpus rows
        own = (ids == sample[:, None])
        d[own] = np.inf
        best_d = np.concatenate([best_d, d], axis=1)
        best_i = np.concatenate([best_i, ids], axis=1)
        keep = np.argsort(best_d, axis=1, kind="stable")[:, : max(k, 64)]
        best_d = np.take_along_axis(best_d, keep, 1)
        best_i = np.take_along_axis(best_i, keep, 1)
    order = np.argsort(best_d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(best_i, order, 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--m", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--metric", choices=["l2", "cosine"], default="l2")
    ap.add_argument("--topk", choices=["exact", "approx"], default="approx")
    ap.add_argument("--recall-target", type=float, default=0.999)
    ap.add_argument("--query-tile", type=int, default=4096)
    ap.add_argument("--corpus-tile", type=int, default=8192)
    ap.add_argument("--schedule", default="twolevel")
    ap.add_argument("--precision", default="high")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--sample", type=int, default=256)
    ap.add_argument("--watchdog-s", type=float,
                    default=float(os.environ.get("SIFT_WATCHDOG_S", "900")))
    ap.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                    default="auto")
    args = ap.parse_args(argv)

    def fire():
        if _DONE.is_set():
            return
        print(json.dumps({
            "metric": f"sift{args.m // 1000}k_allknn_k{args.k}_seconds",
            "m": args.m, "mtr": args.metric, "topk": args.topk,
            "value": args.watchdog_s, "unit": "s", "failed": True,
            "error": "watchdog: device unresponsive",
        }), flush=True)
        os._exit(2)

    if args.watchdog_s > 0:
        t = threading.Timer(args.watchdog_s, fire)
        t.daemon = True
        t.start()

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        force_platform(args.platform)
    use_compile_cache()

    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu import KNNConfig, all_knn
    from mpi_knn_tpu.data.synthetic import make_sift_like
    from mpi_knn_tpu.utils.report import recall_at_k
    from mpi_knn_tpu.utils.timing import device_sync

    X = make_sift_like(m=args.m, d=args.d)
    cfg = KNNConfig(
        k=args.k,
        metric=args.metric,
        backend="serial",
        query_tile=args.query_tile,
        corpus_tile=args.corpus_tile,
        merge_schedule=args.schedule,
        topk_method=args.topk,
        recall_target=args.recall_target,
        matmul_precision=args.precision,
    )
    Xd = jax.device_put(jnp.asarray(X))
    device_sync(Xd)

    res = all_knn(Xd, config=cfg)  # compile + warm
    device_sync(res.dists)
    times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        res = all_knn(Xd, config=cfg)
        device_sync(res.dists, res.ids)
        times.append(time.perf_counter() - t0)

    sample = np.linspace(0, args.m - 1, num=min(args.sample, args.m),
                         dtype=np.int64)
    got = np.asarray(jax.device_get(res.ids[jnp.asarray(sample)]))
    want = oracle_sample(X, sample, args.k, args.metric)
    recall = recall_at_k(got, want)

    _DONE.set()
    print(json.dumps({
        "metric": f"sift{args.m // 1000}k_allknn_k{args.k}_seconds",
        "m": args.m, "d": args.d, "k": args.k,
        "mtr": args.metric, "topk": args.topk,
        "value": round(float(np.median(times)), 4), "unit": "s",
        "times": [round(x, 4) for x in times],
        "recall_at_k_vs_oracle": round(float(recall), 5),
        "platform": jax.default_backend(),
        "schedule": args.schedule, "precision": args.precision,
        "tiles": [cfg.query_tile, cfg.corpus_tile],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
