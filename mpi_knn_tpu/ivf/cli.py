"""``mpi-knn build-index`` — train the k-means partitioner and save a
clustered (IVF) index to ``.npz``.

The offline half of the sublinear serving story: cluster once here, then
``mpi-knn query --index-load corpus.ivf.npz`` serves the saved partitions
through the bucketed AOT executable cache (zero steady-state compiles,
probed bytes per query = nprobe/partitions of the corpus).

``--backend ring`` is accepted and means the SHARDED deployment shape
(`mpi_knn_tpu.ivf.sharded`): training is still single-device math
(clustering is layout-independent), the saved ``.npz`` is identical, and
the shard layout is DERIVED at serve time from ``--devices`` — one
artifact serves on any shard count (``mpi-knn query --index-load …
--backend ring --devices 4``).

Flag combinations the clustered path cannot honor are refused with a loud
exit 2 (the serve-CLI convention — never silently build a different index
than the one requested): a non-L2 metric (the k-means partitioner is
L2 geometry), float64 (the dense backends' debug mode),
nprobe > partitions.

Examples::

    mpi-knn build-index --data sift:100000 --partitions 256 --out sift.ivf.npz
    mpi-knn build-index --data corpus.mat --partitions 64 --nprobe 8 \
        --out corpus.ivf.npz
    mpi-knn query --data sift:100000 --index-load sift.ivf.npz --synthetic 4096
    mpi-knn query --data sift:100000 --index-load sift.ivf.npz \
        --backend ring --devices 4 --synthetic 4096   # sharded serving
"""

from __future__ import annotations

import argparse
import sys
import time

from mpi_knn_tpu.config import KMEANS_INITS, KNNConfig


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi-knn build-index",
        description="train a k-means-clustered (IVF) index and save it "
        "(.npz); query it with `mpi-knn query --index-load`",
    )
    d = p.add_argument_group("data")
    d.add_argument("--data", default="mnist",
                   help="corpus spec (same forms as the run driver: "
                   "'mnist', 'digits', 'synthetic:MxDcC', 'sift:M', "
                   "*.fvecs/bvecs, or a .mat file)")
    d.add_argument("--limit", type=int, default=None,
                   help="use first N corpus rows only")

    k = p.add_argument_group("index")
    k.add_argument("--partitions", type=int, required=True,
                   help="k-means partitions (the sublinear axis: probed "
                   "bytes per query are nprobe/partitions of the corpus)")
    k.add_argument("--nprobe", type=int, default=None,
                   help="partitions probed per query; default: auto-tune "
                   "the smallest nprobe reaching --recall-target on a "
                   "held-out corpus sample vs the brute-force oracle")
    k.add_argument("--recall-target", type=float, default=0.95,
                   help="recall@k target for the nprobe auto-tune")
    k.add_argument("--k", type=int, default=10,
                   help="neighbors the auto-tune measures recall@k at")
    k.add_argument("--metric", default="l2", choices=["l2", "cosine"],
                   help="l2 only — cosine is refused loudly (the k-means "
                   "partitioner and centroid score are L2 geometry)")
    k.add_argument("--backend", default="auto",
                   help="serial/auto (single-device) or ring (the sharded "
                   "deployment shape — training is identical; the shard "
                   "layout is derived at serve time, so the saved index "
                   "is the same artifact)")
    k.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16", "int8", "int4"],
                   help="bucket-store at-rest dtype; bfloat16 halves "
                   "resident HBM and probe-gather bytes; int8/int4 are "
                   "the block-scaled quantized levels (~4x/8x cuts, "
                   "codes + per-row scales, asymmetric distance with "
                   "exact f32 queries — ops/quant.py)")
    k.add_argument("--kmeans-iters", type=int, default=25,
                   help="fixed Lloyd iteration budget (single compiled "
                   "executable)")
    k.add_argument("--kmeans-init", choices=list(KMEANS_INITS),
                   default="kmeans++")
    k.add_argument("--seed", type=int, default=0,
                   help="PRNG seed threading init + re-seeding "
                   "(bit-deterministic training per seed)")

    o = p.add_argument_group("output")
    o.add_argument("--out", required=True, metavar="PATH.npz",
                   help="where to save the index")
    o.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                   default="auto")
    o.add_argument("-q", "--quiet", action="store_true")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        force_platform(args.platform)
    use_compile_cache()

    from mpi_knn_tpu.cli import load_corpus
    from mpi_knn_tpu.ivf import build_ivf_index, save_ivf_index

    X, _, source = load_corpus(args.data, limit=args.limit)

    # --backend ring = the sharded deployment shape: the k-means training
    # and the saved artifact are IDENTICAL (the shard layout is derived at
    # serve time), so the build itself runs the single-device path — the
    # old exit-2 refusal is lifted into real support, not silently mapped
    backend = args.backend
    sharded = backend in ("ring", "ring-overlap")
    if sharded:
        backend = "auto"

    try:
        cfg = KNNConfig(
            k=args.k,
            metric=args.metric,
            backend=backend,
            dtype=args.dtype,
            recall_target=args.recall_target,
            partitions=args.partitions,
            nprobe=args.nprobe,
            kmeans_iters=args.kmeans_iters,
            kmeans_init=args.kmeans_init,
            ivf_seed=args.seed,
        )
    except ValueError as e:
        # invalid knob combination (cosine metric, nprobe > partitions…):
        # loud usage error, never a silently-adjusted index
        print(f"error: {e}", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    try:
        index = build_ivf_index(X, cfg)
    except ValueError as e:
        # the clustered path cannot honor this combination (non-serial
        # backend, partitions > corpus rows, float64 …)
        print(f"error: {e}", file=sys.stderr)
        return 2
    build_s = time.perf_counter() - t0
    path = save_ivf_index(index, args.out)

    if not args.quiet:
        tuned = (
            f"auto-tuned nprobe={index.nprobe} "
            f"(recall@{args.k}={index.tuned_recall:.4f} vs brute force)"
            if index.tuned_recall is not None
            else f"nprobe={index.nprobe}"
        )
        frac = index.probe_bytes / max(index.nbytes_resident, 1)
        print(
            f"[mpi-knn build-index] {source} shape={X.shape} -> "
            f"{index.partitions} partitions (bucket_cap="
            f"{index.bucket_cap}), {tuned}; probes "
            f"{100 * frac:.1f}% of corpus bytes/query; "
            f"train+tune {build_s:.2f}s; saved {path}"
        )
        if sharded:
            print(
                "[mpi-knn build-index] --backend ring noted: the shard "
                "layout is derived at serve time — serve this artifact "
                "with `mpi-knn query --index-load ... --backend ring "
                "--devices N` on any shard count"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
