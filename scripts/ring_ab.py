"""A/B benchmark: blocking-schedule ring vs overlapped ring (BASELINE.md
configs "blocking ring" / "non-blocking (overlapped) 8-way ring"), crossed
with the rotation-schedule axis (uni vs bidir full-duplex counter-rotation,
``cfg.ring_schedule``) — a 2×2 matrix per run.

The reference shipped the sequencing A/B as two whole programs and the B
side never actually overlapped (MPI_Wait before compute — SURVEY.md Q7).
Here all four cells share one implementation (backends/ring.py: overlap
flag × ring_schedule); this harness times them on identical data/mesh and
reports the ratios, which on real multi-chip hardware quantify (a) how much
ICI transfer hides under the distance matmul and (b) how much of the
remaining exposed communication the bidirectional schedule's halved
critical path buys back. On a CPU-simulated mesh the ratios are meaningless
(collectives are memcpys) — the harness still runs for mechanics testing
and for the four-way bit-agreement check.

``--dp`` builds a 2-D mesh, on which the blocking schedule is undefined
(the barrier can pin only the block there — see DESIGN.md §3), so the A/B
refuses it: the 1-D ring is the only defined A/B object.

Usage:
    python scripts/ring_ab.py --m 60000 --d 784 --k 10 [--devices N]
                              [--schedule uni|bidir|both] [--reps 3]
                              [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

# runnable as `python scripts/ring_ab.py` from anywhere
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--m", type=int, default=60000)
    ap.add_argument("--d", type=int, default=784)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--schedule", choices=["uni", "bidir", "both"],
                    default="both",
                    help="rotation schedule axis of the A/B matrix "
                    "(default: both)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--query-tile", type=int, default=1024)
    ap.add_argument("--corpus-tile", type=int, default=4096)
    ap.add_argument("--json", default=None, help="also write results here")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture one XProf trace per schedule into "
                    "DIR/{blocking,overlap} — the overlap-evidence artifact "
                    "(where does the ppermute DMA sit relative to the "
                    "distance matmul?)")
    ap.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                    default="auto")
    args = ap.parse_args(argv)

    from mpi_knn_tpu.utils.platform import force_platform, use_compile_cache

    if args.platform != "auto":
        force_platform(args.platform)
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu import KNNConfig, all_knn
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh
    from mpi_knn_tpu.utils.report import recall_at_k
    from mpi_knn_tpu.utils.timing import device_sync

    n_dev = args.devices or len(jax.devices())
    if args.dp > 1:
        # the blocking A side is undefined on a 2-D mesh (DESIGN.md §3) —
        # running only the B side would not be an A/B
        raise SystemExit(
            "--dp is not a valid A/B axis: the blocking schedule is "
            "undefined on a dp×ring mesh (the barrier can pin only the "
            "block there). The 1-D ring is the only defined A/B object."
        )
    mesh = make_ring_mesh(n_dev)

    rng = np.random.default_rng(0)
    X = rng.standard_normal((args.m, args.d)).astype(np.float32)
    Xd = jax.device_put(jnp.asarray(X))
    device_sync(Xd)

    schedules = (
        ("uni", "bidir") if args.schedule == "both" else (args.schedule,)
    )
    results = {}
    ids = {}
    for sched in schedules:
        for name, backend in (("blocking", "ring"),
                              ("overlap", "ring-overlap")):
            cell = f"{sched}-{name}"
            cfg = KNNConfig(
                k=args.k,
                backend=backend,
                query_tile=args.query_tile,
                corpus_tile=args.corpus_tile,
                ring_schedule=sched,
            )
            res = all_knn(Xd, config=cfg, mesh=mesh)  # compile + warm
            device_sync(res.dists)
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                res = all_knn(Xd, config=cfg, mesh=mesh)
                device_sync(res.dists, res.ids)
                times.append(time.perf_counter() - t0)
            results[cell] = min(times)
            if args.profile_dir:
                tdir = str(Path(args.profile_dir) / cell)
                with jax.profiler.trace(tdir):
                    res = all_knn(Xd, config=cfg, mesh=mesh)
                    device_sync(res.dists, res.ids)
            # sample neighbor ids for the all-cells-agree sanity check
            sample = jnp.asarray(
                np.linspace(0, args.m - 1, num=min(128, args.m),
                            dtype=np.int64)
            )
            ids[cell] = np.asarray(jax.device_get(res.ids[sample]))

    ref_cell = next(iter(ids))
    same = min(
        recall_at_k(got, ids[ref_cell]) for got in ids.values()
    )
    out = {
        "m": args.m,
        "d": args.d,
        "k": args.k,
        "mesh": list(np.asarray(mesh.devices).shape),
        "platform": jax.default_backend(),
        "cells_s": {c: round(t, 4) for c, t in results.items()},
        "results_agree": round(float(same), 5),
    }
    for sched in schedules:
        if f"{sched}-blocking" in results:
            out[f"speedup_overlap_{sched}"] = round(
                results[f"{sched}-blocking"] / results[f"{sched}-overlap"], 3
            )
    if len(schedules) == 2:
        # the headline of the schedule axis: exposed-communication critical
        # path halves, so bidir/uni quantifies what that buys per variant
        for name in ("blocking", "overlap"):
            out[f"speedup_bidir_{name}"] = round(
                results[f"uni-{name}"] / results[f"bidir-{name}"], 3
            )
    print(json.dumps(out))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
