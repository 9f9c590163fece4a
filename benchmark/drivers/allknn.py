"""Driver of the closed-loop batch job: one process, one caller.

Set-up makes the corpus on the device and runs one warm call. The window
calls ``api.all_knn(X, queries=X[lo:lo+q], query_ids=arange(lo, lo+q))`` —
the public API's form for a slice of an all-pairs job that keeps
self-exclusion — for consecutive slices from a seeded offset, each ending
in ``block_until_ready``. The window closes at the end of the call during
which ``--seconds`` ran out, and the rate is all rows of the window over all
its time. After the window a seeded sample of the rows it answered is
compared with the plain reference.
"""

from __future__ import annotations

import shutil
import time

import numpy as np


def run(cell: dict, args, t_start: float):
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    from benchmark import compare, harness, reference, trace
    from benchmark.harness import say

    config, traffic = cell["config"], cell["traffic"]
    device, chip_wait_s = harness.find_chip(cell["chips"], args.allow_cpu)
    peaks = harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"compile cache: {harness.compile_cache()}")
    from mpi_knn_tpu import api

    cfg = harness.knn_config(config, args.control)
    rows, dim, k = config["rows"], config["dim"], config["k"]
    if args.control and "rows" in config["control"]:
        # the program's own lower-precision path needs more scratch than
        # fits beside the whole corpus: the control runs at fewer rows
        rows = int(config["control"]["rows"])
        say(f"control: {rows} rows of the configuration's {config['rows']}")
    q = int(traffic["slice_rows"])
    gen = harness.datagen_for(config)
    X = gen.device_corpus(args.seed, rows, dim, config["data"])
    X.block_until_ready()
    say(f"corpus {X.shape} {X.dtype} on {device['kind']} "
        f"in {time.time() - t_start:.2f}s")

    take = jax.jit(
        lambda x, lo: jax.lax.dynamic_slice_in_dim(x, lo, q, axis=0))
    n_slices = rows // q
    rng = np.random.default_rng([int(args.seed), 0xA1])
    first = int(rng.integers(0, n_slices))

    def call(slice_no: int):
        lo = (slice_no % n_slices) * q
        with jp.TraceAnnotation("bench:all_knn_call"):
            res = api.all_knn(
                X, queries=take(X, jnp.int32(lo)),
                query_ids=np.arange(lo, lo + q, dtype=np.int32), config=cfg,
            )
            jax.block_until_ready((res.dists, res.ids))
        return lo, res

    call(first - 1)  # warm: every program the window uses
    # what the user waited for before the first timed call, less the
    # runtime's hand-over of the chip (the machine's, not the program's)
    setup_s = time.time() - t_start - chip_wait_s
    say(f"setup_s {setup_s:.3f}")

    span = trace.TracedSpan(f"{harness.OUT_DIR}/{cell['name']}/trace")
    shutil.rmtree(span.log_dir, ignore_errors=True)  # the last run's trace
    trace_s = float(traffic["trace_seconds"])
    done, walls, traced_walls = [], [], []
    t0 = at = time.perf_counter()
    while at - t0 < args.seconds:
        if args.trace and len(done) == 1:  # the traced span: whole calls
            span.start()
        before = time.perf_counter()
        done.append(call(first + len(done)))
        at = time.perf_counter()
        walls.append(at - before)
        if span.running:
            traced_walls.append(at - before)
            if at - span.started_at >= trace_s:
                span.stop()
                at = time.perf_counter()
    span.stop()
    window_s = at - t0
    rows_done = len(done) * q
    say(f"window {window_s:.4f}s calls {len(done)} rows {rows_done} "
        f"call wall median {np.median(walls):.4f}s "
        f"min {min(walls):.4f}s max {max(walls):.4f}s")
    for i, w in enumerate(walls):
        if w > 1.5 * float(np.median(walls)):
            say(f"slow call: number {i} of the window took {w:.4f}s")
    peak_program = harness.memory_peak_bytes()

    # the check: a seeded sample of the rows the window answered
    t_ref = time.perf_counter()
    n_probe = int(traffic["probe_rows"])
    pick = rng.choice(rows_done, size=min(n_probe, rows_done), replace=False)
    ids_d = np.concatenate([np.asarray(r.ids) for _, r in done])
    dists_d = np.concatenate([np.asarray(r.dists) for _, r in done])
    row_ids = np.concatenate(
        [np.arange(lo, lo + q, dtype=np.int32) for lo, _ in done])
    whole = (ids_d.shape == (rows_done, k) and dists_d.shape == (rows_done, k))
    probe_ids = row_ids[pick]
    ref_d, ref_i = reference.exact_knn(
        X, np.asarray(X[jnp.asarray(probe_ids)]), k,
        self_ids=probe_ids if config["exclude_self"] else None,
        exclude_zero=config["exclude_zero"])
    verdict = compare.compare_answers(
        ids_d[pick], dists_d[pick], ref_i, ref_d, config["limits"])
    verdict["numbers"]["window_answers_misshapen"] = [
        0 if whole else 1, 0, whole]
    compare.say(verdict["numbers"], info=verdict.get("info"))
    say(f"reference and comparison {time.perf_counter() - t_ref:.2f}s "
        f"({len(pick)} probe rows)")
    correct = bool(verdict["ok"] and whole)

    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    say(f"memory peak: program {peak_program} B, with the reference "
        f"{device['memory_peak_bytes']} B")
    result = {
        "correct": correct,
        "attempted": len(done),
        "failed": 0,
        "metrics": harness.end_to_end(cell, {
            "rows_per_s": rows_done / window_s, "setup_s": setup_s}),
        "device": device,
    }
    if args.trace:
        harness.add_trace(
            result, cell, span.summary(allow_empty=args.allow_cpu), peaks,
            q_rows=len(traced_walls) * q, batches=len(traced_walls),
            traced_call_walls_s=traced_walls)
    return result
