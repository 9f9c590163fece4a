"""Driver of the closed-loop batch job over a corpus ring: one process, one
caller, every chip of the host.

``drivers/allknn.py``'s loop over a corpus that no chip holds. Set-up makes
each chip's shard on that chip (the class centres are the corpus's, the
shard number is folded into the key of its rows), assembles them into one
array sharded by rows over the ring's mesh, and runs one warm call. The
window calls ``api.all_knn(X, queries=X[lo:lo+q], query_ids=arange(lo,
lo+q))`` with the configuration's ring backend for consecutive slices from a
seeded offset, each ending in ``block_until_ready``; a slice is cut from the
shard that holds it and laid over the ring as the queries of a ring call
lie, inside the timed call. The window closes at the end of the call during
which ``--seconds`` ran out. After it a seeded sample of the rows it
answered is compared with the plain reference, computed shard by shard
(``reference_sharded.py``). Nothing of corpus size is ever on one chip or on
the host.

A traced run hands the per-layer readers ``ring``: each chip's device
events inside the traced span, the span's bounds, the chips, and what the
program's ``ring_*`` counters moved by in it (``None`` for a program that
has none).
"""

from __future__ import annotations

import re
import shutil
import time

import numpy as np

RING_COUNTERS = ("ring_calls_total", "ring_rounds_total",
                 "ring_wire_bytes_total")


def shard_rows(gen, seed: int, shard: int, rows: int, dim: int, spec: dict,
               device, chunk_rows: int = 65536):
    """(rows, dim) float32 on ``device``: ``datagen/clustered_u8``'s rows
    (a centre plus noise, rounded, clipped) around the corpus's centres,
    drawn from a key that holds the shard's number, in chunks."""
    import jax
    import jax.numpy as jnp

    if rows % chunk_rows:
        chunk_rows = int(np.gcd(rows, chunk_rows))
    cen = jax.device_put(gen.centres(seed, spec, dim), device)
    sigma = float(spec["sigma"])
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31),
        shard)

    @jax.jit
    def make(key, cen):
        def body(i, buf):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            which = jax.random.randint(k1, (chunk_rows,), 0, cen.shape[0])
            x = cen[which] + jax.random.normal(
                k2, (chunk_rows, dim), jnp.float32) * sigma
            x = jnp.clip(jnp.rint(x), 0.0, 255.0)
            return jax.lax.dynamic_update_slice(buf, x, (i * chunk_rows, 0))

        return jax.lax.fori_loop(
            0, rows // chunk_rows, body, jnp.zeros((rows, dim), jnp.float32))

    return make(jax.device_put(key, device), cen)


def ring_counters() -> dict | None:
    """The program's ``ring_*`` counters as they stand, or None where the
    program has none (a commit before they were added)."""
    from mpi_knn_tpu.obs.metrics import get_registry

    snap = get_registry().snapshot()["metrics"]
    found = {n: float(snap[n]["value"]) for n in RING_COUNTERS if n in snap}
    return found or None


def ring_record(xplane_path, chips: int, before, after) -> dict | None:
    """What the ``ring_*`` readers read: per chip the device events
    ``(name, start_s, seconds)`` inside the traced span, and the counters'
    movement over it."""
    from benchmark import trace

    if xplane_path is None:
        return None
    events = trace.read_xplane(xplane_path)
    spans = [(s, s + d) for n, s, d in events["host"]
             if n == trace.WINDOW_ANNOTATION]
    if not spans or not events["device"]:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    per_chip = [
        [(n, max(s, lo), min(s + d, hi) - max(s, lo))
         for n, s, d in evs if s < hi and s + d > lo]
        for _, evs in sorted(events["device"].items())
    ]
    delta = None
    if before is not None and after is not None:
        delta = {n: after[n] - before.get(n, 0.0) for n in after}
    return {"chips": chips, "window": [lo, hi], "events": per_chip,
            "counters_delta": delta}


NO_SCOPE = "(no knn scope)"
# the scopes the program nests under a ``knn.*`` one
SUB_SCOPES = ("permute", "round", "bins", "finish", "fallback")


def scope_key(op_name: str) -> str:
    """The innermost ``knn.*`` scope of an HLO ``op_name``, with the
    sub-scope the program nests in it: ``.../knn.ring/round/while/body/
    knn.select/bins/pallas_call:`` is ``knn.select/bins``."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        found = re.search(r"knn\.[a-z_]+", parts[i])
        if found:
            nxt = parts[i + 1] if i + 1 < len(parts) else ""
            return (f"{found.group(0)}/{nxt}" if nxt in SUB_SCOPES
                    else found.group(0))
    return NO_SCOPE


def ring_scopes(xplane_path: str, lo: float, hi: float) -> list | None:
    """Own device seconds inside [lo, hi] by the program's innermost
    ``knn.*`` scope, mean of the chips, largest first: the breakdown in
    which a permute reads ``knn.ring/permute``. The names are in the
    trace's ``tf_op`` stat, which the program's own reader of the file
    gives; None where it gives none (a commit before it did)."""
    from benchmark import trace
    from mpi_knn_tpu.obs.xplane import parse_xplane

    chips: dict = {}
    for e in parse_xplane(xplane_path):
        if e["line"] != trace.OPS_LINE or "scope" not in e:
            continue
        s, d = e["start_ps"] * 1e-12, e["dur_ps"] * 1e-12
        if s < hi and s + d > lo:
            chips.setdefault(e["plane"], []).append(
                (scope_key(e["scope"]), max(s, lo), min(s + d, hi) - max(s, lo)))
    if not chips:
        return None
    totals: dict = {}
    for events in chips.values():
        for key, sec in trace.self_times(events).items():
            totals[key] = totals.get(key, 0.0) + sec / len(chips)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])]


def run(cell: dict, args, t_start: float):
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import (compare, harness, opcount_ring, reference_sharded,
                           trace)
    from benchmark.harness import say

    config, traffic = cell["config"], cell["traffic"]
    chips = int(cell["chips"])
    device, chip_wait_s = harness.find_chip(chips, args.allow_cpu)
    peaks = harness.peaks_for(device["kind"], args.allow_cpu)
    say(f"compile cache: {harness.compile_cache()}")
    from mpi_knn_tpu import api
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    cfg = harness.knn_config(config, args.control)
    if cfg.num_devices != chips:
        raise SystemExit(f"error: the cell has {chips} chips, the "
                         f"configuration's ring {cfg.num_devices} devices")
    rows, dim, k = config["rows"], config["dim"], config["k"]
    if args.control and "rows" in config["control"]:
        rows = int(config["control"]["rows"])
        say(f"control: {rows} rows of the configuration's {config['rows']}")
    q = int(traffic["slice_rows"])
    rows_chip = rows // chips
    if rows % chips or rows_chip % q:
        raise SystemExit(f"error: {rows} rows do not lie as {chips} shards "
                         f"of whole {q}-row slices")
    gen = harness.datagen_for(config)
    # the mesh the program itself builds for this configuration: a corpus
    # laid over it is where the ring wants it
    mesh = make_ring_mesh(cfg.num_devices, axis_name=cfg.mesh_axis)
    ring_devices = list(mesh.devices.flat)
    by_rows = NamedSharding(mesh, PartitionSpec(cfg.mesh_axis))
    shards = [shard_rows(gen, args.seed, s, rows_chip, dim, config["data"], d)
              for s, d in enumerate(ring_devices)]
    X = jax.make_array_from_single_device_arrays((rows, dim), by_rows, shards)
    X.block_until_ready()
    say(f"corpus {X.shape} {X.dtype}, {rows_chip} rows a chip on {chips} x "
        f"{device['kind']} in {time.time() - t_start:.2f}s")
    say("ring order (device id, coords): " + ", ".join(
        f"{d.id} {getattr(d, 'coords', None)}" for d in ring_devices))

    take = jax.jit(
        lambda x, lo: jax.lax.dynamic_slice_in_dim(x, lo, q, axis=0))
    n_slices = rows // q
    rng = np.random.default_rng([int(args.seed), 0xA1])
    first = int(rng.integers(0, n_slices))

    def call(slice_no: int):
        lo = (slice_no % n_slices) * q
        with jp.TraceAnnotation("bench:all_knn_call"):
            # X[lo:lo+q]: cut from the shard that holds it, laid over the
            # ring by rows (what a ring call does with its queries anyway)
            s, off = divmod(lo, rows_chip)
            queries = jax.device_put(take(shards[s], jnp.int32(off)), by_rows)
            res = api.all_knn(
                X, queries=queries,
                query_ids=np.arange(lo, lo + q, dtype=np.int32), config=cfg,
                mesh=mesh,
            )
            jax.block_until_ready((res.dists, res.ids))
        return lo, res

    # warm: every program the window uses, the slicer of each chip among them
    for s in range(chips):
        take(shards[s], jnp.int32(0)).block_until_ready()
    call(first - 1)
    setup_s = time.time() - t_start - chip_wait_s
    say(f"setup_s {setup_s:.3f}")

    span = trace.TracedSpan(f"{harness.OUT_DIR}/{cell['name']}/trace")
    shutil.rmtree(span.log_dir, ignore_errors=True)  # the last run's trace
    trace_s = float(traffic["trace_seconds"])
    done, walls, traced_walls = [], [], []
    counters_before = counters_after = None
    t0 = at = time.perf_counter()
    while at - t0 < args.seconds:
        if args.trace and len(done) == 1:  # the traced span: whole calls
            counters_before = ring_counters()
            span.start()
        before = time.perf_counter()
        done.append(call(first + len(done)))
        at = time.perf_counter()
        walls.append(at - before)
        if span.running:
            traced_walls.append(at - before)
            if at - span.started_at >= trace_s:
                span.stop()
                counters_after = ring_counters()
                at = time.perf_counter()
    if span.running:
        span.stop()
        counters_after = ring_counters()
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
    ref_d, ref_i = reference_sharded.exact_knn(
        X, reference_sharded.take_rows(X, probe_ids), k,
        self_ids=probe_ids if config["exclude_self"] else None,
        exclude_zero=config["exclude_zero"])
    verdict = compare.compare_answers(
        ids_d[pick], dists_d[pick], ref_i, ref_d, config["limits"])
    verdict["numbers"]["window_answers_misshapen"] = [
        0 if whole else 1, 0, whole]
    compare.say(verdict["numbers"], info=verdict.get("info"))
    say(f"reference and comparison {time.perf_counter() - t_ref:.2f}s "
        f"({len(pick)} probe rows, {chips} shards)")
    correct = bool(verdict["ok"] and whole)

    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    fullest = max((d.memory_stats() or {} for d in jax.local_devices()),
                  key=lambda st: st.get("peak_bytes_in_use", 0))
    say(f"memory stats of the fullest chip: {fullest}")
    say(f"memory peak of the fullest chip: program {peak_program} B "
        f"({peak_program / (rows_chip * dim * 4):.2f} shards), with the "
        f"reference {device['memory_peak_bytes']} B")
    result = {
        "correct": correct,
        "attempted": len(done),
        "failed": 0,
        "metrics": harness.end_to_end(cell, {
            "rows_per_s": rows_done / window_s, "setup_s": setup_s}),
        "device": device,
    }
    if args.trace:
        xplane = trace.newest_xplane(span.log_dir)
        ring = ring_record(xplane, chips, counters_before, counters_after)
        traced_rows = len(traced_walls) * q
        if ring is not None and peaks:
            least, bound = opcount_ring.chip_least_seconds(
                traced_rows, len(traced_walls), chips, rows, dim, k, peaks)
            ring["chip_least_s"] = least
            say(f"ring: one chip's share of {traced_rows} rows in "
                f"{len(traced_walls)} calls, least {least:.4f}s ({bound} "
                f"bound applies); counters moved by {ring['counters_delta']}")
        harness.add_trace(
            result, cell, span.summary(allow_empty=args.allow_cpu), peaks,
            q_rows=traced_rows, batches=len(traced_walls),
            traced_call_walls_s=traced_walls, ring=ring)
        if ring is not None and "breakdown" in result:
            scopes = ring_scopes(xplane, *ring["window"])
            if scopes:
                result["breakdown"]["ring_scopes"] = scopes
    return result
