"""``mpi-knn doctor`` — preflight device health probe.

Answers one operator question before a bench round or a serving run:
*will a tiny jitted program actually complete on this device, soon?* The
probe (compile a small dot, run it, ``device_sync`` the result) runs in
its OWN subprocess under the worker runner's heartbeat watchdog — a
wedged transport wedges the probe child, never the caller — and the
verdict is a single structured JSON line with exit status 0/1, so it
slots into shell pipelines and the bench supervisor alike::

    mpi-knn doctor                      # probe the default platform
    mpi-knn doctor --platform cpu       # force a platform
    mpi-knn doctor --timeout 30         # beat-starvation bound (s)
    BENCH_DOCTOR=1 python bench.py      # bench runs it as preflight

Verdict schema: ``{"ok": bool, "status": "ok"|"timeout"|"crashed",
"probe": {platform, device_count, jit_probe_s} | null,
"metrics": <obs registry snapshot with the probe's compile count/
duration> | null, "beats": N, "last_beat": label, "elapsed_s": s,
"reason": str|null, "flight": <banked span summary> | null}``.

The supervisor half of this module never imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from mpi_knn_tpu.resilience.worker import python_worker_argv, run_supervised

DEFAULT_BEAT_TIMEOUT_S = 60.0
DEFAULT_WALL_TIMEOUT_S = 180.0


def _probe_child(platform: str, cache_dir: str | None = None) -> int:
    """The probe body, run inside the supervised worker subprocess: tiny
    jit + device_sync under heartbeats. Beats bracket every step that can
    hang so the supervisor's kill names the wedged step."""
    from mpi_knn_tpu.resilience.faults import fault_point
    from mpi_knn_tpu.resilience.heartbeat import maybe_beat

    maybe_beat("start")
    fault_point("doctor-probe")  # injectable wedge for tier-1
    if platform != "auto":
        from mpi_knn_tpu.utils.platform import force_platform

        force_platform(platform)
    maybe_beat("platform")
    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu.obs.metrics import (
        get_registry,
        install_jax_compile_listener,
    )
    from mpi_knn_tpu.utils.timing import device_sync

    # the verdict's metrics snapshot must capture the probe's own
    # compile, so the listener goes live before the jit below
    install_jax_compile_listener()
    maybe_beat("jax-import")
    devices = jax.devices()
    maybe_beat("devices")
    t0 = time.perf_counter()
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    # the probe keeps the Lowered/Compiled handles: the memory block
    # below cross-checks the SAME executable the health probe ran, so
    # one compile serves both verdict lines
    probe_compiled = jax.jit(lambda a: a @ a.T).lower(x).compile()
    y = probe_compiled(x)
    device_sync(y)
    probe_s = time.perf_counter() - t0
    maybe_beat("jit")
    print(
        json.dumps(
            {
                "platform": jax.default_backend(),
                "device_count": len(devices),
                "jit_probe_s": round(probe_s, 4),
            }
        ),
        flush=True,
    )
    # second stdout line: the probe's registry snapshot (compile count +
    # duration histogram via the central jax.monitoring capture) — the
    # supervisor folds it into the verdict as hard evidence the device
    # compiled and ran SOMETHING, not just that the process exited 0
    print(json.dumps({"metrics": get_registry().snapshot()}), flush=True)
    # third stdout line (ISSUE 12): the persistent AOT cache probe —
    # explicit --cache-dir wins, TKNN_AOT_CACHE is honored ambiently.
    # The round trip stores then revives a tiny executable through the
    # PRODUCTION cache path and compares outputs bit-for-bit, so the
    # verdict says "this dir on this platform can actually persist an
    # executable", not just "the dir exists"
    from mpi_knn_tpu.serve import aotcache

    cache = (aotcache.set_cache_dir(cache_dir) if cache_dir
             else aotcache.active_cache())
    if cache is not None:
        maybe_beat("aot-cache-probe")
        rt = aotcache.probe_roundtrip(cache)
        # stats AFTER the round trip so the entry count includes the
        # probe's own entry (0 entries + store_ok would read as broken)
        doc = {**cache.stats(), **rt}
        print(json.dumps({"aot_cache": doc}), flush=True)
        maybe_beat("aot-cache-done")
    # fourth stdout line (ISSUE 14): the live-mutation probe — a tiny
    # throwaway clustered index takes an upsert/delete/query round trip
    # TWICE; the second pass must compile NOTHING (the zero-steady-state
    # contract of the mutation executables, machine-counted from the
    # same jax.monitoring capture). Deleted ids must never come back.
    maybe_beat("mutation-probe")
    print(json.dumps({"mutation": _mutation_probe()}), flush=True)
    maybe_beat("mutation-done")
    # fifth stdout line (ISSUE 15): the memory block — the probe
    # executable's MEASURED memory_analysis() against the static
    # liveness analyzer's prediction over the same after-opt module
    # (analysis.memory, the R7 machinery). Disagreement beyond the
    # declared band means the certification pipeline itself is broken
    # on this host/jax pair — folded into overall ok.
    maybe_beat("memory-probe")
    print(json.dumps({"memory": _memory_probe(probe_compiled)}),
          flush=True)
    maybe_beat("memory-done")
    # sixth stdout line (ISSUE 16): the capacity-planner block — run
    # `mpi_knn_tpu.plan` against the probe-discovered device facts for a
    # tiny corpus, assert a feasible plan comes back AND its predicted
    # peak HBM covers the probe executable's own measured
    # memory_analysis() peak (the planner's conservative model must
    # bound what this runtime actually allocates) — folded into ok.
    maybe_beat("plan-probe")
    print(json.dumps({"plan": _plan_probe(probe_compiled)}), flush=True)
    maybe_beat("plan-done")
    return 0


def _memory_probe(compiled) -> dict:
    """Predict the probe executable's peak live bytes from its after-opt
    HLO (the R7 liveness analyzer) and cross-check against PJRT's own
    measured ``memory_analysis()`` — the doctor's evidence that the
    memory-certification stack tells the truth on THIS host."""
    from mpi_knn_tpu.analysis.memory import (
        analyze_module,
        crosscheck_pjrt,
        pjrt_memory_stats,
    )

    measured = pjrt_memory_stats(compiled)
    if measured is None:
        return {"ok": False,
                "reason": "runtime answered no memory_analysis()"}
    predicted = analyze_module(compiled.as_text())
    disagreements = crosscheck_pjrt(predicted, measured)
    return {
        "ok": not disagreements,
        "predicted_peak_bytes": predicted.peak_bytes,
        "measured": measured,
        "disagreements": disagreements,
    }


def _plan_probe(compiled) -> dict:
    """The doctor's capacity-planner round trip (ISSUE 16): plan a tiny
    corpus against THIS process's discovered device facts (platform →
    shipped profile, real device count) and hold the plan's predicted
    peak HBM against the probe executable's measured
    ``memory_analysis()`` peak. The probe program is deliberately tiny,
    so any feasible plan whose prediction does NOT cover it means the
    planner's memory model is broken on this host — hard evidence, zero
    extra compiles."""
    import jax

    from mpi_knn_tpu import plan as planner
    from mpi_knn_tpu.analysis.cost import profile_for_platform
    from mpi_knn_tpu.analysis.memory import pjrt_memory_stats

    dev = jax.devices()[0]
    name = profile_for_platform(dev.platform, dev.device_kind)
    if name is None:
        # never priced under another device's peaks
        return {"ok": False, "profile": None,
                "reason": f"no shipped device profile for "
                          f"{dev.platform} {dev.device_kind!r}"}
    wl = planner.Workload(m=4096, d=64, k=10, recall_target=0.9,
                          qps=0.0, bucket=256)
    fleet = planner.Fleet(devices=1, profile=name)
    try:
        doc = planner.plan(wl, fleet)
    except planner.Infeasible as e:
        return {"ok": False, "profile": name,
                "reason": f"tiny-corpus plan infeasible — "
                          f"{e.constraint}: {e.detail}"}
    except (OSError, ValueError, KeyError) as e:
        return {"ok": False, "profile": name,
                "reason": f"planner calibration unavailable: {e}"}
    measured = pjrt_memory_stats(compiled)
    probe_peak = measured["peak_bytes"] if measured else None
    predicted = doc["predicted"]["peak_hbm_bytes"]
    covered = probe_peak is None or predicted >= probe_peak
    return {
        "ok": bool(covered),
        "profile": name,
        "config": doc["config"],
        "predicted_peak_hbm_bytes": predicted,
        "probe_measured_peak_bytes": probe_peak,
        "predicted_qps": doc["predicted"]["qps"],
    }


def _mutation_probe() -> dict:
    """The doctor's mutation round trip (runs inside the supervised
    probe child, after jax import): throwaway 64-row clustered index,
    upsert → query → delete → query, twice — pass 2's compile count is
    the verdict's hard evidence that sustained churn compiles nothing."""
    import numpy as np

    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.ivf import build_ivf_index
    from mpi_knn_tpu.obs.metrics import watch_compiles
    from mpi_knn_tpu.serve.engine import query_knn

    rng = np.random.default_rng(0)
    cents = rng.standard_normal((4, 8)).astype(np.float32) * 6
    X = (cents[rng.integers(0, 4, 64)]
         + rng.standard_normal((64, 8)) * 0.1).astype(np.float32)
    index = build_ivf_index(X, KNNConfig(
        k=3, partitions=4, nprobe=4, kmeans_iters=4, query_tile=8,
        query_bucket=8, mutation_bucket=8, dispatch_depth=1,
        bucket_headroom=0.5,
    ))

    def round_trip(base_id: int) -> dict:
        ids = np.arange(base_id, base_id + 4)
        rows = (cents[0] + rng.standard_normal((4, 8)) * 0.05
                ).astype(np.float32)
        up = _sm().upsert_rows(index, ids, rows)
        got = query_knn(rows, index, index.cfg, k=3)
        found = bool(set(ids.tolist()) & set(got.ids.ravel().tolist()))
        _sm().delete_rows(index, ids)
        got2 = query_knn(rows, index, index.cfg, k=3)
        ghost = bool(set(ids.tolist()) & set(got2.ids.ravel().tolist()))
        return {"upserted": up["upserted"], "found": found,
                "ghost": ghost}

    pass1 = round_trip(1000)
    with watch_compiles() as counts:
        pass2 = round_trip(2000)
    compiles = len(counts)
    ok = (
        pass1["found"] and pass2["found"]
        and not pass1["ghost"] and not pass2["ghost"]
        and compiles == 0
    )
    return {
        "ok": ok,
        "pass1": pass1,
        "pass2": pass2,
        "second_pass_compiles": compiles,
    }


def _sm():
    from mpi_knn_tpu.serve import mutate as serve_mutate

    return serve_mutate


def run_probe(
    platform: str = "auto",
    beat_timeout_s: float = DEFAULT_BEAT_TIMEOUT_S,
    wall_timeout_s: float = DEFAULT_WALL_TIMEOUT_S,
    env: dict | None = None,
    cache_dir: str | None = None,
) -> dict:
    """Run the supervised probe and build the verdict document — shared
    by the CLI below and the bench supervisor's ``BENCH_DOCTOR=1``
    preflight (which must not print to its own stdout). ``cache_dir``
    (or an ambient ``TKNN_AOT_CACHE``) adds the persistent AOT cache
    block: dir, entry count, bytes, and a store/load round trip of a
    tiny probe executable."""
    argv = [
        "-m", "mpi_knn_tpu", "doctor", "--child",
        "--platform", platform,
    ]
    if cache_dir:
        argv += ["--cache-dir", cache_dir]
    res = run_supervised(
        python_worker_argv(*argv),
        env=env,
        beat_timeout_s=beat_timeout_s,
        wall_timeout_s=wall_timeout_s,
    )
    probe = None
    metrics = None
    aot_cache = None
    mutation = None
    memory = None
    plan = None
    if res.ok:
        for line in res.stdout.splitlines():
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(doc, dict) and "device_count" in doc:
                probe = doc
            elif isinstance(doc, dict) and "metrics" in doc:
                metrics = doc["metrics"]
            elif isinstance(doc, dict) and "aot_cache" in doc:
                aot_cache = doc["aot_cache"]
            elif isinstance(doc, dict) and "mutation" in doc:
                mutation = doc["mutation"]
            elif isinstance(doc, dict) and "memory" in doc:
                memory = doc["memory"]
            elif isinstance(doc, dict) and "plan" in doc:
                plan = doc["plan"]
    return {
        # the AOT cache block (ISSUE 12): None when no cache dir is
        # configured — absent, not a fake-healthy zero row
        "aot_cache": aot_cache,
        # the live-mutation block (ISSUE 14): upsert/delete/query round
        # trip on a throwaway index, with the SECOND pass's compile
        # count asserted zero (sustained churn must compile nothing) —
        # a failed mutation probe fails the verdict
        "mutation": mutation,
        # the memory-certification block (ISSUE 15): the probe
        # executable's measured memory_analysis() vs the R7 liveness
        # analyzer's prediction — a disagreement fails the verdict (the
        # ledger gate would be lying on this host); None-tolerant for
        # older probe children
        "memory": memory,
        # the capacity-planner block (ISSUE 16): a feasible tiny-corpus
        # plan from THIS host's discovered facts, with its predicted
        # peak HBM covering the probe executable's measured peak — an
        # uncovered probe fails the verdict (the planner would under-
        # promise memory on this host); None-tolerant for older children
        "plan": plan,
        "ok": bool(
            res.ok and probe is not None
            and (mutation is None or mutation.get("ok", False))
            and (memory is None or memory.get("ok", False))
            and (plan is None or plan.get("ok", False))
        ),
        "status": res.status if probe is not None or not res.ok
        else "crashed",  # rc 0 but no probe line = a broken child
        "probe": probe,
        # the child registry's snapshot (jax_compiles_total + duration
        # histogram): the probe's compile, centrally counted (ISSUE 7)
        "metrics": metrics,
        "beats": res.beats,
        "last_beat": res.last_beat_label,
        "elapsed_s": round(res.duration_s, 3),
        "reason": res.reason,
        # a killed probe's span story (open spans name the wedged step,
        # complementing last_beat)
        "flight": res.flight,
    }


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi-knn doctor",
        description="preflight device health probe (tiny jit + "
        "device_sync in a heartbeat-supervised subprocess); exit 0 iff "
        "healthy, JSON verdict on stdout",
    )
    p.add_argument("--platform", choices=["auto", "cpu", "tpu"],
                   default="auto")
    p.add_argument("--timeout", type=float,
                   default=DEFAULT_BEAT_TIMEOUT_S,
                   help="beat-starvation bound in seconds (progress "
                   "gaps longer than this kill the probe)")
    p.add_argument("--wall-timeout", type=float,
                   default=DEFAULT_WALL_TIMEOUT_S,
                   help="outer wall-clock bound in seconds")
    p.add_argument("--report", default=None,
                   help="also write the JSON verdict to this path")
    p.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="probe this persistent AOT executable cache "
                   "(serve/aotcache.py; TKNN_AOT_CACHE is honored "
                   "without the flag): the verdict gains an aot_cache "
                   "block with dir, entry count, bytes, and a store/"
                   "load round trip of a tiny probe executable")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.child:
        return _probe_child(args.platform, cache_dir=args.cache_dir)
    verdict = run_probe(
        platform=args.platform,
        beat_timeout_s=args.timeout,
        wall_timeout_s=args.wall_timeout,
        env=dict(os.environ),
        cache_dir=args.cache_dir,
    )
    print(json.dumps(verdict), flush=True)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(verdict, f, indent=1)
            f.write("\n")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
