"""``mpi-knn lint`` — run the static rule matrix and write the report.

Exit status is the gate: 0 = every checked configuration passed every
applicable rule, 1 = at least one finding (the JSON report carries the
evidence), 2 = usage error. Runs entirely on CPU (virtual 8-device mesh),
so it works on a laptop, in CI, and while the chip is dead.
"""

from __future__ import annotations

import argparse
import os
import sys

from mpi_knn_tpu.config import METRICS


def build_parser() -> argparse.ArgumentParser:
    from mpi_knn_tpu.analysis.lowering import (
        LINT_BACKENDS,
        LINT_DTYPES,
        LINT_QUANTS,
    )

    p = argparse.ArgumentParser(
        prog="mpi-knn lint",
        description="statically lint every backend's compiled program "
        "(HLO rule engine; CPU-only, no TPU needed)",
    )
    p.add_argument("--backend", action="append", choices=LINT_BACKENDS,
                   help="restrict to backend(s); repeatable")
    p.add_argument("--metric", action="append", choices=METRICS,
                   help="restrict to metric(s); repeatable")
    p.add_argument("--dtype", action="append", choices=LINT_DTYPES,
                   help="restrict to dtype(s); repeatable")
    p.add_argument("--policy", action="append", choices=["exact", "mixed"],
                   help="restrict to precision policy(ies): exact "
                   "(one-pass HIGHEST distances) or mixed (the compress-"
                   "and-rerank pipeline, whose dot-precision contract R3 "
                   "certifies); repeatable")
    p.add_argument("--schedule", action="append", choices=["uni", "bidir"],
                   help="restrict to ring schedule(s): uni (one-directional "
                   "rotation) or bidir (full-duplex counter-rotation, whose "
                   "2-permutes-per-direction accounting R4 certifies); "
                   "repeatable")
    p.add_argument("--serve", action="store_true",
                   help="restrict to the serving-engine cells (the "
                   "per-batch programs the executable cache compiles, "
                   "whose donation/aliasing and no-corpus-copy contract "
                   "R5 certifies)")
    p.add_argument("--frontend", action="store_true",
                   help="restrict to the serving-front-end cells (the "
                   "coalesced-dispatch program: a multi-tenant batch "
                   "formed by the production coalescer, which must "
                   "compile exactly an existing serve-grid bucket — no "
                   "new programs — with R1–R5 re-certified on it)")
    p.add_argument("--mutate", action="append",
                   choices=["upsert", "delete", "compact"],
                   help="restrict to the live-mutation cells (ISSUE 14: "
                   "the donated in-place upsert/delete/compact programs "
                   "from serve.mutate.lower_mutation — R5's every-output-"
                   "aliased + no-corpus-copy contract and R2-strict's "
                   "touched-working-set budget); repeatable")
    p.add_argument("--quant", action="append", choices=list(LINT_QUANTS),
                   help="restrict to quantized cells: xfer-int8 (the "
                   "block-scaled int8 ring transfer — R3's quant/dequant "
                   "contract, R4's wire-priced 3-permutes-per-direction "
                   "accounting) or int8/int4 (the clustered store's "
                   "at-rest levels — R2's wire-priced gather bound); "
                   "repeatable")
    p.add_argument("--host", action="store_true",
                   help="run the HOST concurrency lint instead (lock "
                   "discipline / lock ordering / thread confinement / "
                   "atomic publication over the threaded host modules "
                   "— analysis/host; jax-free, writes "
                   "host_report.json). All other flags are the host "
                   "linter's own (--rule/--out/--list-rules/-q)")
    p.add_argument("--memory", action="store_true",
                   help="maintain the per-cell peak-HBM ledger (ISSUE "
                   "15): after the sweep, write every checked cell's "
                   "R7 liveness numbers (peak live bytes, attribution, "
                   "largest-temp culprit, PJRT cross-check) into the "
                   "committed ledger — new cells extend it, re-lowered "
                   "cells refresh it. With --ledger-check, COMPARE "
                   "instead of write")
    p.add_argument("--cost", action="store_true",
                   help="maintain the per-cell cost ledger (ISSUE 16): "
                   "after the sweep, write every checked cell's R8 "
                   "numbers (MXU FLOPs cross-checked against the "
                   "closed form, modeled HBM traffic, wire-priced ICI "
                   "bytes, roofline q/s under the default profile) "
                   "into the committed ledger — new cells extend it, "
                   "re-lowered cells refresh it. With --ledger-check, "
                   "COMPARE instead of write")
    p.add_argument("--ledger-check", action="store_true",
                   help="with --memory and/or --cost: fail (exit 1) "
                   "when any cell's ledgered metric drifts beyond the "
                   "committed ledger's tolerance in either direction "
                   "(growth = regression, shrinkage = stale ledger), "
                   "or when a committed cell vanished from a "
                   "full-matrix sweep; never writes")
    p.add_argument("--ledger", default=None, metavar="PATH",
                   help="memory ledger path (default: <--out>/"
                   "memory_ledger.json)")
    p.add_argument("--cost-ledger", default=None, metavar="PATH",
                   help="cost ledger path (default: <--out>/"
                   "cost_ledger.json)")
    p.add_argument("--rule", action="append", metavar="NAME",
                   help="run only the named rule(s), e.g. R2-memory; "
                   "repeatable")
    p.add_argument("--out", default="artifacts/lint", metavar="DIR",
                   help="report directory (default: artifacts/lint)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule registry and exit")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual CPU device count for the ring mesh "
                   "(default 8)")
    p.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="jax persistent compilation cache for the "
                   "matrix's compile step: lint cells that share a "
                   "program (and repeated lint runs — the check.sh "
                   "gates run overlapping sweeps) reuse compiled "
                   "artifacts instead of re-invoking XLA. This is "
                   "jax's own cache, NOT the serve AOT cache: lint "
                   "needs before/after-opt HLO text, which only a "
                   "real compile step (cached at the XLA layer) "
                   "provides")
    p.add_argument("-q", "--quiet", action="store_true")
    return p


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if "--host" in argv:
        # the host concurrency lint (lock discipline / confinement over
        # the threaded host modules) is a separate, jax-free analyzer:
        # route before the HLO parser so neither namespace pays for the
        # other (and --host never forces a platform or imports jax)
        from mpi_knn_tpu.analysis.host.cli import main as host_main

        return host_main([a for a in argv if a != "--host"])
    args = build_parser().parse_args(argv)

    if args.list_rules:
        from mpi_knn_tpu.analysis.rules import RULES

        for r in RULES:
            print(f"{r.name}: {r.description}")
        return 0

    # platform first: lowering the ring matrix needs the virtual mesh, and
    # the config knob must win before any device access (utils.platform)
    from mpi_knn_tpu.utils.platform import force_platform

    force_platform("cpu", n_devices=args.devices)

    import jax

    # the float64 column is the debug-precision mode; without x64 those
    # lowerings would silently be float32 programs wearing an f64 label
    jax.config.update("jax_enable_x64", True)

    if args.cache_dir:
        # compile-level reuse across cells and runs: thresholds zeroed so
        # even the tiny lint programs cache (the defaults skip sub-second
        # compiles, which is every CPU lint cell). A cache directory
        # placed from outside (JAX_COMPILATION_CACHE_DIR, which jax reads
        # itself) is never overridden.
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", args.cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from mpi_knn_tpu.analysis.engine import run_matrix
    from mpi_knn_tpu.analysis.lowering import default_targets

    targets = [
        t
        for t in default_targets()
        if (not args.backend or t.backend in args.backend)
        and (not args.metric or t.metric in args.metric)
        and (not args.dtype or t.dtype in args.dtype)
        and (not args.policy or t.policy in args.policy)
        and (not args.schedule or t.schedule in args.schedule)
        and (not args.quant or t.quant in args.quant)
        and (not args.mutate or t.mutate in args.mutate)
        and (t.serve or not args.serve)
        and (t.frontend or not args.frontend)
    ]
    if not targets:
        print("error: no targets match the given filters", file=sys.stderr)
        return 2

    def progress(res):
        if args.quiet:
            return
        if res.skipped is not None:
            print(f"  SKIP {res.target.label}: {res.skipped}")
        else:
            state = "ok" if res.ok else f"{len(res.findings)} finding(s)"
            print(f"  {res.target.label}: {state} "
                  f"[{', '.join(res.rules_run)}]")

    if args.ledger_check and not (args.memory or args.cost):
        print("error: --ledger-check requires --memory or --cost",
              file=sys.stderr)
        return 2
    # each ledger is its rule's output; a sweep that filters the rule out
    # would silently write/check an EMPTY ledger — refuse loudly
    for flag, flagname, rule in (
        (args.memory, "--memory", "R7-peak-memory"),
        (args.cost, "--cost", "R8-cost"),
    ):
        if flag and args.rule and rule not in args.rule:
            print(f"error: {flagname} needs rule {rule} in the sweep "
                  "(drop --rule or include it)", file=sys.stderr)
            return 2

    try:
        report = run_matrix(targets, rule_names=args.rule, progress=progress)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2
    path = report.save(args.out)

    ledger_rc = 0
    if args.memory or args.cost:
        import pathlib

        from mpi_knn_tpu.analysis import ledger as ledgermod

        # a filtered sweep covers a subset: vanished-cell semantics only
        # apply when every default cell was attempted — and a cell whose
        # lowering was environment-skipped THIS run (e.g. ring cells on
        # a one-device mesh) is a coverage gap, never a "vanished"
        # regression or a purge candidate
        full_matrix = len(targets) == len(default_targets())
        skipped_labels = {
            r.target.label for r in report.results
            if r.skipped is not None
        }

        gates = []
        if args.memory:
            from mpi_knn_tpu.analysis import memory as memmod

            gates.append((
                memmod.LEDGER_SPEC,
                pathlib.Path(args.ledger or
                             pathlib.Path(args.out) / "memory_ledger.json"),
                {r.target.label: r.memory for r in report.results
                 if r.skipped is None and r.memory is not None},
            ))
        if args.cost:
            from mpi_knn_tpu.analysis import cost as costmod

            gates.append((
                costmod.LEDGER_SPEC,
                pathlib.Path(args.cost_ledger or
                             pathlib.Path(args.out) / "cost_ledger.json"),
                {r.target.label: r.cost for r in report.results
                 if r.skipped is None and r.cost is not None},
            ))

        for spec, ledger_path, cells in gates:
            try:
                committed = ledgermod.load_ledger(ledger_path, spec)
            except ValueError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
            if args.ledger_check:
                if committed is None:
                    print(f"error: no committed {spec.kind} ledger at "
                          f"{ledger_path} (generate one with "
                          f"`{spec.regen_cmd}`)", file=sys.stderr)
                    return 2
                drift = ledgermod.ledger_drift(
                    committed, cells, spec, full_matrix=full_matrix,
                    skipped_labels=skipped_labels,
                )
                for why in drift:
                    print(f"  LEDGER-DRIFT {why}")
                if not args.quiet:
                    print(f"{spec.kind} ledger check: {len(cells)} "
                          f"cell(s) vs {ledger_path}: "
                          + ("GREEN" if not drift
                             else f"{len(drift)} drift finding(s)"))
                ledger_rc = max(ledger_rc, 0 if not drift else 1)
            else:
                ledgermod.save_ledger(
                    ledger_path, cells, spec,
                    merge_into=ledgermod.merge_base_for(
                        committed, full_matrix=full_matrix,
                        skipped_labels=skipped_labels,
                    ),
                )
                if not args.quiet:
                    print(f"{spec.kind} ledger: {len(cells)} cell(s) "
                          f"written to {ledger_path}")

    if not args.quiet:
        s = report.to_json()["summary"]
        print(
            f"lint: {s['targets_checked']} target(s) checked, "
            f"{s['targets_skipped']} skipped, {s['findings']} finding(s); "
            f"report: {path}"
        )
        for f in report.findings:
            print(f"  VIOLATION [{f.rule}] {f.target} {f.stage}: {f.message}")
    return max(0 if report.ok else 1, ledger_rc)


if __name__ == "__main__":
    sys.exit(main())
