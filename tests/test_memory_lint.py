"""Peak-HBM certification (ISSUE 15): the liveness analyzer, the R7
rule, the per-cell memory ledger, and the regression gate.

Four layers:

- ANALYZER unit tests on hand-written HLO: def-use interval peaks,
  forwarding ops allocate nothing, while bodies are loop-resident,
  conditional branches max (not sum), aliased donated outputs count
  once, the tuple pointer table matches PJRT's accounting;
- INJECTED counterexamples through the production rule path
  (``engine.run_rules`` — the test_hlo_lint convention): an un-donated
  scratch that doubles residency, a corpus-sized temp that hides under
  R2's largest-input per-buffer floor (the R2-audit latent hole, pinned
  as caught-by-R7), and a PJRT disagreement;
- the LEDGER: round trip, tolerance-gate pass/fail in both directions
  (growth = regression, shrinkage = stale), new-cell-extends vs
  vanished-cell-is-a-finding semantics, and drift through the
  production ``mpi-knn lint --memory --ledger-check`` CLI;
- the SERVING surface: the ``serve_peak_hbm_bytes`` gauge stamped at
  build time, the session snapshot, and the doctor's memory block.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_knn_tpu.analysis import engine, lowering, memory
from mpi_knn_tpu.analysis import rules as rules_mod
from mpi_knn_tpu.config import KNNConfig


def _ctx(backend="serial", metric="l2", dtype="float32", serve=False,
         **meta):
    meta.setdefault("q_tile", 8)
    meta.setdefault("c_tile", 16)
    meta.setdefault("acc_bytes", 4)
    return engine.LintContext(
        target=lowering.LintTarget(backend, metric, dtype, serve=serve),
        cfg=KNNConfig(k=4, metric=metric, query_tile=8, corpus_tile=16),
        meta=meta,
    )


def _rules(*names):
    return [r for r in rules_mod.RULES if r.name in names]


# ---------------------------------------------------------------------------
# the analyzer on hand-written modules (full control over the shapes —
# no XLA whims between the test and the property)

_LINEAR = """\
HloModule m, entry_computation_layout={(f32[64,32]{1,0})->f32[64,32]{1,0}}

ENTRY %main.1 (a.1: f32[64,32]) -> f32[64,32] {
  %a.1 = f32[64,32]{1,0} parameter(0)
  %b.1 = f32[64,32]{1,0} add(%a.1, %a.1)
  %c.1 = f32[64,32]{1,0} multiply(%b.1, %b.1)
  ROOT %d.1 = f32[64,32]{1,0} negate(%c.1)
}
"""


def test_analyzer_linear_chain_intervals():
    """b dies when c is defined, c when d is: at most two of the three
    8 KiB temporaries are ever live, and the root buffer is the output
    (not a temp)."""
    a = memory.analyze_module(_LINEAR)
    buf = 64 * 32 * 4
    assert a.args_bytes == buf
    assert a.output_bytes == buf
    assert a.aliased_bytes == 0
    # live at the peak: b + c (d IS the output and is excluded from the
    # temp sweep's largest tracking but still occupies output bytes)
    assert a.temp_peak_bytes == 2 * buf
    assert a.peak_bytes == buf + buf + 2 * buf


def test_analyzer_forwarding_is_free():
    """tuple / gte / bitcast shuffle pointers — zero new bytes."""
    mod = """\
HloModule m, entry_computation_layout={(f32[64,32]{1,0})->f32[64,32]{1,0}}

ENTRY %main.1 (a.1: f32[64,32]) -> f32[64,32] {
  %a.1 = f32[64,32]{1,0} parameter(0)
  %t.1 = (f32[64,32]{1,0}, f32[64,32]{1,0}) tuple(%a.1, %a.1)
  %g.1 = f32[64,32]{1,0} get-tuple-element(%t.1), index=0
  ROOT %b.1 = f32[64,32]{1,0} bitcast(%g.1)
}
"""
    a = memory.analyze_module(mod)
    assert a.temp_peak_bytes == 0
    # the output is the forwarded parameter — no new output allocation
    # is modeled, but output_bytes still reports the declared result
    assert a.output_bytes == 64 * 32 * 4


def test_analyzer_aliased_output_counts_once():
    """The same store-update program, donated vs not: the aliased form's
    peak is one store smaller — the donated scratch counts once."""
    body = """\

ENTRY %main.1 (u.1: f32[32,32], s.1: f32[1024,32]) -> f32[1024,32] {
  %u.1 = f32[32,32]{1,0} parameter(0)
  %s.1 = f32[1024,32]{1,0} parameter(1)
  ROOT %n.1 = f32[1024,32]{1,0} negate(%s.1)
}
"""
    layout = ("entry_computation_layout={(f32[32,32]{1,0}, "
              "f32[1024,32]{1,0})->f32[1024,32]{1,0}}")
    donated = memory.analyze_module(
        "HloModule m, input_output_alias={ {}: (1, {}, may-alias) }, "
        + layout + body
    )
    undonated = memory.analyze_module("HloModule m, " + layout + body)
    store = 1024 * 32 * 4
    assert donated.aliased_bytes == store
    assert undonated.aliased_bytes == 0
    assert undonated.peak_bytes - donated.peak_bytes == store


def test_analyzer_while_body_is_loop_resident():
    """The loop body's internal scratch rides on top of the caller's
    live set while the while executes."""
    mod = """\
HloModule m, entry_computation_layout={(f32[64,32]{1,0})->f32[64,32]{1,0}}

%body.1 (p.1: f32[64,32]) -> f32[64,32] {
  %p.1 = f32[64,32]{1,0} parameter(0)
  %big.1 = f32[512,32]{1,0} broadcast(%p.1), dimensions={0,1}
  %sl.1 = f32[64,32]{1,0} slice(%big.1), slice={[0:64], [0:32]}
  ROOT %r.1 = f32[64,32]{1,0} add(%p.1, %sl.1)
}

%cond.1 (q.1: f32[64,32]) -> pred[] {
  %q.1 = f32[64,32]{1,0} parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

ENTRY %main.1 (a.1: f32[64,32]) -> f32[64,32] {
  %a.1 = f32[64,32]{1,0} parameter(0)
  %c.1 = f32[64,32]{1,0} copy(%a.1)
  ROOT %w.1 = f32[64,32]{1,0} while(%c.1), condition=%cond.1, body=%body.1
}
"""
    a = memory.analyze_module(mod)
    # at the while: the state (the copy, 8K) + the body's broadcast
    # (64K) + the body's add result (8K) are live together
    assert a.temp_peak_bytes >= 64 * 32 * 4 + 512 * 32 * 4
    assert a.largest_temp_op == "broadcast"


def test_analyzer_conditional_branches_max_not_sum():
    mod_tmpl = """\
HloModule m, entry_computation_layout={(pred[], f32[8,8]{1,0})->f32[8,8]{1,0}}

%true.1 (p.1: f32[8,8]) -> f32[8,8] {
  %p.1 = f32[8,8]{1,0} parameter(0)
  %b.1 = f32[BIG,8]{1,0} broadcast(%p.1), dimensions={0,1}
  %s.1 = f32[8,8]{1,0} slice(%b.1), slice={[0:8], [0:8]}
  ROOT %r.1 = f32[8,8]{1,0} add(%p.1, %s.1)
}

%false.1 (q.1: f32[8,8]) -> f32[8,8] {
  %q.1 = f32[8,8]{1,0} parameter(0)
  %b.2 = f32[BIG,8]{1,0} broadcast(%q.1), dimensions={0,1}
  %s.2 = f32[8,8]{1,0} slice(%b.2), slice={[0:8], [0:8]}
  ROOT %r.2 = f32[8,8]{1,0} add(%q.1, %s.2)
}

ENTRY %main.1 (p.0: pred[], a.1: f32[8,8]) -> f32[8,8] {
  %p.0 = pred[] parameter(0)
  %a.1 = f32[8,8]{1,0} parameter(1)
  ROOT %c.1 = f32[8,8]{1,0} conditional(%p.0, %a.1, %a.1), true_computation=%true.1, false_computation=%false.1
}
"""
    a = memory.analyze_module(mod_tmpl.replace("BIG", "256"))
    # one branch's broadcast (256·8·4 = 8192), never both at once
    assert a.temp_peak_bytes < 2 * 256 * 8 * 4
    assert a.temp_peak_bytes >= 256 * 8 * 4


def test_analyzer_matches_pjrt_on_a_real_program():
    """The honesty anchor as a unit test: structural components match
    PJRT exactly, the total peak sits inside the declared band."""
    lowered = jax.jit(lambda a: (a @ a.T).sum(axis=0)).lower(
        jnp.zeros((64, 32), jnp.float32)
    )
    compiled = lowered.compile()
    pjrt = memory.pjrt_memory_stats(compiled)
    assert pjrt is not None
    a = memory.analyze_module(compiled.as_text())
    assert memory.crosscheck_pjrt(a, pjrt) == []
    assert a.args_bytes == pjrt["argument_bytes"]
    assert a.output_bytes == pjrt["output_bytes"]


def test_crosscheck_flags_structural_and_band_disagreement():
    a = memory.analyze_module(_LINEAR)
    good = {
        "argument_bytes": a.args_bytes,
        "output_bytes": a.output_bytes,
        "alias_bytes": a.aliased_bytes,
        "temp_bytes": a.temp_peak_bytes,
        "peak_bytes": a.peak_bytes,
    }
    assert memory.crosscheck_pjrt(a, good) == []
    bad_struct = dict(good, argument_bytes=a.args_bytes + 4)
    assert any("argument" in w for w in memory.crosscheck_pjrt(a, bad_struct))
    # a peak disagreement far past the band (analyzer would be missing
    # a corpus-sized buffer): must be loud
    bad_peak = dict(good, peak_bytes=a.peak_bytes * 10 + 10 ** 6)
    assert any("beyond tolerance" in w
               for w in memory.crosscheck_pjrt(a, bad_peak))


# ---------------------------------------------------------------------------
# injected counterexamples through the PRODUCTION rule path


def test_counterexample_undonated_scratch_doubles_residency():
    """The same in-place store update lowered WITHOUT donation: the
    output no longer aliases the donated store, residency doubles, and
    R7's budget (which grants donated cells NO unaliased-output
    allowance) must fire — while the donated production shape is clean
    under the identical context."""
    store = jnp.zeros((8192, 32), jnp.float32)
    rows = jnp.zeros((32, 32), jnp.float32)

    def update(rows, store):
        return store.at[:32].set(rows)

    meta = dict(
        q_tile=32, c_tile=32, acc_bytes=4,
        donated_params=(1,), budget_elems=32 * 32,
    )
    ctx = _ctx(serve=True, **meta)
    undonated = lowering.hlo_texts(jax.jit(update).lower(rows, store))
    findings, ran = engine.run_rules(
        undonated, ctx, _rules("R7-peak-memory")
    )
    assert ran == ["R7-peak-memory"]
    assert any("peak live bytes" in f.message for f in findings), [
        f.message for f in findings
    ]
    # the finding names its numbers: peak ≈ 2× the donated peak
    f = next(f for f in findings if "peak live bytes" in f.message)
    assert f.details["peak_bytes"] > 2 * 8192 * 32 * 4

    donated = lowering.hlo_texts(
        jax.jit(update, donate_argnums=(1,)).lower(rows, store)
    )
    ok_findings, _ = engine.run_rules(
        donated, _ctx(serve=True, **meta), _rules("R7-peak-memory")
    )
    assert not ok_findings, [f.message for f in ok_findings]


def test_counterexample_corpus_temp_under_r2_radar():
    """A corpus-sized intermediate whose largest single buffer equals
    the largest input: R2's per-buffer floor passes it (the latent hole
    the ISSUE 15 audit names), R7's liveness peak — whose temp budget
    deliberately has NO input floor — fires and names the culprit."""

    def sneaky(q, c):
        c2 = jnp.cumsum(c, axis=0)  # corpus-sized live intermediates
        return q[:8] @ c2[:16].T  # tiny output

    lowered = jax.jit(sneaky).lower(
        jnp.zeros((64, 32), jnp.float32),
        jnp.zeros((4096, 32), jnp.float32),
    )
    texts = lowering.hlo_texts(lowered)
    ctx = _ctx()
    r2_findings, _ = engine.run_rules(texts, ctx, _rules("R2-memory"))
    assert not r2_findings, [f.message for f in r2_findings]
    r7_findings, _ = engine.run_rules(texts, _ctx(),
                                      _rules("R7-peak-memory"))
    over = [f for f in r7_findings if "peak live bytes" in f.message]
    assert over, "corpus-sized temp passed the liveness budget"
    # the report names a culprit an operator can grep for
    assert over[0].details["largest_temp"]["bytes"] >= 4096 * 32 * 4 / 2


def test_counterexample_pjrt_disagreement_is_a_finding():
    """Feed R7 a doctored PJRT report (as if the runtime saw half the
    memory the analyzer sees): the cross-check must fire through the
    production rule path."""
    texts, cfg, meta = lowering.lower_target(
        lowering.LintTarget("serial", "l2", "float32")
    )
    bad_meta = dict(meta)
    real = bad_meta.get("pjrt_memory")
    assert real is not None, "lowering no longer captures PJRT stats"
    bad_meta["pjrt_memory"] = {
        **real, "peak_bytes": max(1, real["peak_bytes"] // 10),
    }
    ctx = engine.LintContext(
        target=lowering.LintTarget("serial", "l2", "float32"),
        cfg=cfg, meta=bad_meta,
    )
    findings, _ = engine.run_rules(texts, ctx, _rules("R7-peak-memory"))
    assert any("beyond tolerance" in f.message for f in findings)
    # and with the REAL numbers the same cell is clean
    ctx2 = engine.LintContext(
        target=lowering.LintTarget("serial", "l2", "float32"),
        cfg=cfg, meta=dict(meta),
    )
    ok, _ = engine.run_rules(texts, ctx2, _rules("R7-peak-memory"))
    assert not ok, [f.message for f in ok]


# ---------------------------------------------------------------------------
# the R2-floor audit (ISSUE 15 satellite): every divergence between
# R2's input-floored per-buffer budget and R7's floor-free temp budget
# is either absorbed by the derived allowance or carried by a NAMED
# registered allowance — no cell silently leans on the input floor


def _default_meta(target):
    try:
        _, _, meta = lowering.lower_target(target)
    except lowering.UnsupportedTarget:
        return None
    return meta


def test_r2_floor_audit_allowances_are_named_and_load_bearing():
    allowed = []
    for t in lowering.default_targets():
        meta = _default_meta(t)
        if meta is None:
            continue
        if meta.get("peak_extra_elems"):
            allowed.append(t)
    # exactly the one audited divergence: the bf16 store's f32 upcast
    # (dense serial cells) — a new entry here means a new divergence that
    # needs a rationale in analysis/lowering.py AND this pin extended
    families = {
        (t.backend, t.dtype, t.policy) for t in allowed
    }
    assert families == {("serial", "bfloat16", "exact")}, families
    # and the allowance is load-bearing: dropping it fires R7 (the
    # audit found a real divergence, not a cargo-cult slack bump)
    for t in (lowering.LintTarget("serial", "l2", "bfloat16"),):
        texts, cfg, meta = lowering.lower_target(t)
        stripped = dict(meta)
        stripped.pop("peak_extra_elems")
        ctx = engine.LintContext(target=t, cfg=cfg, meta=stripped)
        findings, _ = engine.run_rules(texts, ctx,
                                       _rules("R7-peak-memory"))
        assert any("peak live bytes" in f.message for f in findings), (
            t.label, "allowance is not load-bearing — remove it"
        )


# ---------------------------------------------------------------------------
# the ledger


def _cell(peak, budget=None):
    return {
        "args_bytes": peak // 2, "output_bytes": 64, "aliased_bytes": 0,
        "temp_peak_bytes": peak // 2, "peak_bytes": peak,
        "largest_temp": {"bytes": peak // 4, "op": "dot",
                         "instruction": "main::d.1"},
        "peak_at": "d.1",
        "categories": {"scratch": 0, "temp": peak // 2, "exchange": 0},
        "budget_bytes": budget if budget is not None else peak * 2,
        "pjrt": None,
    }


def test_ledger_round_trip_and_merge(tmp_path):
    path = tmp_path / "memory_ledger.json"
    assert memory.load_ledger(path) is None
    doc = memory.save_ledger(path, {"a/l2/f32": _cell(1000)})
    loaded = memory.load_ledger(path)
    assert loaded["cells"] == doc["cells"]
    assert loaded["schema_version"] == memory.LEDGER_SCHEMA_VERSION
    assert loaded["tolerance"] == {
        "rel": memory.LEDGER_TOL_REL, "abs_bytes": memory.LEDGER_TOL_ABS,
    }
    # a filtered refresh merges: the un-re-lowered cell survives
    memory.save_ledger(path, {"b/l2/f32": _cell(2000)}, merge_into=loaded)
    merged = memory.load_ledger(path)
    assert set(merged["cells"]) == {"a/l2/f32", "b/l2/f32"}
    # unknown schema is refused loudly, not silently re-interpreted
    path.write_text(json.dumps({"schema_version": 99, "cells": {}}))
    with pytest.raises(ValueError):
        memory.load_ledger(path)


def test_ledger_tolerance_gate_both_directions(tmp_path):
    committed = memory.save_ledger(
        tmp_path / "l.json", {"cell": _cell(100_000)}
    )
    # inside tolerance: green both ways
    assert memory.ledger_drift(
        committed, {"cell": _cell(100_000 + 2000)}, full_matrix=True
    ) == []
    assert memory.ledger_drift(
        committed, {"cell": _cell(100_000 - 2000)}, full_matrix=True
    ) == []
    # growth beyond tolerance: a regression, naming the culprit
    grew = memory.ledger_drift(
        committed, {"cell": _cell(200_000)}, full_matrix=True
    )
    assert grew and "grew" in grew[0] and "dot" in grew[0]
    # shrinkage beyond tolerance: a stale ledger
    shrank = memory.ledger_drift(
        committed, {"cell": _cell(50_000)}, full_matrix=True
    )
    assert shrank and "shrank" in shrank[0]


def test_ledger_new_cell_extends_vanished_cell_fires(tmp_path):
    committed = memory.save_ledger(
        tmp_path / "l.json", {"old": _cell(1000)}
    )
    # a NEW cell extends the ledger silently
    assert memory.ledger_drift(
        committed, {"old": _cell(1000), "new": _cell(5000)},
        full_matrix=True,
    ) == []
    # a VANISHED cell is a finding on full-matrix runs only (a filtered
    # sweep legitimately re-lowers a subset)
    gone_full = memory.ledger_drift(committed, {}, full_matrix=True)
    assert gone_full and "vanished" in gone_full[0]
    assert memory.ledger_drift(committed, {}, full_matrix=False) == []
    # an ENVIRONMENT-SKIPPED cell (a too-small mesh) is a coverage gap,
    # not a vanished certification — `--devices 1` must not fail every
    # committed ring cell
    assert memory.ledger_drift(
        committed, {}, full_matrix=True, skipped_labels={"old"}
    ) == []


def test_ledger_full_regeneration_purges_vanished_cells(tmp_path):
    """The drift error's prescribed remedy must actually work: after a
    cell is removed from the matrix on purpose, a full-matrix
    `--memory` regeneration drops its committed entry (merge_base_for
    returns no merge base) instead of re-importing it forever — while
    an environment-skipped cell keeps its entry, and a FILTERED sweep
    still preserves the whole committed ledger."""
    committed = memory.save_ledger(
        tmp_path / "l.json",
        {"removed": _cell(1000), "skipped": _cell(2000),
         "kept": _cell(3000)},
    )
    # full regeneration, nothing skipped: no merge base → vanished
    # cells purge
    assert memory.merge_base_for(committed, full_matrix=True) is None
    # full regeneration with an env-skip: only the skipped cell's
    # committed entry survives the merge
    base = memory.merge_base_for(
        committed, full_matrix=True, skipped_labels={"skipped"}
    )
    assert set(base["cells"]) == {"skipped"}
    doc = memory.save_ledger(
        tmp_path / "l.json", {"kept": _cell(3000)}, merge_into=base
    )
    assert set(doc["cells"]) == {"kept", "skipped"}
    # filtered sweep: the committed ledger is preserved wholesale
    assert memory.merge_base_for(
        committed, full_matrix=False
    ) is committed
    assert memory.merge_base_for(None, full_matrix=True) is None


def test_ledger_drift_through_production_cli(tmp_path):
    """The ledger-drift counterexample through the REAL `mpi-knn lint
    --memory --ledger-check` path: a committed ledger whose serial cell
    claims half the real peak must fail the gate (exit 1), and the
    freshly-written ledger must pass it (exit 0)."""
    from mpi_knn_tpu.analysis import cli as lint_cli

    args = ["--backend", "serial", "--metric", "l2", "--dtype", "float32",
            "--policy", "exact", "--schedule", "uni",
            "--out", str(tmp_path), "-q"]
    # generate the honest ledger for the one-cell sweep
    rc = lint_cli.main(args + ["--memory"])
    assert rc == 0
    ledger_path = tmp_path / "memory_ledger.json"
    honest = json.loads(ledger_path.read_text())
    label = "serial/l2/float32"
    assert label in honest["cells"]
    # the honest ledger passes the check
    assert lint_cli.main(args + ["--memory", "--ledger-check"]) == 0
    # tamper: halve the committed peak — the real program now "grew"
    honest["cells"][label]["peak_bytes"] //= 2
    ledger_path.write_text(json.dumps(honest))
    assert lint_cli.main(args + ["--memory", "--ledger-check"]) == 1
    # usage errors stay loud: --ledger-check without --memory, and a
    # --rule filter that would sweep WITHOUT R7
    assert lint_cli.main(args + ["--ledger-check"]) == 2
    assert lint_cli.main(
        args + ["--memory", "--rule", "R2-memory"]
    ) == 2
    # missing committed ledger is a usage error, not a silent pass
    assert lint_cli.main(
        args + ["--memory", "--ledger-check",
                "--ledger", str(tmp_path / "nope.json")]
    ) == 2


def test_committed_ledger_matches_default_matrix():
    """The committed artifact covers the serial seed cell and carries
    the PJRT evidence + a named culprit for every cell (the full-matrix
    regeneration runs in check.sh; tier-1 pins the shape so a hand-
    edited ledger cannot pass)."""
    doc = memory.load_ledger(memory.DEFAULT_LEDGER)
    assert doc is not None, "artifacts/lint/memory_ledger.json missing"
    assert len(doc["cells"]) >= 65
    for label, cell in doc["cells"].items():
        assert cell["peak_bytes"] <= cell["budget_bytes"], label
        assert cell["pjrt"] is not None, label
        assert cell["largest_temp"]["op"], label


# ---------------------------------------------------------------------------
# the serving surface: gauge + snapshot + doctor block


def test_serve_stamps_peak_hbm_gauge_and_report():
    from mpi_knn_tpu.obs.metrics import get_registry
    from mpi_knn_tpu.serve import ServeSession, build_index
    from mpi_knn_tpu.serve.engine import index_peak_hbm_bytes

    rng = np.random.default_rng(0)
    X = rng.standard_normal((256, 16)).astype(np.float32)
    cfg = KNNConfig(k=4, backend="serial", query_tile=32, corpus_tile=64,
                    query_bucket=32)
    index = build_index(X, cfg)
    session = ServeSession(index)
    session.warm([32])
    peak = index_peak_hbm_bytes(index)
    assert peak > X.nbytes  # the resident corpus is inside the peak
    gauge = get_registry().gauge("serve_peak_hbm_bytes")
    assert gauge.snapshot()["value"] >= peak
    # the session posture snapshot carries it to /healthz
    assert session.stats_snapshot()["peak_hbm_bytes"] == peak
    # and it agrees with the executable's own PJRT figure
    exec_ = next(iter(index._cache.values()))
    assert exec_.peak_hbm_bytes == peak


def test_doctor_memory_probe_agrees():
    from mpi_knn_tpu.resilience.doctor import _memory_probe

    compiled = jax.jit(lambda a: a @ a.T).lower(
        jnp.zeros((8, 8), jnp.float32)
    ).compile()
    block = _memory_probe(compiled)
    assert block["ok"] is True, block
    assert block["predicted_peak_bytes"] > 0
    assert block["disagreements"] == []
    assert block["measured"]["peak_bytes"] > 0
