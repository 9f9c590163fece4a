"""The lint engine's tier-1 gate (ISSUE 1 tentpole).

Three layers:

- the FULL backend × metric × dtype rule matrix runs clean on the current
  code (every parametrized cell lowers on the 8-device CPU mesh and passes
  every applicable rule; no cell is skipped);
- each rule catches its injected counterexample through the exact
  production rule path (``engine.run_rules``): R2 a deliberately de-tiled
  lowering that materializes the full distance matrix, R4 an injected
  sharding leak (``all_gather`` inside the ring body), R1 a doctored
  module whose permute depends on the compute, R3 synthetic downcast /
  bf16-dot modules;
- the CLI contract: ``mpi-knn lint`` writes the JSON report and its exit
  status IS the verdict.
"""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from mpi_knn_tpu.analysis import engine, lowering
from mpi_knn_tpu.analysis import rules as rules_mod
from mpi_knn_tpu.config import KNNConfig


def _ctx(backend="serial", metric="l2", dtype="float32", serve=False, **meta):
    meta.setdefault("q_tile", 8)
    meta.setdefault("c_tile", 16)
    meta.setdefault("acc_bytes", 8 if dtype == "float64" else 4)
    return engine.LintContext(
        target=lowering.LintTarget(backend, metric, dtype, serve=serve),
        cfg=KNNConfig(k=4, metric=metric, query_tile=8, corpus_tile=16),
        meta=meta,
    )


def _rules(*names):
    return [r for r in rules_mod.RULES if r.name in names]


# ---------------------------------------------------------------------------
# the full matrix, parametrized per cell


@pytest.mark.parametrize(
    "target", lowering.default_targets(), ids=lambda t: t.label
)
def test_full_matrix_is_clean(target):
    res = engine.lint_target(target)
    assert res.skipped is None, (target.label, res.skipped)
    assert res.ok, "\n".join(
        f"[{f.rule}] {f.stage}: {f.message}" for f in res.findings
    )
    assert set(res.stages) == {"before_opt", "after_opt"}
    ran = set(res.rules_run)
    assert {"R2-memory", "R3-dtype", "R7-peak-memory"} <= ran
    # R7 ran for real: every checked cell banks its ledger numbers with
    # the PJRT cross-check evidence attached (ISSUE 15)
    assert res.memory is not None
    assert res.memory["peak_bytes"] <= res.memory["budget_bytes"]
    assert res.memory["pjrt"] is not None
    if target.mutate and target.backend == "ivf-sharded":
        # GSPMD-partitioned mutation scatter: no candidate exchange to
        # account, so R4 registers out of scope (rules.R4Collectives)
        assert "R4-collective" not in ran
    else:
        assert "R4-collective" in ran
    if target.mutate:
        # the mutation cells' own contract: donated in-place update
        assert "R5-donation" in ran
    if target.backend in ("ring", "ring-overlap"):
        assert "R1-overlap" in ran
    else:
        assert "R1-overlap" not in ran


# ---------------------------------------------------------------------------
# R2: a deliberately de-tiled lowering must be caught


def test_r2_catches_detiled_distance_matrix():
    """Compute the FULL (nq × m) distance matrix in one shot — the exact
    mistake tiling exists to prevent (an HBM-busting materialization at
    SIFT scale) — and assert the memory rule flags it in both stages."""
    from mpi_knn_tpu.ops.distance import pairwise_sq_l2

    def detiled(q, c):
        d = pairwise_sq_l2(q, c)  # (64, 4096) in one buffer
        return jax.lax.top_k(-d, 4)

    lowered = jax.jit(detiled).lower(
        jnp.zeros((64, 32), jnp.float32), jnp.zeros((4096, 32), jnp.float32)
    )
    texts = lowering.hlo_texts(lowered)
    findings, ran = engine.run_rules(texts, _ctx(), _rules("R2-memory"))
    assert ran == ["R2-memory"]
    assert findings, "de-tiled lowering passed the memory bound"
    assert {f.stage for f in findings} == {"before_opt", "after_opt"}
    # the flagged buffer really is matrix-sized, not some small temp
    assert max(f.details["bytes"] for f in findings) >= 64 * 4096 * 4


def test_r2_passes_the_tiled_equivalent():
    """Same computation, production tiling — the serial matrix cell —
    stays under the budget (the rule separates shapes, not programs)."""
    res = engine.lint_target(lowering.LintTarget("serial", "l2", "float32"))
    assert res.ok


# ---------------------------------------------------------------------------
# R4: an injected sharding leak must be caught


def test_r4_catches_injected_sharding_leak():
    """A ring body that all-gathers the corpus instead of rotating it —
    the classic sharding leak: results stay correct, memory and bytes on
    the wire silently stop scaling with the ring."""
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    mesh = make_ring_mesh(None)
    axis = mesh.axis_names[0]

    def leaky(blk):
        return jax.lax.all_gather(blk, axis, axis=0, tiled=True)

    fn = jax.jit(
        jax.shard_map(leaky, mesh=mesh, in_specs=P(axis), out_specs=P())
    )
    texts = lowering.hlo_texts(
        fn.lower(jnp.zeros((128, 32), jnp.float32))
    )
    ctx = _ctx(backend="ring", ring_n=8, expected_permutes=2)
    findings, _ = engine.run_rules(texts, ctx, _rules("R4-collective"))
    strays = [f for f in findings if f.details.get("op") == "all-gather"]
    assert strays, "all-gather leak not flagged"


_BIDIR_TMPL = """\
HloModule b, entry_computation_layout={(f32[4,8]{1,0})->f32[4,8]{1,0}}

ENTRY %main.1 (a.1: f32[4,8]) -> f32[4,8] {
  %a.1 = f32[4,8]{1,0} parameter(0)
  %cp.1 = f32[4,8]{1,0} collective-permute(%a.1), channel_id=1, source_target_pairs=FWD
  %cp.2 = f32[4,8]{1,0} collective-permute(%a.1), channel_id=2, source_target_pairs=FWD
  %cp.3 = f32[4,8]{1,0} collective-permute(%a.1), channel_id=3, source_target_pairs=PAIRS3
  %cp.4 = f32[4,8]{1,0} collective-permute(%a.1), channel_id=4, source_target_pairs=PAIRS4
  ROOT %s.1 = f32[4,8]{1,0} add(%cp.1, %cp.3)
}
"""

_FWD4 = "{{0,1},{1,2},{2,3},{3,0}}"
_BWD4 = "{{0,3},{1,0},{2,1},{3,2}}"
# neither rotation: 0 and 1 swapped pairwise, 2→3→2 — a "ring" nobody runs
_WRONG4 = "{{0,1},{1,0},{2,3},{3,2}}"


def _bidir_module(pairs3, pairs4):
    return (
        _BIDIR_TMPL.replace("FWD", _FWD4)
        .replace("PAIRS3", pairs3)
        .replace("PAIRS4", pairs4)
    )


def _bidir_ctx():
    return _ctx(backend="ring", ring_n=4, expected_permutes=4,
                ring_schedule="bidir")


def test_r4_bidir_accounting_passes_the_correct_shape():
    """2 forward + 2 backward counter-directed permutes — the compiled
    shape of the full-duplex round — is clean."""
    texts = {"before_opt": _bidir_module(_BWD4, _BWD4)}
    findings, _ = engine.run_rules(texts, _bidir_ctx(), _rules("R4-collective"))
    assert not findings, [f.message for f in findings]


def test_r4_bidir_catches_missing_counter_directed_permute():
    """All four permutes forward (the ids pair never counter-rotated — a
    silent fallback to half-duplex) must be a finding."""
    texts = {"before_opt": _bidir_module(_FWD4, _FWD4)}
    findings, _ = engine.run_rules(texts, _bidir_ctx(), _rules("R4-collective"))
    assert findings
    assert any("half-duplex" in f.message for f in findings)


def test_r4_bidir_catches_wrong_direction_permute():
    """A permute whose source_target_pairs is neither ring rotation merges
    blocks in an order the round plan does not account for — a finding."""
    texts = {"before_opt": _bidir_module(_BWD4, _WRONG4)}
    findings, _ = engine.run_rules(texts, _bidir_ctx(), _rules("R4-collective"))
    assert any("neither the forward nor the backward" in f.message
               for f in findings)


def test_r4_bidir_catches_missing_permute_count():
    """Only 2 permutes under a bidir context (one traveler never moves)."""
    mod = "\n".join(
        line for line in _bidir_module(_BWD4, _BWD4).splitlines()
        if "cp.2" not in line and "cp.4" not in line
    )
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _bidir_ctx(), _rules("R4-collective")
    )
    assert any("expected exactly 4" in f.message for f in findings)


def test_r4_bidir_two_ring_checks_combined_count_only():
    """On a 2-ring the forward and backward rotations coincide ({{0,1},
    {1,0}}), so the census cannot split directions — R4 must accept a
    correct 4-permute program there (the per-direction split false-failed
    `lint --devices 2` before this regression test) and still flag a
    missing permute via the combined count."""
    two = "{{0,1},{1,0}}"
    mod = (
        _BIDIR_TMPL.replace("FWD", two)
        .replace("PAIRS3", two)
        .replace("PAIRS4", two)
    )
    ctx = _ctx(backend="ring", ring_n=2, expected_permutes=4,
               ring_schedule="bidir")
    findings, _ = engine.run_rules({"before_opt": mod}, ctx,
                                   _rules("R4-collective"))
    assert not findings, [f.message for f in findings]
    # drop one permute: the combined count still catches it
    short = "\n".join(
        line for line in mod.splitlines() if "cp.4" not in line
    )
    findings, _ = engine.run_rules({"before_opt": short}, ctx,
                                   _rules("R4-collective"))
    assert findings


def test_r4_flags_any_collective_in_single_device_backends():
    """The same leaked program judged as a serial lowering: ANY collective
    is a violation there."""
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    mesh = make_ring_mesh(None)
    axis = mesh.axis_names[0]

    def leaky(blk):
        return jax.lax.all_gather(blk, axis, axis=0, tiled=True)

    fn = jax.jit(
        jax.shard_map(leaky, mesh=mesh, in_specs=P(axis), out_specs=P())
    )
    texts = lowering.hlo_texts(
        fn.lower(jnp.zeros((128, 32), jnp.float32))
    )
    findings, _ = engine.run_rules(texts, _ctx(), _rules("R4-collective"))
    assert any("sharding leak" in f.message for f in findings)


# ---------------------------------------------------------------------------
# R1: the overlap/sequencing rule through the engine path

_SEQUENCED = """\
HloModule m, entry_computation_layout={(f32[4,8]{1,0})->f32[4,4]{1,0}}

%inner.1 (p.1: f32[4,8], p.2: f32[4,8]) -> f32[4,4] {
  %p.1 = f32[4,8]{1,0} parameter(0)
  %p.2 = f32[4,8]{1,0} parameter(1)
  ROOT %d.1 = f32[4,4]{1,0} dot(%p.1, %p.2), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}

ENTRY %main.2 (a.1: f32[4,8]) -> f32[4,4] {
  %a.1 = f32[4,8]{1,0} parameter(0)
  %c.1 = f32[4,4]{1,0} call(%a.1, %a.1), to_apply=%inner.1
  %t.1 = (f32[4,4]{1,0}, f32[4,8]{1,0}) tuple(%c.1, %a.1)
  %b.1 = (f32[4,4]{1,0}, f32[4,8]{1,0}) opt-barrier(%t.1)
  %g.1 = f32[4,8]{1,0} get-tuple-element(%b.1), index=1
  %cp.1 = f32[4,8]{1,0} collective-permute(%g.1), channel_id=1, source_target_pairs={{0,1},{1,0}}
  ROOT %r.1 = f32[4,4]{1,0} get-tuple-element(%b.1), index=0
}
"""


def test_r1_flags_a_sequenced_permute_in_the_overlap_schedule():
    """A permute reading through the barrier (the blocking shape) labeled
    as the OVERLAP schedule must fail R1 in both stages — this is exactly
    the reference's bug class: overlap requested, overlap not achieved."""
    texts = {"before_opt": _SEQUENCED, "after_opt": _SEQUENCED}
    ctx = _ctx(backend="ring-overlap", ring_n=2, expected_permutes=1)
    findings, _ = engine.run_rules(texts, ctx, _rules("R1-overlap"))
    assert len(findings) >= 2  # compute dependence + barrier, both stages
    assert all(f.rule == "R1-overlap" for f in findings)
    # and the SAME module labeled blocking passes (before-opt claim)
    ctx2 = _ctx(backend="ring", ring_n=2, expected_permutes=1)
    findings2, _ = engine.run_rules(
        {"before_opt": _SEQUENCED}, ctx2, _rules("R1-overlap")
    )
    assert not findings2


# ---------------------------------------------------------------------------
# R3: dtype integrity on synthetic counterexamples


def test_r3_flags_silent_f64_downcast():
    mod = """\
HloModule m, entry_computation_layout={(f64[4,8]{1,0})->f32[4,8]{1,0}}

ENTRY %main.1 (a.1: f64[4,8]) -> f32[4,8] {
  %a.1 = f64[4,8]{1,0} parameter(0)
  ROOT %c.1 = f32[4,8]{1,0} convert(%a.1)
}
"""
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _ctx(dtype="float64"), _rules("R3-dtype")
    )
    assert findings and "f64" in findings[0].message
    # the same convert under a float32 config is nobody's business
    findings2, _ = engine.run_rules(
        {"before_opt": mod}, _ctx(dtype="float32"), _rules("R3-dtype")
    )
    assert not findings2


def test_r3_flags_bf16_dot_without_f32_accumulation():
    mod = """\
HloModule m, entry_computation_layout={(bf16[4,8]{1,0})->bf16[4,4]{1,0}}

ENTRY %main.1 (a.1: bf16[4,8]) -> bf16[4,4] {
  %a.1 = bf16[4,8]{1,0} parameter(0)
  ROOT %d.1 = bf16[4,4]{1,0} dot(%a.1, %a.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
"""
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _ctx(dtype="bfloat16"), _rules("R3-dtype")
    )
    assert findings and "bf16 dot" in findings[0].message


# ---------------------------------------------------------------------------
# R5: donation/aliasing of the serving batch program

_SERVE_BODY = """\

ENTRY %main.1 (q.1: f32[8,32], c.1: f32[8,4], ci.1: s32[8,4], t.1: f32[128,32]) -> (f32[8,4], s32[8,4]) {
  %q.1 = f32[8,32]{1,0} parameter(0)
  %c.1 = f32[8,4]{1,0} parameter(1)
  %ci.1 = s32[8,4]{1,0} parameter(2)
  %t.1 = f32[128,32]{1,0} parameter(3)
  ROOT %r.1 = (f32[8,4]{1,0}, s32[8,4]{1,0}) tuple(%c.1, %ci.1)
}
"""

_SERVE_LAYOUT = (
    "entry_computation_layout={(f32[8,32]{1,0}, f32[8,4]{1,0}, "
    "s32[8,4]{1,0}, f32[128,32]{1,0})->(f32[8,4]{1,0}, s32[8,4]{1,0})}"
)

# a correct serve module: both outputs alias the donated scratch pair
_SERVE_OK = (
    "HloModule m, input_output_alias={ {0}: (1, {}, may-alias), "
    "{1}: (2, {}, may-alias) }, " + _SERVE_LAYOUT + _SERVE_BODY
)
# counterexample 1: donation missing entirely (no alias, no buffer_donor)
_SERVE_NO_DONATION = "HloModule m, " + _SERVE_LAYOUT + _SERVE_BODY
# counterexample 2: donation resolved for only ONE of the two outputs —
# the other output allocates fresh memory every batch
_SERVE_HALF_ALIASED = (
    "HloModule m, input_output_alias={ {0}: (1, {}, may-alias) }, "
    + _SERVE_LAYOUT + _SERVE_BODY
)
# before-opt sharded form: buffer_donor declared, aliases not yet resolved
_SERVE_DONOR_ONLY = (
    "HloModule m, buffer_donor={ (1, {}), (2, {}) }, "
    + _SERVE_LAYOUT + _SERVE_BODY
)


def _serve_ctx():
    # resident corpus at these shapes: 128×32 f32 = 16384 bytes
    return _ctx(serve=True, donated_params=(2, 3), resident_bytes=128 * 32 * 4)


def test_r5_passes_the_aliased_serve_program():
    findings, ran = engine.run_rules(
        {"before_opt": _SERVE_OK, "after_opt": _SERVE_OK},
        _serve_ctx(),
        _rules("R5-donation"),
    )
    assert ran == ["R5-donation"]
    assert not findings, [f.message for f in findings]


def test_r5_skips_non_serve_targets():
    findings, ran = engine.run_rules(
        {"before_opt": _SERVE_NO_DONATION}, _ctx(), _rules("R5-donation")
    )
    assert ran == []
    assert not findings


def test_r5_catches_missing_donation():
    """A serve program with no donation declaration at all — every batch
    allocates a fresh carry — must be a finding in both stages."""
    findings, _ = engine.run_rules(
        {"before_opt": _SERVE_NO_DONATION, "after_opt": _SERVE_NO_DONATION},
        _serve_ctx(),
        _rules("R5-donation"),
    )
    assert {f.stage for f in findings} == {"before_opt", "after_opt"}
    assert any("no donation" in f.message for f in findings)


def test_r5_catches_dropped_alias_in_compiled_program():
    """Donation declared but resolved for only one output in the compiled
    program: the other result buffer silently allocates per batch."""
    findings, _ = engine.run_rules(
        {"after_opt": _SERVE_HALF_ALIASED}, _serve_ctx(),
        _rules("R5-donation"),
    )
    assert findings
    assert "output buffer(s) [1]" in findings[0].message


def test_r5_accepts_unresolved_buffer_donor_before_opt():
    """The sharded before-opt form declares buffer_donor without concrete
    aliases — a declaration, not a violation (the after-opt check is
    where resolution is enforced)."""
    findings, _ = engine.run_rules(
        {"before_opt": _SERVE_DONOR_ONLY}, _serve_ctx(),
        _rules("R5-donation"),
    )
    assert not findings, [f.message for f in findings]


def test_r5_catches_full_corpus_copy():
    """A copy of resident-corpus size inside the per-batch program re-pays
    the upload the index exists to amortize — a finding even when the
    donation itself is clean."""
    body_with_copy = _SERVE_BODY.replace(
        "  ROOT %r.1",
        "  %cp.1 = f32[128,32]{1,0} copy(%t.1)\n  ROOT %r.1",
    )
    mod = (
        "HloModule m, input_output_alias={ {0}: (1, {}, may-alias), "
        "{1}: (2, {}, may-alias) }, " + _SERVE_LAYOUT + body_with_copy
    )
    findings, _ = engine.run_rules(
        {"after_opt": mod}, _serve_ctx(), _rules("R5-donation")
    )
    assert findings
    assert any("resident corpus" in f.message for f in findings)
    # a small (block-sized) copy is the rotation's legitimate loop-state
    # traffic and must NOT be flagged
    small = _SERVE_BODY.replace(
        "  ROOT %r.1",
        "  %cp.1 = f32[16,32]{1,0} copy(%q.1)\n  ROOT %r.1",
    )
    mod_small = (
        "HloModule m, input_output_alias={ {0}: (1, {}, may-alias), "
        "{1}: (2, {}, may-alias) }, " + _SERVE_LAYOUT + small
    )
    findings2, _ = engine.run_rules(
        {"after_opt": mod_small}, _serve_ctx(), _rules("R5-donation")
    )
    assert not findings2, [f.message for f in findings2]


def test_r5_header_readers():
    from mpi_knn_tpu.analysis.rules import (
        donor_params,
        entry_output_count,
        output_aliases,
    )
    from mpi_knn_tpu.utils.hlo_graph import parse_hlo

    mod = parse_hlo(_SERVE_OK)
    assert output_aliases(mod) == {0: 1, 1: 2}
    assert entry_output_count(mod) == 2
    assert donor_params(parse_hlo(_SERVE_DONOR_ONLY)) == {1, 2}
    # single (non-tuple) output counts as 1, aliased at index 0
    single = (
        "HloModule m, input_output_alias={ {}: (0, {}, may-alias) }, "
        "entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}\n"
    )
    mod1 = parse_hlo(single)
    assert entry_output_count(mod1) == 1
    assert output_aliases(mod1) == {0: 0}


# ---------------------------------------------------------------------------
# report + CLI contract


def test_report_json_schema(tmp_path):
    report = engine.run_matrix(
        [lowering.LintTarget("serial", "l2", "float32")]
    )
    path = report.save(tmp_path)
    data = json.loads(path.read_text())
    assert data["ok"] is True
    assert data["schema_version"] == engine.SCHEMA_VERSION
    assert data["summary"]["targets_checked"] == 1
    (entry,) = data["targets"]
    assert entry["backend"] == "serial" and entry["ok"] is True
    assert entry["stages"] == ["before_opt", "after_opt"]


def test_cli_lint_exit_codes(tmp_path):
    from mpi_knn_tpu.analysis import cli as lint_cli

    rc = lint_cli.main(
        ["--backend", "serial", "--metric", "l2", "--dtype", "float32",
         "--out", str(tmp_path), "-q"]
    )
    assert rc == 0
    assert (tmp_path / "report.json").exists()

    # exit is non-zero when any rule reports: inject an always-failing
    # rule into the registry for the duration
    class _AlwaysFails(rules_mod.Rule):
        name = "R0-test-canary"
        description = "always fails (test injection)"

        def check(self, ctx, stage, module):
            return [
                rules_mod.Finding(
                    self.name, ctx.target.label, stage, "canary"
                )
            ]

    rules_mod.RULES.append(_AlwaysFails())
    try:
        rc = lint_cli.main(
            ["--backend", "serial", "--metric", "l2", "--dtype", "float32",
             "--rule", "R0-test-canary", "--out", str(tmp_path), "-q"]
        )
    finally:
        rules_mod.RULES.pop()
    assert rc == 1
    data = json.loads((tmp_path / "report.json").read_text())
    assert data["ok"] is False


def test_cli_lint_unknown_rule_is_usage_error(tmp_path):
    from mpi_knn_tpu.analysis import cli as lint_cli

    rc = lint_cli.main(
        ["--backend", "serial", "--rule", "R9-no-such", "--out",
         str(tmp_path), "-q"]
    )
    assert rc == 2


# ---------------------------------------------------------------------------
# quantized-cell counterexamples (ISSUE 9): R3's quant/dequant contract,
# R2's wire-priced gather bound, R4's wire-priced permute payloads. Each
# injected module is the exact bug class the quantization layer makes
# possible — scoring raw codes, dropping the dequant, double-dequanting a
# compress pass, dequantizing before the gather, rotating float rows
# under an int8 label — pushed through the production rule path.


def _quant_ctx(policy="exact", backend="ivf", **meta):
    meta.setdefault("q_tile", 8)
    meta.setdefault("c_tile", 16)
    meta.setdefault("acc_bytes", 4)
    meta.setdefault("quantized", True)
    cfg = KNNConfig(k=4, query_tile=8, corpus_tile=32,
                    precision_policy=policy)
    return engine.LintContext(
        target=lowering.LintTarget(
            backend, "l2", "float32", policy,
            quant="int8" if backend == "ivf" else "xfer-int8",
        ),
        cfg=cfg,
        meta=meta,
    )


def test_r3_quant_flags_dot_consuming_raw_codes():
    """A dot fed raw int8 codes is scoring unscaled integers — a
    different function, not a precision loss."""
    mod = """\
HloModule m, entry_computation_layout={(s8[4,8]{1,0}, s8[16,8]{1,0})->s32[4,16]{1,0}}

ENTRY %main.1 (a.1: s8[4,8], b.1: s8[16,8]) -> s32[4,16] {
  %a.1 = s8[4,8]{1,0} parameter(0)
  %b.1 = s8[16,8]{1,0} parameter(1)
  %cv.1 = f32[4,8]{1,0} convert(%a.1)
  ROOT %d.1 = s32[4,16]{1,0} dot(%a.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
"""
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _quant_ctx(), _rules("R3-dtype")
    )
    assert findings and "raw int8" in findings[0].message
    # the identical module under an UNQUANTIZED config is not R3-quant's
    # business (int8 dots exist legitimately elsewhere)
    ctx = _quant_ctx()
    ctx.meta.pop("quantized")
    findings2, _ = engine.run_rules(
        {"before_opt": mod}, ctx, _rules("R3-dtype")
    )
    assert not findings2


def test_r3_quant_flags_missing_dequant_as_vacuous():
    """A quantized cell whose module contains no s8→float convert never
    dequantized anything — every other quant check would be vacuous."""
    mod = """\
HloModule m, entry_computation_layout={(f32[4,8]{1,0})->f32[4,4]{1,0}}

ENTRY %main.1 (a.1: f32[4,8]) -> f32[4,4] {
  %a.1 = f32[4,8]{1,0} parameter(0)
  ROOT %d.1 = f32[4,4]{1,0} dot(%a.1, %a.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, operand_precision={highest,highest}
}
"""
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _quant_ctx(), _rules("R3-dtype")
    )
    assert findings and "dequant" in findings[0].message


_QUANT_MIXED_TMPL = """\
HloModule m, entry_computation_layout={(f32[4,8]{1,0}, s8[16,8]{1,0}, s8[16,8]{1,0}, f32[16,8]{1,0})->f32[4,16]{1,0}}

ENTRY %main.1 (q.1: f32[4,8], a.1: s8[16,8], b.1: s8[16,8], s.1: f32[16,8]) -> f32[4,16] {
  %q.1 = f32[4,8]{1,0} parameter(0)
  %a.1 = s8[16,8]{1,0} parameter(1)
  %b.1 = s8[16,8]{1,0} parameter(2)
  %s.1 = f32[16,8]{1,0} parameter(3)
  %ca.1 = f32[16,8]{1,0} convert(%a.1)
%EXTRA%
  %d.1 = f32[4,16]{1,0} dot(%q.1, %FEED%), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  ROOT %d2.1 = f32[4,16]{1,0} dot(%q.1, %s.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, operand_precision={highest,highest}
}
"""


def _quant_mixed_mod(extra, feed):
    return _QUANT_MIXED_TMPL.replace("%EXTRA%", extra).replace(
        "%FEED%", feed
    )


def test_r3_quant_mixed_passes_one_dequant_one_multiply():
    mod = _quant_mixed_mod(
        "  %m.1 = f32[16,8]{1,0} multiply(%ca.1, %s.1)", "%m.1"
    )
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _quant_ctx("mixed"), _rules("R3-dtype")
    )
    assert not findings, [f.message for f in findings]


def test_r3_quant_mixed_flags_two_dequants_feeding_compress_dot():
    """Two quantized sources merged into one compress pass — a shape the
    wire/gather budgets do not model (and a likely sign the scales were
    crossed)."""
    mod = _quant_mixed_mod(
        "  %cb.1 = f32[16,8]{1,0} convert(%b.1)\n"
        "  %ad.1 = f32[16,8]{1,0} add(%ca.1, %cb.1)\n"
        "  %m.1 = f32[16,8]{1,0} multiply(%ad.1, %s.1)",
        "%m.1",
    )
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _quant_ctx("mixed"), _rules("R3-dtype")
    )
    assert findings and "2 dequant converts" in findings[0].message


def test_r3_quant_mixed_flags_unscaled_codes_at_compress_dot():
    """The compress dot sees the convert but no scale multiply — the
    codes are scored unscaled."""
    mod = _quant_mixed_mod("", "%ca.1")
    findings, _ = engine.run_rules(
        {"before_opt": mod}, _quant_ctx("mixed"), _rules("R3-dtype")
    )
    assert findings and "NO scale multiply" in findings[0].message


def test_r2_quant_flags_float_sized_bucket_gather():
    """Dequantize-before-gather: the gather moves float-width rows, so
    the bytes the store compressed away are re-paid on every probe —
    caught by the wire-priced gather bound, invisible to the
    element-denominated budget (element counts are identical)."""

    def deq_then_gather(idx, store_f32):
        return jnp.take(store_f32, idx, axis=0)

    lowered = jax.jit(deq_then_gather).lower(
        jnp.zeros((8, 2), jnp.int32),
        jnp.zeros((16, 64, 32), jnp.float32),
    )
    texts = lowering.hlo_texts(lowered)
    # the wire budget for the same probe at int8 lanes (2× headroom)
    budget = 2 * 8 * 2 * 64 * 32 * 1
    ctx = _quant_ctx(quant_gather_bytes=budget)
    findings, _ = engine.run_rules(texts, ctx, _rules("R2-memory"))
    assert any("quantized wire budget" in f.message for f in findings)

    def code_gather(idx, store_s8):
        return jnp.take(store_s8, idx, axis=0)

    lowered2 = jax.jit(code_gather).lower(
        jnp.zeros((8, 2), jnp.int32),
        jnp.zeros((16, 64, 32), jnp.int8),
    )
    findings2, _ = engine.run_rules(
        lowering.hlo_texts(lowered2), ctx, _rules("R2-memory")
    )
    assert not [f for f in findings2 if "wire budget" in f.message]


def test_r4_quant_flags_float_width_rotation_and_missing_scale_permute():
    """A float-width block rotating under an int8 label: the payload
    check prices every permute at the wire dtype, and the quantized
    permute count (3 per direction: codes + scales + ids) catches a
    dropped scale permute."""
    texts, cfg, meta = lowering.lower_target(
        lowering.LintTarget("ring-overlap", "l2", "float32", "mixed")
    )
    ring_n = meta["ring_n"]
    c_shard = 256 // ring_n  # LINT_M_MIXED rows over the ring
    bad_meta = {
        **meta,
        "quantized": True,
        # the int8 wire budget for this block; the f32 lowering's block
        # permute is 4× over it
        "permute_bytes_budget": c_shard * lowering.LINT_D,
        # the quantized schedule rotates three arrays; the f32 lowering
        # has two — a missing scale permute is a finding, not a pass
        "expected_permutes": 3,
    }
    ctx = engine.LintContext(
        target=lowering.LintTarget(
            "ring-overlap", "l2", "float32", "mixed", quant="xfer-int8"
        ),
        cfg=cfg,
        meta=bad_meta,
    )
    findings, _ = engine.run_rules(texts, ctx, _rules("R4-collective"))
    assert any("wire-dtype budget" in f.message for f in findings)
    assert any("expected exactly 3" in f.message for f in findings)


# ---------------------------------------------------------------------------
# Live-mutation counterexamples (ISSUE 14): the injected broken mutation
# programs must FIRE through the production rule path — an un-donated
# store update, a full-store copy, and the headroom-overflow full-store
# gather. The clean cells are certified by the default-matrix sweep
# (mutate-upsert/delete/compact above).


def _mutate_ctx(kind="upsert", **meta):
    """A mutation-cell context at the production meta shape
    (analysis/lowering._lower_mutate)."""
    meta.setdefault("q_tile", 32)
    meta.setdefault("c_tile", 32)
    meta.setdefault("acc_bytes", 4)
    meta.setdefault("mutate", kind)
    meta.setdefault("strict_exempt_ops", (
        "scatter", "dynamic-update-slice", "fusion", "bitcast", "reshape",
    ))
    return engine.LintContext(
        target=lowering.LintTarget("ivf", "l2", "float32", mutate=kind),
        cfg=KNNConfig(k=4, partitions=8, nprobe=2, query_tile=8),
        meta=meta,
    )


def _lint_mutation_index():
    cfg = lowering._ivf_cfg(
        lowering.LintTarget("ivf", "l2", "float32", mutate="upsert")
    )
    return lowering._ivf_lint_index(cfg)


def test_mutation_counterexample_undonated_store_fires_r5():
    """The SAME upsert program lowered WITHOUT donation: the compiled
    module carries no input_output_alias, so every chunk would allocate
    a fresh store — R5 must fire on the after-opt stage through the
    production rule path."""
    import jax

    from mpi_knn_tpu.ivf.mutate import UPSERT_DONATED, ivf_upsert_chunk
    from mpi_knn_tpu.serve.mutate import _mutation_chunk_specs

    index = _lint_mutation_index()
    undonated = jax.jit(ivf_upsert_chunk, static_argnames=("cfg",))
    chunk = [
        jax.ShapeDtypeStruct(s, d)
        for s, d in _mutation_chunk_specs(index, index.cfg, 32, "upsert")
    ]
    lowered = undonated.lower(
        chunk[0], chunk[1], chunk[2], chunk[3], chunk[4], chunk[5],
        index.buckets, index.bucket_ids, index.bucket_sqs,
        index.bucket_scales, cfg=index.cfg,
    )
    texts = lowering.hlo_texts(lowered)
    ctx = _mutate_ctx(
        donated_params=UPSERT_DONATED,
        resident_bytes=lowering.serve_resident_bytes(index),
        budget_elems=32 * lowering.LINT_D,
    )
    findings, ran = engine.run_rules(texts, ctx, _rules("R5-donation"))
    assert ran == ["R5-donation"]
    assert any(
        "no donation" in f.message or "no input_output_alias" in f.message
        or "carry\nno input_output_alias" in f.message
        or "carry " in f.message
        for f in findings
    ), [f.message for f in findings]
    # and the PRODUCTION (donated) program is clean under the same ctx
    from mpi_knn_tpu.serve.mutate import lower_mutation

    good = lowering.hlo_texts(lower_mutation(index, index.cfg, 32, "upsert"))
    ok_findings, _ = engine.run_rules(good, ctx, _rules("R5-donation"))
    assert not ok_findings, [f.message for f in ok_findings]


_MUT_BODY = """\

ENTRY %main.1 (p.1: s32[32], s.1: s32[32], b.1: f32[8,64,32]) -> f32[8,64,32] {
  %p.1 = s32[32]{0} parameter(0)
  %s.1 = s32[32]{0} parameter(1)
  %b.1 = f32[8,64,32]{2,1,0} parameter(2)
  %cp.1 = f32[8,64,32]{2,1,0} copy(%b.1)
  ROOT %r.1 = f32[8,64,32]{2,1,0} bitcast(%cp.1)
}
"""
_MUT_LAYOUT = (
    "entry_computation_layout={(s32[32]{0}, s32[32]{0}, "
    "f32[8,64,32]{2,1,0})->f32[8,64,32]{2,1,0}}"
)


def test_mutation_counterexample_full_store_copy_fires_census():
    """A mutation program that COPIES the whole resident store per chunk
    (instead of scattering in place) re-pays the corpus every mutation —
    the R5 copy census must fire even though the alias header is clean."""
    mod = (
        "HloModule m, input_output_alias={ {}: (2, {}, may-alias) }, "
        + _MUT_LAYOUT + _MUT_BODY
    )
    store_bytes = 8 * 64 * 32 * 4
    findings, _ = engine.run_rules(
        {"after_opt": mod},
        _mutate_ctx(donated_params=(2,), resident_bytes=store_bytes,
                    budget_elems=32 * 32),
        _rules("R5-donation"),
    )
    assert any("re-copied every batch" in f.message
               or "resident" in f.message for f in findings), (
        [f.message for f in findings]
    )


def test_mutation_counterexample_overflow_gather_fires_r2_strict():
    """The headroom-overflow shape: a 'mutation' program that gathers
    the FULL store to rebuild it (what growing shapes would force)
    materializes store-sized payload against a touched-chunk budget —
    R2-strict must fire on the gather, which is deliberately NOT in the
    in-place exemption set."""
    import jax
    import jax.numpy as jnp

    index = _lint_mutation_index()
    P, cap, d = (index.buckets.shape[0], index.bucket_cap,
                 index.buckets.shape[-1])

    def overflow_upsert(rows, part, slot, buckets):
        flat = buckets.reshape(-1, d)
        # a store-sized gather: every slot re-fetched to rebuild
        all_rows = flat[jnp.arange(P * cap) % (P * cap)]
        rebuilt = all_rows.reshape(P, cap, d)
        return rebuilt.at[part, slot].set(rows, mode="drop")

    lowered = jax.jit(overflow_upsert, donate_argnums=(3,)).lower(
        jax.ShapeDtypeStruct((32, d), jnp.float32),
        jax.ShapeDtypeStruct((32,), jnp.int32),
        jax.ShapeDtypeStruct((32,), jnp.int32),
        index.buckets,
    )
    texts = lowering.hlo_texts(lowered)
    ctx = _mutate_ctx(budget_elems=32 * d, donated_params=(3,),
                      resident_bytes=lowering.serve_resident_bytes(index))
    findings, _ = engine.run_rules(texts, ctx, _rules("R2-memory"))
    assert any(
        f.rule == "R2-memory" and "gather" in f.message
        for f in findings
    ), [f.message for f in findings]
    # the production upsert program fits the SAME touched-chunk budget
    from mpi_knn_tpu.serve.mutate import lower_mutation

    good = lowering.hlo_texts(lower_mutation(index, index.cfg, 32, "upsert"))
    ok_findings, _ = engine.run_rules(good, ctx, _rules("R2-memory"))
    assert not ok_findings, [f.message for f in ok_findings]


# ---------------------------------------------------------------------------
# a ring program without its rotation

# one dot, no collectives anywhere: what a ring's after-opt module looks
# like when the compiler removed the rotation
_PERMUTE_FREE_MODULE = """\
HloModule ring_round, entry_computation_layout={(f32[8,32]{1,0},f32[32,16]{1,0})->f32[8,16]{1,0}}

ENTRY %main.1 (q.1: f32[8,32], b.1: f32[32,16]) -> f32[8,16] {
  %q.1 = f32[8,32]{1,0} parameter(0)
  %b.1 = f32[32,16]{1,0} parameter(1)
  ROOT %dot.1 = f32[8,16]{1,0} dot(%q.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_permute_free_ring_program_is_the_rotation_vanished_finding():
    """A ring program whose after-opt module holds no collective-permute
    moved no block: R4 says the rotation was optimized away."""
    ctx = _ctx(backend="ring-overlap", ring_n=8, expected_permutes=0)
    findings, _ = engine.run_rules(
        {"after_opt": _PERMUTE_FREE_MODULE}, ctx, _rules("R4-collective")
    )
    assert any("optimized away" in f.message for f in findings), [
        f.message for f in findings
    ]
