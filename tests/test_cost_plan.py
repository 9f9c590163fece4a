"""Static cost certification (ISSUE 16): the R8-cost rule, the cost
ledger, and the ledger-driven capacity planner.

Four layers, mirroring test_memory_lint's structure for R7:

- the COST MODEL units: the closed-form FLOP schemes, the roofline's
  binding-leg naming, and the wire-priced collective census on
  hand-written HLO;
- INJECTED counterexamples through the production rule path
  (``engine.run_rules`` — the test_hlo_lint convention): a doctored
  declaration whose closed form cannot name the HLO's work (both
  directions of the exactness breach), an unpriced collective
  (``ragged-all-to-all`` — the spelling that evades the family
  prefixes), and a cell with no declared cost facts at all;
- the LEDGER: exactness on every committed cell (``mxu_flops ==
  analytical_flops``, no tolerance) and drift in both directions
  through the production ``mpi-knn lint --cost --ledger-check`` CLI;
- the PLANNER: in-matrix predictions equal the committed R7 ledger
  byte-for-byte (shared code path, not a parallel model), the matrix
  constants pin lowering's, refusals exit 2 naming the binding
  constraint, and predicted q/s ordering agrees with the committed
  CPU baseline within the nprobe family.
"""

import json

import pytest

from mpi_knn_tpu import plan as plan_mod
from mpi_knn_tpu.analysis import cost, engine, lowering, memory
from mpi_knn_tpu.analysis import rules as rules_mod
from mpi_knn_tpu.config import KNNConfig


def _rules(*names):
    return [r for r in rules_mod.RULES if r.name in names]


def _ctx(target, cfg, meta):
    return engine.LintContext(target=target, cfg=cfg, meta=dict(meta))


# ---------------------------------------------------------------------------
# the cost model units


def test_analytical_schemes_closed_form():
    """Hand-computed counts for every scheme; an unknown scheme is a
    loud error, not a silent zero."""
    assert cost.analytical_mxu_flops({"scheme": "zero"}) == 0
    dense = {"scheme": "dense", "q": 2, "c": 3, "d": 5,
             "sites": 2, "trips": 3}
    assert cost.analytical_mxu_flops(dense) == 2 * 3 * (2 * 2 * 3 * 5)
    mixed = dict(dense, rblocks=2, w=7)
    assert cost.analytical_mxu_flops(mixed) == 2 * 3 * (
        2 * 2 * 3 * 5 + 2 * 2 * 2 * 7 * 5
    )
    ivf = {"scheme": "ivf", "q": 2, "d": 5, "partitions": 4,
           "nprobe": 2, "bucket_cap": 3}
    assert cost.analytical_mxu_flops(ivf) == (
        2 * 2 * 4 * 5 + 2 * 2 * (2 * 3) * 5
    )
    with pytest.raises(ValueError, match="unknown cost scheme"):
        cost.analytical_mxu_flops({"scheme": "mystery", "q": 1, "d": 1})


def test_roofline_names_the_binding_leg():
    prof = {"peak_flops": 100.0, "hbm_bw": 10.0, "ici_bw": 1.0}
    r = cost.roofline(1000, 10, 0, 5, prof)
    assert (r["bound"], r["wall_s"]) == ("mxu", 10.0)
    assert r["qps"] == pytest.approx(0.5)
    # a single wire byte at 1 B/s out-costs everything
    assert cost.roofline(10, 10, 50, 5, prof)["bound"] == "ici"
    assert cost.roofline(10, 1000, 0, 5, prof)["bound"] == "hbm"


def test_profiles_ship_and_unknown_is_loud():
    for name in ("cpu-test", "tpu-v4", "tpu-v5e"):
        p = cost.get_profile(name)
        assert p["peak_flops"] > 0 and p["hbm_bytes"] > 0, name
    with pytest.raises(KeyError, match="cpu-test"):
        cost.get_profile("tpu-v9000")
    assert cost.profile_for_platform("cpu", "cpu") == "cpu-test"
    assert cost.profile_for_platform("tpu", "TPU v4") == "tpu-v4"
    assert cost.profile_for_platform("tpu", "TPU v5 lite") == "tpu-v5e"


_RAGGED = """\
HloModule m, entry_computation_layout={(f32[8,4]{1,0})->f32[8,4]{1,0}}

ENTRY %main.1 (a.1: f32[8,4]) -> f32[8,4] {
  %a.1 = f32[8,4]{1,0} parameter(0)
  ROOT %r.1 = f32[8,4]{1,0} ragged-all-to-all(%a.1), replica_groups={{0,1}}
}
"""

_PRICED = """\
HloModule m, entry_computation_layout={(f32[8,4]{1,0})->f32[8,4]{1,0}}

ENTRY %main.1 (a.1: f32[8,4]) -> f32[8,4] {
  %a.1 = f32[8,4]{1,0} parameter(0)
  ROOT %r.1 = f32[8,4]{1,0} collective-permute(%a.1), source_target_pairs={{0,1},{1,0}}
}
"""


def test_collective_census_prices_and_refuses():
    """A priced collective contributes its result bytes; a family
    opcode outside the registry is a problem, never a silent zero."""
    from mpi_knn_tpu.utils.hlo_graph import parse_hlo

    bytes_, problems = cost.collective_census(parse_hlo(_PRICED))
    assert bytes_ == 8 * 4 * 4 and not problems
    bytes_, problems = cost.collective_census(parse_hlo(_RAGGED))
    assert bytes_ == 0
    assert any("unpriced collective" in p for p in problems), problems


# ---------------------------------------------------------------------------
# injected counterexamples through the production rule path


def _lowered_serial():
    target = lowering.LintTarget("serial", "l2", "float32")
    texts, cfg, meta = lowering.lower_target(target)
    return target, texts, cfg, meta


def test_counterexample_doctored_facts_fire_both_directions():
    """The exactness contract through ``engine.run_rules``: shrink the
    declared corpus extent and the HLO does work the closed form cannot
    name; grow it and the closed form prices a dot the program lost.
    The honest declaration is finding-free."""
    target, texts, cfg, meta = _lowered_serial()
    ok, ran = engine.run_rules(texts, _ctx(target, cfg, meta),
                               _rules("R8-cost"))
    assert ran == ["R8-cost"]
    assert not ok, [f.message for f in ok]

    shrunk = dict(meta)
    shrunk["cost"] = {**meta["cost"], "c": meta["cost"]["c"] // 2}
    findings, _ = engine.run_rules(texts, _ctx(target, cfg, shrunk),
                                   _rules("R8-cost"))
    assert any("cannot name" in f.message for f in findings), [
        f.message for f in findings
    ]
    f = next(f for f in findings if "cannot name" in f.message)
    assert f.details["mxu_flops"] > f.details["analytical_flops"]

    grown = dict(meta)
    grown["cost"] = {**meta["cost"], "c": meta["cost"]["c"] * 2}
    findings, _ = engine.run_rules(texts, _ctx(target, cfg, grown),
                                   _rules("R8-cost"))
    assert any("lost a loop or a dot" in f.message for f in findings), [
        f.message for f in findings
    ]


def test_counterexample_unpriced_collective_is_a_finding():
    """``ragged-all-to-all`` through the production rule path: its
    spelling starts with none of the priced family prefixes, so before
    the ``ragged-`` marker it was invisible to the census — now it is
    an R8 finding naming the instruction."""
    target = lowering.LintTarget("serial", "l2", "float32")
    cfg = KNNConfig(k=4, query_tile=8, corpus_tile=16)
    ctx = _ctx(target, cfg, {"cost": {"scheme": "zero", "queries": 8}})
    findings, _ = engine.run_rules({"after_opt": _RAGGED}, ctx,
                                   _rules("R8-cost"))
    unpriced = [f for f in findings if "unpriced collective" in f.message]
    assert unpriced, [f.message for f in findings]
    assert "ragged-all-to-all" in unpriced[0].message
    # the priced spelling of the same program is census-clean
    ctx2 = _ctx(target, cfg, {"cost": {"scheme": "zero", "queries": 8}})
    ok, _ = engine.run_rules({"after_opt": _PRICED}, ctx2,
                             _rules("R8-cost"))
    assert not ok, [f.message for f in ok]


def test_counterexample_missing_cost_facts_is_a_finding():
    """A cell that declares no ``meta['cost']`` cannot be certified —
    that absence is itself a finding, not a skipped check."""
    target, texts, cfg, meta = _lowered_serial()
    bare = {k: v for k, v in meta.items() if k != "cost"}
    findings, _ = engine.run_rules(texts, _ctx(target, cfg, bare),
                                   _rules("R8-cost"))
    assert any("declares no cost facts" in f.message for f in findings)


# ---------------------------------------------------------------------------
# the committed ledger + drift through the production CLI


def test_committed_cost_ledger_is_exact_on_every_cell():
    """The committed artifact covers the full matrix and holds the
    exactness contract with NO tolerance: the HLO counter and the
    closed form agree to the FLOP on every cell, and every roofline
    names its binding resource."""
    doc = cost.load_cost_ledger(cost.DEFAULT_COST_LEDGER)
    assert doc is not None, "artifacts/lint/cost_ledger.json missing"
    assert len(doc["cells"]) >= 65
    for label, cell in doc["cells"].items():
        assert cell["mxu_flops"] == cell["analytical_flops"], label
        assert cell["roofline"]["bound"] in ("mxu", "hbm", "ici"), label
        assert cell["queries"] > 0, label
        if cell["mxu_flops"]:
            assert cell["largest_dot"]["instruction"], label


def test_cost_ledger_drift_through_production_cli(tmp_path):
    """Drift in BOTH directions through the real ``mpi-knn lint --cost
    --ledger-check`` path: a committed ledger claiming half the real
    FLOPs (the program grew) and one claiming double (the ledger went
    stale) must both fail the gate; the honest ledger passes."""
    from mpi_knn_tpu.analysis import cli as lint_cli

    args = ["--backend", "serial", "--metric", "l2", "--dtype",
            "float32", "--policy", "exact", "--schedule", "uni",
            "--out", str(tmp_path), "-q"]
    assert lint_cli.main(args + ["--cost"]) == 0
    ledger_path = tmp_path / "cost_ledger.json"
    honest = json.loads(ledger_path.read_text())
    label = "serial/l2/float32"
    assert label in honest["cells"]
    assert lint_cli.main(args + ["--cost", "--ledger-check"]) == 0
    # the program "grew" past the committed claim
    tampered = json.loads(json.dumps(honest))
    tampered["cells"][label]["mxu_flops"] //= 2
    ledger_path.write_text(json.dumps(tampered))
    assert lint_cli.main(args + ["--cost", "--ledger-check"]) == 1
    # the committed claim went stale above the real program
    tampered = json.loads(json.dumps(honest))
    tampered["cells"][label]["mxu_flops"] *= 2
    ledger_path.write_text(json.dumps(tampered))
    assert lint_cli.main(args + ["--cost", "--ledger-check"]) == 1
    # usage errors stay loud: --ledger-check without a ledger flag, a
    # --rule filter that would sweep WITHOUT R8, a missing committed
    # ledger
    assert lint_cli.main(args + ["--ledger-check"]) == 2
    assert lint_cli.main(args + ["--cost", "--rule", "R2-memory"]) == 2
    assert lint_cli.main(
        args + ["--cost", "--ledger-check",
                "--cost-ledger", str(tmp_path / "nope.json")]
    ) == 2


# ---------------------------------------------------------------------------
# the planner: shared code path with R7/R8, not a parallel model


def test_plan_matrix_constants_pin_lowering():
    """The planner's in-matrix shapes ARE lowering's lint shapes — a
    drift here silently downgrades byte-exact ledger lookups to model
    estimates."""
    assert plan_mod.MATRIX_DENSE == {
        "m": lowering.LINT_M, "d": lowering.LINT_D,
        "k": lowering.LINT_K, "bucket": lowering.LINT_NQ,
    }
    assert plan_mod.MATRIX_IVF == {
        "m": lowering.LINT_M_IVF, "d": lowering.LINT_D,
        "k": lowering.LINT_K, "bucket": lowering.LINT_NQ,
        "partitions": lowering.LINT_PARTITIONS,
        "nprobe": lowering.LINT_NPROBE,
        "shards": lowering.LINT_IVF_SHARDS,
    }


def test_plan_in_matrix_peak_equals_r7_ledger_byte_for_byte():
    committed = memory.load_ledger(plan_mod.DEFAULT_PLAN_LEDGER)
    assert committed is not None
    ref = plan_mod.MATRIX_IVF
    wl_dense = plan_mod.Workload(m=128, d=32, k=4, bucket=64)
    wl_ivf = plan_mod.Workload(m=ref["m"], d=32, k=4, bucket=64)
    cases = [
        (plan_mod.Candidate("serial"), wl_dense,
         "serial/l2/float32/serve"),
        (plan_mod.Candidate("ivf", partitions=ref["partitions"],
                            nprobe=ref["nprobe"]), wl_ivf,
         "ivf/l2/float32/serve"),
        (plan_mod.Candidate("ivf-sharded", partitions=ref["partitions"],
                            nprobe=ref["nprobe"],
                            shards=ref["shards"]), wl_ivf,
         "ivf-sharded/l2/float32/serve"),
    ]
    for cand, wl, label in cases:
        got = plan_mod.predict_peak_hbm(cand, wl)
        assert got["source"] == f"ledger:{label}", got
        assert got["peak_hbm_bytes"] == (
            committed["cells"][label]["peak_bytes"]
        ), label
    # and through the full search: the dense lint workload plans onto
    # the committed serial serve cell
    doc = plan_mod.plan(
        plan_mod.Workload(m=128, d=32, k=4, bucket=64,
                          recall_target=0.9),
        plan_mod.Fleet(), backends=("serial",), dtypes=("float32",),
    )
    assert doc["predicted"]["peak_hbm_source"] == (
        "ledger:serial/l2/float32/serve"
    )
    assert doc["predicted"]["peak_hbm_bytes"] == (
        committed["cells"]["serial/l2/float32/serve"]["peak_bytes"]
    )


def test_plan_off_matrix_uses_the_model_and_r7_decomposition():
    cand = plan_mod.Candidate("ivf", partitions=64, nprobe=4)
    wl = plan_mod.Workload(m=4096, d=64, k=10, bucket=128)
    got = plan_mod.predict_peak_hbm(cand, wl)
    assert got["source"] == "model"
    # the model is R7's own budget decomposition: args + outputs + the
    # temp allowance from analysis.memory — strictly more than the
    # resident store alone
    assert got["peak_hbm_bytes"] > 4096 * 64 * 4 / 64 * 4


@pytest.mark.parametrize(
    "argv,constraint,needle",
    [
        (["--corpus", "100000000", "--dim", "128",
          "--hbm-bytes", "1000000"], "hbm", "exceeds the budget"),
        (["--corpus", "4096", "--dim", "32", "--recall-target",
          "0.999", "--dtype", "int4"], "recall", "int4"),
        (["--corpus", "4096", "--dim", "32",
          "--qps", "1000000000000"], "qps", "roofline"),
    ],
)
def test_plan_refusals_exit_2_naming_the_binding_constraint(
    capsys, argv, constraint, needle
):
    rc = plan_mod.main(argv + ["-q"])
    assert rc == 2
    doc = json.loads(capsys.readouterr().out)
    assert doc["feasible"] is False
    assert doc["binding_constraint"] == constraint
    assert needle in doc["detail"]
    assert doc["rejected"][constraint] > 0
    assert doc["closest_candidate"]["backend"] in plan_mod.PLAN_BACKENDS


def test_plan_feasible_cli_emits_runnable_commands(capsys):
    rc = plan_mod.main(["--corpus", "2048", "--dim", "32", "--bucket",
                        "128", "--recall-target", "0.9", "-q"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["feasible"] is True
    assert doc["predicted"]["recall_at_k"] >= 0.9
    assert doc["commands"]["serve"].startswith("mpi-knn ")
    assert doc["predicted"]["roofline_bound"] in ("mxu", "hbm", "ici")
    # the unknown-profile refusal is a usage error, not a traceback
    assert plan_mod.main(["--corpus", "64", "--dim", "8",
                          "--device-profile", "tpu-v9000", "-q"]) == 2


def test_recall_calibration_is_monotone_and_dtype_capped():
    calib = plan_mod.load_calibration()
    fracs = [f for f, _ in calib["points"]]
    assert fracs == sorted(fracs) and len(fracs) >= 3
    rec = [plan_mod.predict_recall(f, "float32", calib)
           for f in fracs + [1.0]]
    assert rec == sorted(rec), rec
    scale = calib["dtype_scale"]
    assert scale["float32"] == pytest.approx(1.0)
    assert scale["int4"] < scale["int8"] <= 1.0
    # the int4 ceiling is the measured quantization cap — the number a
    # recall refusal names
    assert plan_mod.predict_recall(1.0, "int4", calib) < 0.95


def test_predicted_qps_ordering_matches_cpu_baseline_family():
    """Within the committed ivf_query nprobe family the measured q/s
    is strictly decreasing in nprobe — the planner's roofline must
    order the same configs the same way (ordering, not magnitude: the
    cpu-test profile is a declared stand-in, not a measured machine)."""
    doc = json.loads(
        (plan_mod.DEFAULT_BENCH).read_text()
    )
    family = {
        r["variant"]: r for r in doc["results"]
        if r.get("op") == "ivf_query"
    }
    measured = [family[f"p64-nprobe{n}"]["queries_per_s"]
                for n in (1, 4, 16)]
    assert measured == sorted(measured, reverse=True), measured
    prof = cost.get_profile("cpu-test")
    wl = plan_mod.Workload(m=61440, d=64, k=10, bucket=64)
    predicted = [
        plan_mod.predict_qps(
            plan_mod.Candidate("ivf", partitions=64, nprobe=n), wl, prof
        )["qps"]
        for n in (1, 4, 16)
    ]
    assert predicted == sorted(predicted, reverse=True), predicted


def test_bench_baseline_carries_roofline_columns():
    """Every serving row of the committed CPU baseline names its
    roofline cell and carries the prediction from the committed cost
    ledger — the static number the measured one is compared against."""
    doc = json.loads(plan_mod.DEFAULT_BENCH.read_text())
    ledger = cost.load_cost_ledger(cost.DEFAULT_COST_LEDGER)
    seen = 0
    for r in doc["results"]:
        if "roofline_cell" not in r:
            continue
        seen += 1
        assert r["roofline_cell"] == r["peak_hbm_cell"]
        cell = ledger["cells"][r["roofline_cell"]]
        assert r["predicted_qps"] == round(cell["roofline"]["qps"], 1)
        # static roofline is an upper bound; the host CPU baseline
        # must not beat physics
        assert r["queries_per_s"] <= r["predicted_qps"]
    assert seen >= 3
