"""The mixed-precision compress-and-rerank pipeline
(``KNNConfig.precision_policy="mixed"``, ops/rerank.py) against the f64
oracle and the exact policy — on CPU, where the compress pass's EXPLICIT
bf16 operand rounding makes the recall gate measure the same loss the TPU
MXU's single-pass DEFAULT dot would inflict (an implicit
``Precision.DEFAULT`` f32 dot is exact on CPU and would prove nothing).

The acceptance bar is the ISSUE 2 gate: recall@10 >= 0.999 vs the f64
oracle on all three backend families, plus the structural corners —
overfetch wider than the tile (the policy must degenerate to exact, not
crash or truncate), duplicate points whose compressed distances collapse at
the bf16 rounding boundary (the exact rerank must re-separate and
re-exclude them), and full id agreement with the exact policy when recall
is 1.0.
"""

import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.ops.rerank import mixed_applies, overfetch_width
from tests.oracle import oracle_all_knn, recall_against_oracle

K = 10
RECALL_GATE = 0.999

BACKENDS = ["serial", "ring"]


def _mnist_like(rng, m=512, d=96):
    """Integer-pixel-magnitude data (the headline workload's regime): large
    positive values whose CENTERED form genuinely loses mantissa bits in
    bf16 — the exact case the compress pass must survive via overfetch."""
    return np.rint(rng.random((m, d)) * 255.0).astype(np.float32)


@pytest.mark.parametrize("backend", BACKENDS)
def test_mixed_recall_gate_vs_f64_oracle(rng, backend):
    """The acceptance gate: recall@10 >= 0.999 vs the f64 oracle for every
    backend family, on data where bf16 compression is actually lossy."""
    X = _mnist_like(rng)
    got = all_knn(
        X,
        k=K,
        backend=backend,
        precision_policy="mixed",
        query_tile=64,
        corpus_tile=128,
    )
    want_d, want_i = oracle_all_knn(X, k=K)
    rec = recall_against_oracle(got.ids, want_d, want_i, K)
    assert rec >= RECALL_GATE, f"{backend}: recall@10 {rec} < {RECALL_GATE}"


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_mixed_matches_oracle_both_metrics(rng, backend, metric):
    X = (rng.standard_normal((300, 32)) * 3).astype(np.float32)
    got = all_knn(
        X,
        k=8,
        backend=backend,
        metric=metric,
        precision_policy="mixed",
        query_tile=64,
        corpus_tile=128,
    )
    want_d, want_i = oracle_all_knn(X, k=8, metric=metric)
    assert recall_against_oracle(got.ids, want_d, want_i, 8) >= RECALL_GATE
    np.testing.assert_allclose(
        np.asarray(got.dists), want_d, rtol=1e-3, atol=1e-3
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_mixed_agrees_with_exact_at_full_recall(rng, backend):
    """When mixed recall vs exact is 1.0 the two policies must return the
    SAME id sets per query and matching distances — the rerank's exact
    recompute (same mask semantics, HIGHEST dot) is what guarantees the
    surviving candidates score identically to the exact pipeline."""
    X = (rng.standard_normal((256, 24)) * 4).astype(np.float32)
    kw = dict(k=6, backend=backend, query_tile=32, corpus_tile=128)
    exact = all_knn(X, precision_policy="exact", **kw)
    mixed = all_knn(X, precision_policy="mixed", **kw)
    ex_sets = [set(r.tolist()) for r in np.asarray(exact.ids)]
    mx_sets = [set(r.tolist()) for r in np.asarray(mixed.ids)]
    rec = np.mean(
        [len(a & b) / 6 for a, b in zip(ex_sets, mx_sets)]
    )
    if rec < 1.0:
        pytest.skip(f"recall vs exact is {rec} on this draw; the "
                    "agreement claim is conditional on 1.0")
    assert ex_sets == mx_sets
    # same candidates, same exact recompute -> same sorted distance rows
    np.testing.assert_allclose(
        np.sort(np.asarray(mixed.dists), axis=1),
        np.sort(np.asarray(exact.dists), axis=1),
        rtol=1e-6,
        atol=1e-6,
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_overfetch_wider_than_tile_degenerates_to_exact(rng, backend):
    """4k > c_tile: the compress pass could not drop a candidate, so the
    pipeline must fall back to the exact single pass — identical id sets to
    the exact policy, no shape errors at the boundary."""
    X = (rng.standard_normal((200, 16)) * 3).astype(np.float32)
    # k=10 -> overfetch 40 > corpus_tile=32
    kw = dict(k=10, backend=backend, query_tile=32, corpus_tile=32)
    exact = all_knn(X, precision_policy="exact", **kw)
    mixed = all_knn(X, precision_policy="mixed", **kw)
    want_d, want_i = oracle_all_knn(X, k=10)
    assert recall_against_oracle(mixed.ids, want_d, want_i, 10) >= RECALL_GATE
    np.testing.assert_allclose(
        np.asarray(mixed.dists), np.asarray(exact.dists), rtol=1e-5,
        atol=1e-5,
    )
    assert not mixed_applies(10, 32)  # the XLA tile really is degenerate


def test_overfetch_width_boundaries():
    assert overfetch_width(4, 128) == 16
    assert overfetch_width(10, 32) == 32  # clamped to the tile
    assert mixed_applies(4, 128)
    assert not mixed_applies(10, 32)
    assert not mixed_applies(4, 16)  # 4k == c: nothing to drop


@pytest.mark.parametrize("backend", BACKENDS)
def test_duplicates_at_bf16_boundary_are_reseparated(rng, backend):
    """Exact duplicates plus near-duplicates that bf16 rounding COLLAPSES
    onto them: the compress pass sees identical keys for both (it cannot
    tell duplicate from near-twin), so only the exact rerank can (a)
    re-exclude the true duplicate by the zero rule and (b) keep the
    near-twin as the genuine nearest neighbor."""
    X = _mnist_like(rng, m=128, d=64)
    X[7] = X[3]  # exact duplicate pair
    # near-twin of row 11: one pixel nudged by 8 → exact d² = 64, above
    # the relative zero threshold (~1e-6·‖pair‖² ≈ 0.7 here) but orders
    # below both genuine neighbor distances (~1e6) AND the compress key's
    # bf16 noise floor (xy products ~3e5, bf16 ulp ≈ 2^-8 relative →
    # O(1e3) key error) — so the compressed keys of the duplicate and the
    # near-twin collapse and only the exact rerank can tell them apart
    X[42] = X[11]
    X[42, 0] += 8.0
    got = all_knn(
        X,
        k=6,
        backend=backend,
        precision_policy="mixed",
        query_tile=32,
        corpus_tile=128,
    )
    ids = np.asarray(got.ids)
    dists = np.asarray(got.dists)
    # duplicate pair excluded by the zero rule, on exact values
    assert 7 not in ids[3] and 3 not in ids[7]
    # near-twin kept, ranked first, at its exact (nonzero) distance —
    # a compressed-key-only pipeline could return it at key noise scale
    # (O(1e3)) or drop it as zero; the rerank restores d² = 64 exactly
    assert ids[11][0] == 42 and ids[42][0] == 11
    assert 1.0 < dists[11][0] < 1000.0


@pytest.mark.parametrize("schedule", ["stream", "twolevel"])
def test_mixed_both_merge_schedules(rng, schedule):
    """The policy lives in the per-tile reduction, below the schedule split
    — both schedules must pass the gate and agree with each other."""
    X = _mnist_like(rng, m=300, d=48)
    a = all_knn(X, k=K, backend="serial", precision_policy="mixed",
                merge_schedule=schedule, query_tile=64, corpus_tile=128)
    want_d, want_i = oracle_all_knn(X, k=K)
    assert recall_against_oracle(a.ids, want_d, want_i, K) >= RECALL_GATE


def test_mixed_ring_resumable_checkpoint_layout_unchanged(rng, tmp_path):
    """The carry stays exact f32 under mixed, so a kill-and-resume run is
    bit-identical to an uninterrupted one — same property the exact policy
    guarantees, now under the two-pass tile reduction."""
    from mpi_knn_tpu.backends.ring_resumable import all_knn_ring_resumable

    X = _mnist_like(rng, m=256, d=32)
    qids = np.arange(256, dtype=np.int32)
    cfg = KNNConfig(k=5, backend="ring", precision_policy="mixed",
                    query_tile=16, corpus_tile=128)
    full_d, full_i = all_knn_ring_resumable(
        X, X, qids, cfg, checkpoint_dir=None
    )
    ck = tmp_path / "ck"
    all_knn_ring_resumable(
        X, X, qids, cfg, checkpoint_dir=str(ck), stop_after_rounds=3
    )
    res_d, res_i = all_knn_ring_resumable(
        X, X, qids, cfg, checkpoint_dir=str(ck)
    )
    np.testing.assert_array_equal(np.asarray(full_d), np.asarray(res_d))
    np.testing.assert_array_equal(np.asarray(full_i), np.asarray(res_i))


def test_mixed_config_validation():
    with pytest.raises(ValueError, match="dtype"):
        KNNConfig(precision_policy="mixed", dtype="bfloat16")
    with pytest.raises(ValueError, match="matmul_precision"):
        KNNConfig(precision_policy="mixed", matmul_precision="high")
    with pytest.raises(ValueError, match="precision_policy"):
        KNNConfig(precision_policy="fast")
    # the valid combination constructs
    KNNConfig(precision_policy="mixed")


def test_r3_mixed_contract_catches_violations():
    """The lint side of the acceptance gate, negatively: a mixed-labeled
    program whose dots do NOT follow the declared contract (no DEFAULT
    compress dot / no HIGHEST rerank dot / a third precision) must be
    flagged by R3 through the production rule path."""
    from mpi_knn_tpu.analysis import engine, lowering
    from mpi_knn_tpu.analysis import rules as rules_mod

    def ctx():
        return engine.LintContext(
            target=lowering.LintTarget("serial", "l2", "float32", "mixed"),
            cfg=KNNConfig(k=4, query_tile=8, corpus_tile=32,
                          precision_policy="mixed"),
            meta={"q_tile": 8, "c_tile": 32, "acc_bytes": 4},
        )

    r3 = [r for r in rules_mod.RULES if r.name == "R3-dtype"]

    def run(body):
        mod = f"""\
HloModule m, entry_computation_layout={{(f32[4,8]{{1,0}})->f32[4,4]{{1,0}}}}

ENTRY %main.1 (a.1: f32[4,8]) -> f32[4,4] {{
  %a.1 = f32[4,8]{{1,0}} parameter(0)
{body}
}}
"""
        findings, _ = engine.run_rules({"before_opt": mod}, ctx(), r3)
        return findings

    dot = ("  %d{n}.1 = f32[4,4]{{1,0}} dot(%a.1, %a.1), "
           "lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}{attr}\n")
    d_def = dot.format(n=1, attr="")
    d_def2 = dot.format(n=2, attr="")
    d_hi = dot.format(n=3, attr=", operand_precision={highest,highest}")
    d_high = dot.format(n=4, attr=", operand_precision={high,high}")
    root = "  ROOT %r.1 = f32[4,4]{1,0} add(%d1.1, %d1.1)"

    # the declared shape: one DEFAULT + one HIGHEST — clean
    assert not run(d_def + d_hi + root)
    # missing rerank dot
    assert any("no highest" in f.message.lower()
               for f in run(d_def + root))
    # missing compress dot
    assert any("no default" in f.message.lower()
               for f in run(d_hi + root))
    # two compress dots in one computation
    assert any("2 default" in f.message.lower()
               for f in run(d_def + d_def2 + d_hi + root))
    # a third precision (HIGH) is neither compress nor rerank
    assert any("'high'" in f.message for f in run(d_def + d_hi + d_high + root))


def test_full_mixed_lint_matrix_is_clean():
    """The positive lint acceptance criterion: every mixed backend × metric
    cell lowers and passes all rules — R3 certifying exactly one DEFAULT
    compress dot per tile computation and a HIGHEST rerank dot (zero of
    either is itself a finding, so 'ok' is non-vacuous)."""
    from mpi_knn_tpu.analysis import engine, lowering

    targets = [t for t in lowering.default_targets() if t.policy == "mixed"]
    assert targets, "mixed cells missing from the default lint sweep"
    for t in targets:
        res = engine.lint_target(t)
        assert res.skipped is None, (t.label, res.skipped)
        assert res.ok, (t.label, [f.message for f in res.findings])
