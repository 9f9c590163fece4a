"""The clustered (IVF) index — k-means partitioner, recall-targeted probed
search, serve-cache and lint integration (``mpi_knn_tpu.ivf``).

The gates:

- recall@k ≥ the configured ``recall_target`` vs the f64 oracle on both a
  synthetic clustered corpus and the REAL bundled digits corpus
  (tie-aware: a backend that breaks a top-k-boundary tie differently is
  not a miss — ``tests/oracle.recall_against_oracle``);
- ``nprobe == partitions`` is the exact full scan: recall 1.0 and
  value-level distance parity vs the serial backend unconditionally, and
  BIT-identity gated on the platform's batched-vs-plain dot bit-stability
  probe (the ``test_ref_mpi_shim`` convention: CPU Eigen's summation
  order follows the contraction shape, which is environmental, not an
  indexing bug);
- save/load ``.npz`` round-trip is bit-identical end to end;
- k-means is bit-deterministic per seed and the empty-cluster re-seed
  path actually fires and repairs;
- serving a clustered index through the bucket cache issues ZERO
  steady-state compiles (counted at the XLA compiler via
  ``jax.monitoring``, the test_serve.py machinery) and is bit-identical
  to the one-shot search;
- the ACCEPTANCE bound: on the SIFT-shaped 32k corpus at the default
  ``recall_target=0.95``, the auto-tuned nprobe reaches measured
  recall@10 ≥ 0.95 while the probed bytes per query — asserted from lint
  R2's STRICT probed-bytes budget over the lowered serve program, not a
  Python-side counter — stay under 25 % of the resident corpus;
- lint rule R6 catches its injected counterexamples and the default ivf
  lint cells are clean.
"""

import dataclasses

import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, query_knn
from mpi_knn_tpu.ivf import (
    build_ivf_index,
    kmeans,
    load_ivf_index,
    save_ivf_index,
    search_ivf,
)
from tests.oracle import oracle_all_knn, recall_against_oracle

K = 10


def _clustered(rng, m=2048, d=48, centers=24, spread=0.25):
    """A corpus with genuine cluster structure — the workload IVF exists
    for (uniform random data is clusterless and any partitioner fails its
    preconditions there)."""
    cents = rng.standard_normal((centers, d)).astype(np.float32) * 4
    assign = rng.integers(0, centers, size=m)
    return (
        cents[assign] + rng.standard_normal((m, d)).astype(np.float32)
        * spread * 4
    ).astype(np.float32)


@pytest.fixture
def compile_counter():
    """XLA backend-compile counter (the test_serve.py machine check that
    a cache hit really compiled nothing), on the shared obs-registry
    scope instead of a third hand-rolled jax.monitoring listener."""
    from mpi_knn_tpu.obs.metrics import watch_compiles

    with watch_compiles() as counts:
        yield counts


# ---------------------------------------------------------------------------
# recall gates vs the f64 oracle


def test_recall_gate_synthetic(rng):
    X = _clustered(rng)
    idx = build_ivf_index(X, KNNConfig(k=K, partitions=32))
    sample = np.arange(0, 2048, 8)
    d, i = search_ivf(idx, X[sample], query_ids=sample.astype(np.int32))
    # wider oracle so the tie cohort at the k-th boundary is visible
    want_d, want_i = oracle_all_knn(X, k=K + 5, queries=X[sample],
                                    exclude_self=False)
    for r, s in enumerate(sample):
        want_d[r][want_i[r] == s] = np.inf  # self-exclusion by identity
    order = np.argsort(want_d, axis=1, kind="stable")
    want_d = np.take_along_axis(want_d, order, axis=1)
    want_i = np.take_along_axis(want_i, order, axis=1)
    rec = recall_against_oracle(i, want_d, want_i, K)
    assert rec >= idx.cfg.recall_target, rec
    # the auto-tune must have bought the recall sublinearly on clustered
    # data, not by degenerating to the full scan
    assert idx.nprobe < idx.partitions


def test_recall_gate_digits(rng):
    from mpi_knn_tpu.data.digits import load_digits

    X, _ = load_digits()
    X = X.astype(np.float32)
    idx = build_ivf_index(X, KNNConfig(k=K, partitions=16))
    sample = np.arange(0, len(X), 7)
    d, i = search_ivf(idx, X[sample], query_ids=sample.astype(np.int32))
    want_d, want_i = oracle_all_knn(X, k=K + 5, queries=X[sample],
                                    exclude_self=False)
    for r, s in enumerate(sample):
        want_d[r][want_i[r] == s] = np.inf
    order = np.argsort(want_d, axis=1, kind="stable")
    want_d = np.take_along_axis(want_d, order, axis=1)
    want_i = np.take_along_axis(want_i, order, axis=1)
    assert recall_against_oracle(i, want_d, want_i, K) >= \
        idx.cfg.recall_target


def test_mixed_policy_composes(rng):
    """precision_policy='mixed' rides the same probed candidates through
    the compress-and-rerank recipe — the gate must hold there too."""
    X = _clustered(rng, m=1024, d=64)
    idx = build_ivf_index(
        X, KNNConfig(k=K, partitions=8, nprobe=4,
                     precision_policy="mixed")
    )
    sample = np.arange(0, 1024, 8)
    _, i_mixed = search_ivf(idx, X[sample],
                            query_ids=sample.astype(np.int32))
    _, i_exact = search_ivf(idx, X[sample],
                            query_ids=sample.astype(np.int32),
                            precision_policy="exact")
    # same probed candidates, exact rerank both ways: near-total agreement
    agree = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / K
        for a, b in zip(i_mixed, i_exact)
    ])
    assert agree >= 0.999, agree


# ---------------------------------------------------------------------------
# nprobe == partitions: the degenerate exact full scan


def _batched_dot_bit_stable() -> bool:
    """Environment probe for the bit-identity claim: does this backend's
    f32 HIGHEST dot produce identical bits through the plain (q,d)×(c,d)
    matmul and the batched (q,d)×(q,v,d) candidate form? True on the TPU
    MXU; false where CPU Eigen picks different summation orders per
    contraction shape (environmental — the ``test_ref_mpi_shim``
    precedent)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.random((8, 48)) * 255, dtype=jnp.float32)
    c = jnp.asarray(rng.random((128, 48)) * 255, dtype=jnp.float32)

    plain = np.asarray(jax.jit(
        lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
    )(q, c))
    batched = np.asarray(jax.jit(
        lambda a, b: jax.lax.dot_general(
            a, jnp.broadcast_to(b, (8, 128, 48)),
            (((1,), (2,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST)
    )(q, c))
    return bool(np.array_equal(plain, batched))


def test_nprobe_equals_partitions_is_brute_force(rng):
    from mpi_knn_tpu import all_knn

    X = _clustered(rng, m=1024, d=32)
    idx = build_ivf_index(X, KNNConfig(k=K, partitions=8, nprobe=8))
    sample = np.arange(0, 1024, 4)
    gd, gi = search_ivf(idx, X[sample], query_ids=sample.astype(np.int32))
    want = all_knn(X, queries=X[sample], query_ids=sample,
                   config=KNNConfig(k=K, backend="serial"))
    wd, wi = np.asarray(want.dists), np.asarray(want.ids)
    # value-level parity and full recall hold on ANY platform
    np.testing.assert_allclose(gd, wd, rtol=1e-5, atol=1e-5)
    rec = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / K for a, b in zip(gi, wi)
    ])
    assert rec == 1.0 or rec >= 0.999, rec
    if not _batched_dot_bit_stable():
        pytest.skip(
            "environmental: this backend's f32 dot is not bit-stable "
            "between the plain and batched contraction forms (probe), so "
            "serial-vs-ivf bit-identity cannot hold here; value/recall "
            "parity asserted above"
        )
    np.testing.assert_array_equal(gd, wd)

    def tie_canonical(dists_arr, ids_arr):
        out = np.empty_like(ids_arr)
        for r in range(ids_arr.shape[0]):
            out[r] = ids_arr[r][np.lexsort((ids_arr[r], dists_arr[r]))]
        return out

    np.testing.assert_array_equal(
        tie_canonical(wd, wi), tie_canonical(gd, gi)
    )


# ---------------------------------------------------------------------------
# save/load, determinism, empty-cluster re-seed


def test_save_load_round_trip_bit_identity(rng, tmp_path):
    X = _clustered(rng, m=512, d=24)
    idx = build_ivf_index(X, KNNConfig(k=5, partitions=8))
    Q = X[::16]
    d1, i1 = search_ivf(idx, Q)
    path = save_ivf_index(idx, str(tmp_path / "idx"))
    idx2 = load_ivf_index(path)
    assert idx2.cfg == idx.cfg
    assert idx2.nprobe == idx.nprobe
    np.testing.assert_array_equal(
        np.asarray(idx.buckets), np.asarray(idx2.buckets)
    )
    np.testing.assert_array_equal(
        np.asarray(idx.centroids), np.asarray(idx2.centroids)
    )
    d2, i2 = search_ivf(idx2, Q)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(i1, i2)


def test_save_load_bf16_at_rest(rng, tmp_path):
    X = _clustered(rng, m=512, d=24)
    idx = build_ivf_index(
        X, KNNConfig(k=5, partitions=8, dtype="bfloat16")
    )
    assert idx.nbytes_resident == idx.buckets.size * 2  # half-width store
    d1, i1 = search_ivf(idx, X[::16])
    path = save_ivf_index(idx, str(tmp_path / "idx16"))
    idx2 = load_ivf_index(path)
    assert str(idx2.buckets.dtype) == "bfloat16"
    d2, i2 = search_ivf(idx2, X[::16])
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(i1, i2)


def test_seeded_kmeans_determinism(rng):
    X = _clustered(rng, m=600, d=16)
    a = kmeans(X, 12, seed=3)
    b = kmeans(X, 12, seed=3)
    np.testing.assert_array_equal(
        np.asarray(a.centroids), np.asarray(b.centroids)
    )
    np.testing.assert_array_equal(
        np.asarray(a.assignments), np.asarray(b.assignments)
    )
    c = kmeans(X, 12, seed=4)
    assert not np.array_equal(np.asarray(a.centroids),
                              np.asarray(c.centroids))
    # and the whole trained INDEX is seed-deterministic
    i1 = build_ivf_index(X, KNNConfig(k=5, partitions=12, ivf_seed=3))
    i2 = build_ivf_index(X, KNNConfig(k=5, partitions=12, ivf_seed=3))
    np.testing.assert_array_equal(
        np.asarray(i1.bucket_ids), np.asarray(i2.bucket_ids)
    )


def test_empty_cluster_reseed_path(rng):
    """More partitions than DISTINCT points: vanilla Lloyd's would leave
    empty clusters and NaN centroids; the deterministic farthest-point
    re-seed must keep every centroid finite and the index must still
    answer exactly."""
    base = rng.standard_normal((4, 8)).astype(np.float32) * 3
    X = np.repeat(base, 8, axis=0)  # 32 rows, only 4 distinct
    res = kmeans(X, 8, seed=0, init="random")
    assert np.isfinite(np.asarray(res.centroids)).all()
    # k-means on 4-distinct-point data: at most 4 clusters can own points,
    # so the re-seed path has genuinely fired (some counts are 0, never NaN)
    assert int((np.asarray(res.counts) == 0).sum()) >= 4
    # ... and the full index still answers: nearest neighbor of each row
    # is one of its 7 duplicates, excluded by the zero rule -> distances
    # to the OTHER clusters' points are exact
    idx = build_ivf_index(
        X, KNNConfig(k=3, partitions=8, nprobe=8, ivf_seed=0,
                     kmeans_init="random")
    )
    qids = np.arange(32, dtype=np.int32)
    d, i = search_ivf(idx, X, query_ids=qids)
    assert np.isfinite(d).all()
    # duplicates are zero-distance-excluded; survivors are real neighbors
    assert (i >= 0).all()
    for r in range(32):
        assert r not in i[r]


# ---------------------------------------------------------------------------
# serve-cache integration


def test_serve_cache_zero_steady_state_compiles(rng, compile_counter):
    X = _clustered(rng, m=1024, d=24)
    idx = build_ivf_index(
        X, KNNConfig(k=7, partitions=8, nprobe=2, query_bucket=64)
    )
    rng2 = np.random.default_rng(5)
    warm_sizes = (64, 128)
    for n in warm_sizes:
        query_knn(rng2.standard_normal((n, 24)).astype(np.float32), idx)
    compile_counter.clear()
    for n in (1, 17, 63, 64, 65, 100, 128):
        res = query_knn(
            rng2.standard_normal((n, 24)).astype(np.float32), idx
        )
        assert res.ids.shape == (n, 7)
    assert compile_counter == [], (
        f"steady-state ivf serving compiled {len(compile_counter)} "
        "program(s)"
    )
    assert len(idx._cache) == len(warm_sizes)


def test_serve_matches_one_shot_bit_identically(rng):
    from mpi_knn_tpu.serve import ServeSession

    X = _clustered(rng, m=768, d=24)
    idx = build_ivf_index(
        X, KNNConfig(k=6, partitions=8, query_bucket=32)
    )
    Q = rng.standard_normal((70, 24)).astype(np.float32)
    d1, i1 = search_ivf(idx, Q)
    res = query_knn(Q, idx)
    np.testing.assert_array_equal(res.dists, d1)
    np.testing.assert_array_equal(res.ids, i1)
    sess = ServeSession(idx)
    outs = list(sess.stream([Q[:20], Q[20:50], Q[50:]]))
    np.testing.assert_array_equal(
        np.concatenate([o.ids for o in outs]), i1
    )


def test_serve_refuses_corpus_side_changes(rng):
    X = _clustered(rng, m=256, d=16)
    idx = build_ivf_index(X, KNNConfig(k=5, partitions=4))
    with pytest.raises(ValueError, match="corpus-side"):
        idx.compatible_cfg(idx.cfg.replace(partitions=8))
    with pytest.raises(ValueError, match="corpus-side"):
        idx.compatible_cfg(idx.cfg.replace(ivf_seed=9))
    # nprobe is query-side: varying it is allowed and resolves
    assert idx.compatible_cfg(idx.cfg.replace(nprobe=2)).nprobe == 2
    assert idx.compatible_cfg(idx.cfg.replace(nprobe=None)).nprobe == \
        idx.nprobe
    # knobs the probed path cannot honor are refused, not silently
    # ignored — a measurement labeled 'approx' for a run that executed
    # the exact rerank would be a lie
    with pytest.raises(ValueError, match="topk_method"):
        idx.compatible_cfg(idx.cfg.replace(topk_method="approx"))
    with pytest.raises(ValueError, match="matmul_precision"):
        idx.compatible_cfg(idx.cfg.replace(matmul_precision="high"))
    with pytest.raises(ValueError, match="merge_schedule"):
        idx.compatible_cfg(idx.cfg.replace(merge_schedule="stream"))
    with pytest.raises(ValueError, match="topk_method"):
        build_ivf_index(X, KNNConfig(k=5, partitions=4,
                                     topk_method="approx"))


def test_build_refusals():
    X = np.zeros((64, 8), np.float32)
    with pytest.raises(ValueError, match="partitions"):
        build_ivf_index(X, KNNConfig(k=3))
    with pytest.raises(ValueError, match="backend"):
        build_ivf_index(X, KNNConfig(k=3, partitions=4,
                                     backend="ring-overlap"))
    with pytest.raises(ValueError, match="metric"):
        KNNConfig(k=3, partitions=4, metric="cosine")
    with pytest.raises(ValueError, match="nprobe"):
        KNNConfig(k=3, partitions=4, nprobe=8)
    with pytest.raises(ValueError, match="nprobe"):
        KNNConfig(k=3, nprobe=2)
    with pytest.raises(ValueError, match="dtype"):
        build_ivf_index(X, KNNConfig(k=3, partitions=4, dtype="float64"))
    with pytest.raises(ValueError, match="exceeds"):
        build_ivf_index(np.zeros((4, 8), np.float32),
                        KNNConfig(k=3, partitions=8))


def test_cli_refusals_exit_2(tmp_path, rng):
    from mpi_knn_tpu.ivf import cli as ivf_cli
    from mpi_knn_tpu.serve import cli as serve_cli

    assert ivf_cli.main(
        ["--data", "synthetic:64x8c2", "--partitions", "4",
         "--metric", "cosine", "--out", str(tmp_path / "x.npz")]
    ) == 2
    assert ivf_cli.main(
        ["--data", "synthetic:64x8c2", "--partitions", "4",
         "--backend", "pallas", "--out", str(tmp_path / "x.npz")]
    ) == 2
    assert ivf_cli.main(
        ["--data", "synthetic:64x8c2", "--partitions", "4",
         "--nprobe", "9", "--out", str(tmp_path / "x.npz")]
    ) == 2
    # a real index, then unhonorable query flags against it
    path = str(tmp_path / "ok.npz")
    assert ivf_cli.main(
        ["--data", "synthetic:256x16c4", "--partitions", "4", "--k", "3",
         "--out", path, "-q"]
    ) == 0
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--backend", "ring-overlap", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--metric", "cosine", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--nprobe", "99", "--synthetic", "8"]
    ) == 2
    # corpus-side flags baked into the saved layout: explicitly passing
    # them alongside --index-load is refused, never silently dropped
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--corpus-tile", "4096", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--ring-schedule", "bidir", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--ring-transfer-dtype", "int8", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--dtype", "bfloat16", "--synthetic", "8"]
    ) == 2
    # --nprobe without a clustered index is a silently-ignored knob: refuse
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--nprobe", "2",
         "--synthetic", "8"]
    ) == 2
    # the honorable combination serves
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--synthetic", "16", "--batch", "8", "--bucket", "8", "-q"]
    ) == 0


# ---------------------------------------------------------------------------
# the acceptance bound: lint-asserted probed bytes on the 32k SIFT corpus


def test_sift32k_recall_target_with_sublinear_probed_bytes():
    """ISSUE 5 acceptance: at the default recall_target=0.95 the
    auto-tuned nprobe reaches measured recall@10 ≥ 0.95 on the
    SIFT-shaped 32k corpus while scanning < 25 % of corpus bytes per
    query — and the probed-bytes bound is asserted from lint R2's STRICT
    budget over the LOWERED serve program (plus R6's gather discipline),
    not from Python-side counters."""
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis.lowering import (
        LintTarget,
        _ivf_meta,
        hlo_texts,
    )
    from mpi_knn_tpu.data.synthetic import make_sift_like
    from mpi_knn_tpu.serve.engine import lower_bucket

    X = make_sift_like(m=32768, d=128, seed=0)
    cfg = KNNConfig(k=K, partitions=64, kmeans_iters=10, query_bucket=256)
    assert cfg.recall_target == 0.95  # the DEFAULT target is the subject
    idx = build_ivf_index(X, cfg)

    # measured recall@10 vs the f64 oracle on a held-out sample
    sample = np.linspace(0, 32767, num=128, dtype=np.int64)
    _, got = search_ivf(idx, X[sample], query_ids=sample.astype(np.int32))
    X64 = X.astype(np.float64)
    od = (
        (X64[sample] ** 2).sum(1)[:, None]
        + (X64**2).sum(1)[None, :]
        - 2.0 * (X64[sample] @ X64.T)
    )
    od[od <= 1e-9] = np.inf
    od[np.arange(len(sample)), sample] = np.inf
    order = np.argsort(od, axis=1, kind="stable")[:, : K + 5]
    want_d = np.take_along_axis(od, order, axis=1)
    rec = recall_against_oracle(got, want_d, order.astype(np.int32), K)
    assert rec >= 0.95, f"auto-tuned nprobe={idx.nprobe}: recall {rec}"

    # the probed-bytes bound, from the compiled program: lower the REAL
    # serve-cache cell for this index and run R2 in strict mode with the
    # probe gather as the declared budget — if anything in the program
    # materialized more than nprobe·bucket_cap·d per query row (e.g. a
    # full-corpus scan), R2 flags it and this assert fails
    serve_cfg = idx.compatible_cfg(idx.cfg)
    lowered, q_pad, q_tile = lower_bucket(idx, serve_cfg, 256)
    meta = {
        **_ivf_meta(idx, serve_cfg, q_tile, q_pad, 256),
        "serve": True,
        "donated_params": idx.layout.donate_argnums,
        "resident_bytes": idx.nbytes_resident,
    }
    probe_budget_bytes = meta["budget_elems"] * meta["acc_bytes"]
    corpus_bytes_per_batch = q_tile * idx.m * idx.dim * 4
    assert probe_budget_bytes < 0.25 * corpus_bytes_per_batch, (
        "the lint budget itself must be sublinear: "
        f"{probe_budget_bytes} vs corpus-scan {corpus_bytes_per_batch}"
    )
    target = LintTarget("ivf", "l2", "float32", serve=True)
    ctx = engine.LintContext(target=target, cfg=serve_cfg, meta=meta)
    findings, ran = engine.run_rules(hlo_texts(lowered), ctx)
    assert "R2-memory" in ran and "R6-ivf-probe" in ran
    assert not findings, [f.message for f in findings]


# ---------------------------------------------------------------------------
# lint: R6 counterexamples + the default ivf cells


def _r6_ctx():
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis.lowering import LintTarget

    return engine.LintContext(
        target=LintTarget("ivf", "l2", "float32"),
        cfg=KNNConfig(k=4, partitions=8, nprobe=2),
        meta={"q_tile": 8, "c_tile": 64, "acc_bytes": 4,
              "partitions": 8, "dim": 16},
    )


def _run_r6(body):
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis import rules as rules_mod

    r6 = [r for r in rules_mod.RULES if r.name == "R6-ivf-probe"]
    mod = f"""\
HloModule m, entry_computation_layout={{(f32[8,16]{{1,0}},s32[8,2]{{1,0}},\
f32[512,16]{{1,0}})->f32[8,4]{{1,0}}}}

ENTRY %main.1 (a.1: f32[8,16], p.1: s32[8,2], c.1: f32[512,16]) -> f32[8,4] {{
  %a.1 = f32[8,16]{{1,0}} parameter(0)
  %p.1 = s32[8,2]{{1,0}} parameter(1)
  %c.1 = f32[512,16]{{1,0}} parameter(2)
{body}
}}
"""
    findings, _ = engine.run_rules({"before_opt": mod}, _r6_ctx(), r6)
    return findings


def test_r6_catches_injected_counterexamples():
    gather = (
        "  %g.1 = f32[8,64,16]{2,1,0} gather(%c.1, %p.1), "
        "offset_dims={2}, collapsed_slice_dims={0}, start_index_map={0}, "
        "index_vector_dim=2, slice_sizes={1,16}\n"
    )
    # broadcast stands in for a candidate tensor NOT derived from a gather
    bcast = (
        "  %b.1 = f32[8,512,16]{2,1,0} broadcast(%c.1), dimensions={1,2}\n"
    )
    probed_dot = (
        "  %d1.1 = f32[8,4]{1,0} dot(%a.1, %g.1), lhs_batch_dims={0}, "
        "lhs_contracting_dims={1}, rhs_batch_dims={0}, "
        "rhs_contracting_dims={2}, operand_precision={highest,highest}\n"
    )
    unprobed_dot = (
        "  %d2.1 = f32[8,4]{1,0} dot(%a.1, %b.1), lhs_batch_dims={0}, "
        "lhs_contracting_dims={1}, rhs_batch_dims={0}, "
        "rhs_contracting_dims={2}, operand_precision={highest,highest}\n"
    )
    corpus_dot = (
        "  %d3.1 = f32[8,512]{1,0} dot(%a.1, %c.1), "
        "lhs_contracting_dims={1}, rhs_contracting_dims={1}, "
        "operand_precision={highest,highest}\n"
    )
    root = "  ROOT %r.1 = f32[8,4]{1,0} add(%d1.1, %d1.1)"

    # the declared shape: gather feeding the batched exact dot — clean
    assert not _run_r6(gather + probed_dot + root)
    # a batched dot NOT fed by a gather: scores unprobed rows
    bad = _run_r6(gather + bcast + probed_dot + unprobed_dot + root)
    assert any("no gather" in f.message.lower() for f in bad)
    # an un-batched full-corpus dot bypasses partition pruning entirely
    bad = _run_r6(gather + probed_dot + corpus_dot + root)
    assert any("bypasses the partition pruning" in f.message for f in bad)
    # no batched candidate dot at all: the contract is vacuous
    bad = _run_r6(gather + corpus_dot.replace("%d3", "%d1") + root)
    assert any("vacuous" in f.message.lower() for f in bad)


def test_r2_strict_budget_catches_full_corpus_materialization():
    """R2 in strict (budget_elems) mode: a corpus-sized GATHER result is a
    finding even though the corpus itself is an exempt parameter — the
    probed-bytes bound is the claim, not 'no bigger than the input'."""
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis import rules as rules_mod

    r2 = [r for r in rules_mod.RULES if r.name == "R2-memory"]
    ctx = _r6_ctx()
    ctx.meta["budget_elems"] = 8 * 64 * 16  # q_tile * v * d
    big = (
        "  %g.1 = f32[8,512,16]{2,1,0} gather(%c.1, %p.1), "
        "offset_dims={2}, collapsed_slice_dims={0}, start_index_map={0}, "
        "index_vector_dim=2, slice_sizes={1,16}\n"
        "  ROOT %r.1 = f32[8,4]{1,0} slice(%g.1), "
        "slice={[0:8], [0:4], [0:1]}"
    )
    mod = f"""\
HloModule m, entry_computation_layout={{(s32[8,2]{{1,0}},\
f32[512,16]{{1,0}})->f32[8,4]{{1,0}}}}

ENTRY %main.1 (p.1: s32[8,2], c.1: f32[512,16]) -> f32[8,4] {{
  %p.1 = s32[8,2]{{1,0}} parameter(0)
  %c.1 = f32[512,16]{{1,0}} parameter(1)
{big}
}}
"""
    findings, _ = engine.run_rules({"before_opt": mod}, ctx, r2)
    assert any("probed-bytes" in f.message for f in findings), (
        [f.message for f in findings]
    )


def test_default_ivf_lint_cells_are_clean():
    """The positive lint criterion: every default ivf cell lowers and
    passes all applicable rules — R6 and strict-R2 run on every one (zero
    batched dots or an over-budget buffer would be findings, so 'ok' is
    non-vacuous), R5 on the serve cells. The set includes the two
    degradation-ladder cells (ladder-bucket, ladder-nprobe — the programs
    resilience/ladder.py's rungs serve under deadline breach; the nprobe
    rung must fit R2-strict's SMALLER probed-bytes budget)."""
    from mpi_knn_tpu.analysis import engine, lowering

    targets = [t for t in lowering.default_targets() if t.backend == "ivf"]
    plain = [t for t in targets if not t.quant and not t.mutate]
    assert len(plain) == 6, targets
    assert sorted(t.ladder for t in plain) == [
        "", "", "", "", "bucket", "nprobe",
    ]
    # the live-mutation cells (ISSUE 14) ride the same sweep but carry
    # their own contract (R5 donation on the scatter programs, R2-strict
    # touched-set budget; R6's probe discipline has no dot to check) —
    # certified in depth by tests/test_mutation.py + test_hlo_lint.py
    assert sorted(t.mutate for t in targets if t.mutate) == [
        "compact", "delete", "upsert",
    ]
    # the quantized at-rest cells (ISSUE 9): int8 one-shot × both
    # policies, int4 one-shot, int8 mixed serve — certified in depth by
    # tests/test_quant.py and the named check.sh gate; here they ride the
    # same positive sweep
    assert sorted((t.quant, t.policy, t.serve) for t in targets
                  if t.quant) == [
        ("int4", "exact", False),
        ("int8", "exact", False),
        ("int8", "mixed", False),
        ("int8", "mixed", True),
    ]
    for t in targets:
        res = engine.lint_target(t)
        assert res.skipped is None, (t.label, res.skipped)
        assert res.ok, (t.label, [f.message for f in res.findings])
        if t.mutate:
            assert "R5-donation" in res.rules_run
            assert "R6-ivf-probe" not in res.rules_run
        else:
            assert "R6-ivf-probe" in res.rules_run
        if t.serve:
            assert "R5-donation" in res.rules_run


@pytest.mark.parametrize("m,d,rests_at", [
    (512, 24, 24),
    # 2048-row tiles of fractional float32 rows off the lane grid rest
    # zero-padded to it (ISSUE 49, ``serve/index.py rest_width``): the rows
    # come back out of the stack at their own width
    (2148, 100, 128),
])
def test_build_from_serve_corpus_index(rng, m, d, rests_at):
    """An IVFIndex built FROM a serial-layout serve.CorpusIndex (its
    centered resident tiles, no second centering pass) answers
    identically to one built from the raw array."""
    from mpi_knn_tpu.serve import build_index

    X = _clustered(rng, m=m, d=d)
    cfg = KNNConfig(k=5, partitions=8, nprobe=3)
    from_array = build_ivf_index(X, cfg)
    corpus_idx = build_index(X, KNNConfig(k=5, backend="serial"))
    assert corpus_idx.tiles.shape[-1] == rests_at and corpus_idx.dim == d
    from_index = build_ivf_index(corpus_idx, cfg)
    assert from_index.dim == d
    np.testing.assert_array_equal(
        np.asarray(from_array.bucket_ids),
        np.asarray(from_index.bucket_ids),
    )
    Q = X[::16]
    d1, i1 = search_ivf(from_array, Q)
    d2, i2 = search_ivf(from_index, Q)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_allclose(d1, d2, rtol=1e-6, atol=1e-6)
    # non-serial layouts cannot donate their corpus back
    ring_like = build_index(X, KNNConfig(k=5, backend="ring-overlap"))
    with pytest.raises(ValueError, match="serial-layout"):
        build_ivf_index(ring_like, cfg)


def test_config_round_trips_through_npz(rng, tmp_path):
    """Every KNNConfig field survives the save/load JSON (a new field
    added without npz support would silently reload as its default)."""
    X = _clustered(rng, m=256, d=16)
    cfg = KNNConfig(k=5, partitions=4, nprobe=2, kmeans_iters=7,
                    kmeans_init="random", ivf_seed=11)
    idx = build_ivf_index(X, cfg)
    path = save_ivf_index(idx, str(tmp_path / "cfg"))
    idx2 = load_ivf_index(path)
    assert dataclasses.asdict(idx2.cfg) == dataclasses.asdict(idx.cfg)


# ---------------------------------------------------------------------------
# the bucket-major probe (ISSUE 42): the same answers as the row-major tile
# body, the same five counts, and no probe dropped whatever the skew

_WALK_P = 16  # lists of the parity index (d = 128: the bucket is a block)


@pytest.fixture(scope="module")
def walk_index():
    """One small clustered index at d = 128 with what a served store
    holds: a list emptied after the build, slots tombstoned (id -1) in two
    others. ``exclude_self`` is a query-side knob, so both values of it run
    against the one store."""
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    X = _clustered(rng, m=4096, d=128, centers=24)
    idx = build_ivf_index(X, KNNConfig(k=K, partitions=_WALK_P, nprobe=4))
    assert idx.onepass is None  # fractional rows: no branch anywhere
    return _worn(idx), X


def _worn(idx):
    """``idx`` with a list emptied and slots tombstoned in two others."""
    import jax.numpy as jnp

    ids = np.asarray(idx.bucket_ids).copy()
    sizes = (ids >= 0).sum(axis=1)
    ids[int(np.argsort(sizes)[_WALK_P // 2])] = -1  # an empty list
    for p in np.argsort(sizes)[-2:]:  # dead slots in the two largest
        ids[p, ::3] = -1
    idx.bucket_ids = jnp.asarray(ids)
    return idx


@pytest.fixture(scope="module")
def whole_walk_index():
    """:func:`walk_index` over WHOLE numbers 0-255 (descriptor-like): the
    build centres by a whole-number mean, every stored element is a bf16
    number and the index holds the one-pass fact (ISSUE 46)."""
    rng = np.random.default_rng(46)
    cen = rng.random((24, 128)) * 140.0
    X = cen[rng.integers(0, 24, 4096)] + rng.standard_normal(
        (4096, 128)) * 30.0
    X = np.clip(np.rint(X), 0, 255).astype(np.float32)
    idx = build_ivf_index(X, KNNConfig(k=K, partitions=_WALK_P, nprobe=4))
    assert idx.onepass is not None and bool(idx.onepass)
    assert np.array_equal(idx.mu, np.rint(idx.mu))
    # what the rounding took off, for the finish to take off again
    np.testing.assert_array_equal(
        np.asarray(idx.mean_frac),
        X.astype(np.float64).mean(axis=0).astype(np.float32) - idx.mu)
    return _worn(idx), X


def _walk_queries(idx, X, rows: int, skewed: bool):
    """``rows`` centred query rows of the corpus with their ids, the last
    eighth padding (zero rows, id -1: what the engine pads a batch with);
    ``skewed``: every row a small step from ONE corpus row and none
    padding, so all of them probe the same lists."""
    rng = np.random.default_rng(rows)
    pick = rng.choice(len(X), size=rows, replace=False)
    q = X[pick] - idx.mu
    if skewed and idx.onepass is not None:  # whole numbers stay whole
        q = q[:1] + rng.integers(-2, 3, q.shape)
    elif skewed:
        q = q[:1] + 1e-3 * rng.standard_normal(q.shape).astype(np.float32)
    q_ids = pick.astype(np.int32)
    if not skewed:
        pad = max(1, rows // 8)
        q[-pad:], q_ids[-pad:] = 0.0, -1
    return q.astype(np.float32), q_ids


def _both_tiles(idx, q, q_ids, nprobe: int, exclude_self: bool):
    import jax

    from mpi_knn_tpu.ivf import search

    cfg = idx.cfg.replace(nprobe=nprobe, exclude_self=exclude_self)
    store = (idx.centroids, idx.centroid_sqs, idx.buckets, idx.bucket_ids,
             idx.bucket_sqs)
    row = jax.jit(lambda *a: search.ivf_query_tile(*a, None, cfg, nprobe))
    walk = jax.jit(lambda *a: search.bucket_major_tile(*a, cfg, nprobe))
    out = []
    for fn in (row, walk):
        d, i, counts = fn(q, q_ids, *store)
        out.append((np.asarray(d), np.asarray(i), np.asarray(
            search.probe_counts(len(q), nprobe, idx.bucket_cap,
                                *(c[None] for c in counts)))))
    _, probe = search.score_centroids(
        q, idx.centroids, idx.centroid_sqs, nprobe)
    return out[0], out[1], np.asarray(probe)


def _assert_same_answers(row, walk, q, idx):
    """Ids equal and distances within 1e-6 of the pair's scale (the unit
    ``mask_tile``'s zero threshold is in: float32's rounding of ``q_sq - 2
    q.x + x_sq`` follows the operands' size, and this backend's batched dot
    sums in an order that follows the candidates' count — the
    ``_batched_dot_bit_stable`` probe). Where two candidates sit closer
    than that, the two programs may order them differently: a row's ids
    may then differ in those slots alone."""
    (d0, i0, _), (d1, i1, _) = row, walk
    assert np.array_equal(np.isinf(d0), np.isinf(d1))
    tol = 1e-6 * ((q * q).sum(axis=1) + float(np.max(idx.bucket_sqs)))
    fin = np.isfinite(d0)
    gap = np.abs(np.where(fin, d0, 0.0) - np.where(fin, d1, 0.0))
    assert np.all(gap <= tol[:, None])
    assert np.array_equal(np.where(fin, i0, -1) < 0,
                          np.where(fin, i1, -1) < 0)
    for r, s in np.argwhere((i0 != i1) & fin):
        near = np.abs(d0[r] - d0[r, s]) <= 2 * tol[r]
        last = np.flatnonzero(fin[r])[-1]
        # a swap inside a near-tie, or a near-tie at the k-th place
        assert (set(i0[r][near]) == set(i1[r][near])
                or abs(d0[r, last] - d0[r, s]) <= 2 * tol[r]), (r, s)
    assert np.mean(i0 == i1) > 0.99


@pytest.mark.parametrize("exclude_self", [False, True])
@pytest.mark.parametrize("nprobe", [1, 4, _WALK_P])
@pytest.mark.parametrize("rows,skewed", [
    (8, False), (64, False), (256, False), (256, True)])
def test_bucket_major_matches_row_major(walk_index, rows, skewed, nprobe,
                                        exclude_self):
    from mpi_knn_tpu.ivf import search

    idx, X = walk_index
    q, q_ids = _walk_queries(idx, X, rows, skewed)
    row, walk, probe = _both_tiles(idx, q, q_ids, nprobe, exclude_self)
    _assert_same_answers(row, walk, q, idx)
    # the five counts keep their meaning; the sixth is the walk's own
    assert row[2][:5].tolist() == walk[2][:5].tolist()
    assert row[2][5] == 0
    per_list = np.bincount(probe.reshape(-1), minlength=_WALK_P)
    groups = -(-per_list // search.PROBE_GROUP)
    assert walk[2][5] == groups.sum()
    assert groups.sum() <= search.bucket_major_items(rows, nprobe, _WALK_P)
    if skewed:
        # every row probes the same lists: each is rows / 8 work items, no
        # probe truncated (an empty or dead slot answers +inf, not less)
        assert len(np.unique(probe)) == nprobe
        assert walk[2][5] == nprobe * (rows // search.PROBE_GROUP)
    if nprobe == _WALK_P and not skewed and not exclude_self:
        # every list probed: the serial backend's exact scan of the live
        # rows (the zero mask hides a query's own row in both)
        from mpi_knn_tpu import all_knn

        live = np.asarray(idx.bucket_ids)
        live = np.sort(live[live >= 0])
        want = all_knn(X[live], queries=q + idx.mu,
                       config=KNNConfig(k=K, backend="serial"))
        wi, wd = live[np.asarray(want.ids)], np.asarray(want.dists)
        hits = [len(set(a.tolist()) & set(b.tolist())) / K
                for a, b in zip(wi, walk[1])]
        assert np.mean(hits) >= 0.999, np.mean(hits)
        np.testing.assert_allclose(walk[0], wd, rtol=1e-5, atol=2e-3)


def _walk_sides(idx, q, q_ids, nprobe: int, exclude_self: bool):
    """The walk's kernel on both sides of the one-pass rule over one
    batch's work items: ``(slots under the flag TRUE, under FALSE, under
    no flag at all, work items walked)``, each (W, 8, 128) as the kernel
    left them."""
    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu.ivf import search
    from mpi_knn_tpu.ops.bucket_walk import bucket_walk

    cfg = idx.cfg.replace(nprobe=nprobe, exclude_self=exclude_self)

    @jax.jit
    def sides(q, q_ids):
        _, probe = search.score_centroids(
            q, idx.centroids, idx.centroid_sqs, nprobe)
        lists, rows, _, _, walked = search.invert_probe(probe, _WALK_P)
        at = jnp.maximum(rows, 0)

        def walk(flag):
            return bucket_walk(
                lists, walked, jnp.take(q, at, axis=0),
                jnp.take(q_ids, at, axis=0) if exclude_self else None,
                idx.buckets, idx.bucket_ids, idx.bucket_sqs, k=cfg.k,
                exclude_zero=cfg.exclude_zero, zero_eps=cfg.zero_eps,
                onepass=flag)

        return (walk(jnp.asarray(True)), walk(jnp.asarray(False)),
                walk(None), walked)

    one, six, plain, walked = sides(jnp.asarray(q), jnp.asarray(q_ids))
    return np.asarray(one), np.asarray(six), np.asarray(plain), int(walked)


@pytest.mark.parametrize("exclude_self", [False, True])
@pytest.mark.parametrize("nprobe", [1, 4, _WALK_P])
@pytest.mark.parametrize("rows,skewed", [
    (8, False), (64, False), (256, False), (256, True)])
def test_the_walks_two_sides_name_the_same_slots(whole_walk_index, rows,
                                                 skewed, nprobe,
                                                 exclude_self):
    """Whole-number store, whole-number query rows: the one-pass side's
    slots are the six-pass side's in every lane — the -1s past a short or
    emptied list, the dead slots, the padding rows, the self and zero
    masks included — and the kernel with no branch names them too; the
    answers of the batch program are then the same bits, and its seventh
    count is its sixth."""
    import jax

    from mpi_knn_tpu.ivf import search
    from mpi_knn_tpu.ops.distance import bf16_exact

    idx, X = whole_walk_index
    q, q_ids = _walk_queries(idx, X, rows, skewed)
    assert bf16_exact(q) and bf16_exact(np.asarray(idx.buckets))
    one, six, plain, walked = _walk_sides(idx, q, q_ids, nprobe,
                                          exclude_self)
    assert walked > 0
    assert np.array_equal(one[:walked], six[:walked])
    assert np.array_equal(six[:walked], plain[:walked])
    if nprobe == _WALK_P:  # the emptied list is among them: all -1
        assert (one[:walked, :, :K] == -1).all(axis=(1, 2)).any()
    cfg = idx.cfg.replace(nprobe=nprobe, exclude_self=exclude_self)
    store = (idx.centroids, idx.centroid_sqs, idx.buckets, idx.bucket_ids,
             idx.bucket_sqs)
    out = [jax.jit(lambda *a, f=fact: search.bucket_major_tile(
        *a, cfg, nprobe, f))(q, q_ids, *store)
        for fact in (idx.onepass, None)]
    for a, b in zip(out[0][:2], out[1][:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(out[0][2][4]) == int(out[0][2][3]) == walked
    assert int(out[1][2][4]) == 0 and int(out[1][2][3]) == walked


@pytest.mark.parametrize("exclude_self", [False, True])
@pytest.mark.parametrize("rows,nprobe", [(8, 1), (64, 4), (256, _WALK_P)])
def test_fractional_query_rows_take_the_six_pass_side(whole_walk_index,
                                                      rows, nprobe,
                                                      exclude_self):
    """A qualifying store, query rows that are no bf16 numbers: the flag
    is false on the device, no work item counts as one pass, and the
    answer is the row-major program's."""
    idx, X = whole_walk_index
    q, q_ids = _walk_queries(idx, X, rows, False)
    q = q + np.float32(0.3)
    cfg = idx.cfg.replace(nprobe=nprobe, exclude_self=exclude_self)
    import jax

    from mpi_knn_tpu.ivf import search

    store = (idx.centroids, idx.centroid_sqs, idx.buckets, idx.bucket_ids,
             idx.bucket_sqs)
    d, i, counts = jax.jit(lambda *a: search.bucket_major_tile(
        *a, cfg, nprobe, idx.onepass))(q, q_ids, *store)
    assert int(counts[4]) == 0 < int(counts[3])
    row, _, _ = _both_tiles(idx, q, q_ids, nprobe, exclude_self)
    _assert_same_answers(row, (np.asarray(d), np.asarray(i), None), q, idx)


@pytest.mark.parametrize("exclude_self", [False, True])
@pytest.mark.parametrize("rows,nprobe", [(64, 1), (256, 4)])
def test_the_finish_reads_the_operands_the_unrounded_mean_left(
        whole_walk_index, rows, nprobe, exclude_self, monkeypatch):
    """The rounded mean serves the KEYS. With ``mean_frac`` the finish
    names the same ids at the same distances (to float32's rounding: L2
    does not see the translation) — from fractional operands, as before
    the rule: a finish on operands rounded to bfloat16's precision (the
    clustered cell's control, ``benchmark/serve_launcher_ivf.py``) then
    misses the distances by parts in a thousand, where on the store's
    whole numbers alone it would return them to the bit and the control
    could fail nothing."""
    import jax

    from mpi_knn_tpu.ivf import search

    idx, X = whole_walk_index
    q, q_ids = _walk_queries(idx, X, rows, False)
    cfg = idx.cfg.replace(nprobe=nprobe, exclude_self=exclude_self)
    store = (idx.centroids, idx.centroid_sqs, idx.buckets, idx.bucket_ids,
             idx.bucket_sqs)

    def answers(frac):
        d, i, _ = jax.jit(lambda *a: search.bucket_major_tile(
            *a, cfg, nprobe, idx.onepass, frac))(q, q_ids, *store)
        return np.asarray(d), np.asarray(i)

    (d_w, i_w), (d_f, i_f) = answers(None), answers(idx.mean_frac)
    np.testing.assert_array_equal(i_f, i_w)
    found = np.isfinite(d_w)
    assert found.any() and np.array_equal(np.isfinite(d_f), found)
    np.testing.assert_allclose(d_f[found], d_w[found], rtol=2e-6)

    exact = search.rerank_exact_topk

    def rounded(q_x, q_ids, q_sq, rows, *rest, **kw):
        def bf16(x):
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7)
        return exact(bf16(q_x), q_ids, q_sq, bf16(rows), *rest, **kw)

    monkeypatch.setattr(search, "rerank_exact_topk", rounded)
    (r_w, _), (r_f, _) = answers(None), answers(idx.mean_frac)
    np.testing.assert_array_equal(r_w, d_w)  # whole numbers: nothing lost
    err = np.abs(r_f[found] - d_f[found]) / d_f[found]
    assert err.max() > 1e-4, err.max()


def test_the_fact_counts_the_one_pass_kernels_vmem():
    """A store whose plain kernel just fits the walk's share of VMEM and
    whose two-dot kernel, with its bfloat16 copies, would not: answered
    bucket-major, granted no fact (and so compiled without the branch)."""
    import jax.numpy as jnp

    from mpi_knn_tpu.ivf.index import store_onepass
    from mpi_knn_tpu.ivf.search import (
        _WALK_VMEM_BYTES,
        PROBE_GROUP,
        bucket_major_engages,
    )
    from mpi_knn_tpu.ops.bucket_walk import bucket_walk_vmem_bytes

    cfg = KNNConfig(k=K, partitions=1, nprobe=1)
    for cap, fact in ((46000, True), (56656, False)):
        plain = bucket_walk_vmem_bytes(PROBE_GROUP, cap, 128)
        both = bucket_walk_vmem_bytes(PROBE_GROUP, cap, 128, onepass=True)
        assert plain <= _WALK_VMEM_BYTES
        assert (both <= _WALK_VMEM_BYTES) == fact
        assert bucket_major_engages(8, 1, 1, cap, 128)
        assert bucket_major_engages(8, 1, 1, cap, 128, onepass=True) == fact
        got = store_onepass(cfg, jnp.ones((1, cap, 128), jnp.float32), None)
        assert (got is not None and bool(got)) == fact


def test_a_fractional_store_compiles_no_branch(walk_index, whole_walk_index):
    """The fact decides the program: a fractional store's batch program
    holds the walk's one dot and takes no flag; a qualifying store's holds
    one dot more and one scalar argument more. And a fractional corpus is
    centred by the mean it always was."""
    from mpi_knn_tpu.serve.engine import expected_args, lower_bucket

    texts = {}
    for name, (idx, X) in (("fractional", walk_index),
                           ("whole", whole_walk_index)):
        cfg = idx.cfg.replace(query_bucket=64)
        texts[name] = lower_bucket(idx, cfg, 64)[0].as_text()
        args = expected_args(idx, cfg, 64)
        assert (((), "bool") in args) == (name == "whole")
        assert (((128,), "float32") in args) == (name == "whole")
    dots = {n: t.count("stablehlo.dot_general") for n, t in texts.items()}
    assert dots["whole"] == dots["fractional"] + 1, dots
    assert "bf16" in texts["whole"] and "bf16" not in texts["fractional"]
    idx, X = walk_index
    np.testing.assert_array_equal(idx.mu, X.astype(np.float64).mean(axis=0))
    live = np.asarray(idx.bucket_ids) >= 0
    np.testing.assert_array_equal(
        np.asarray(idx.buckets)[live],
        (X[np.asarray(idx.bucket_ids)[live]] - idx.mu).astype(np.float32))


def test_bucket_major_engages_by_shapes_alone():
    from mpi_knn_tpu.ivf.search import (
        PROBE_GROUP,
        bucket_major_engages,
        bucket_major_items,
        ivf_query_shapes,
    )

    cell = (1024, 16, 4096, 4728, 128)  # serve-bigann10m-ivf-bulk
    assert bucket_major_engages(*cell)
    assert bucket_major_engages(8, 1, 16, 672, 128)
    for dtype in ("int8", "int4", "bfloat16"):
        assert not bucket_major_engages(*cell, dtype=dtype)
    assert not bucket_major_engages(*cell, precision_policy="mixed")
    assert not bucket_major_engages(1024, 16, 4096, 4728, 100)  # off lanes
    assert not bucket_major_engages(1024, 16, 4096, 4730, 128)  # no block
    assert not bucket_major_engages(1024, 16, 64, 1 << 17, 128)  # VMEM
    assert ivf_query_shapes(  # k past the kernel's 128 lanes: row-major
        KNNConfig(k=200, partitions=4096, nprobe=16, query_tile=1024),
        16, 4728, 128, 1024) == (16, 1024)
    # W: a list probed by n rows is ceil(n / G) items, whatever the skew
    assert bucket_major_items(1024, 16, 4096) == 4096 + 16384 // PROBE_GROUP
    assert bucket_major_items(8, 1, 4096) == 8 + 1
    # the cell's batch is ONE query tile; the row-major tile stays 16 rows
    cfg = KNNConfig(k=K, partitions=4096, nprobe=16, query_tile=1024)
    assert ivf_query_shapes(cfg, 16, 4728, 128, 1024) == (1024, 1024)
    assert ivf_query_shapes(
        cfg.replace(precision_policy="mixed"), 16, 4728, 128, 1024
    ) == (16, 1024)
    with pytest.raises(ValueError, match="max_tile_elems"):
        ivf_query_shapes(cfg.replace(max_tile_elems=1 << 12), 16, 4728,
                         128, 1024)


@pytest.mark.parametrize("path,policy,store,shift", [
    ("bucket_major", "exact", "fractional", 0.0),
    ("row_major", "mixed", "fractional", 0.0),
    ("bucket_major", "exact", "whole", 0.0),
    ("bucket_major", "exact", "whole", 0.25),  # fractional query rows
    ("row_major", "mixed", "whole", 0.0),
])
def test_served_batch_says_which_probe_answered(walk_index, whole_walk_index,
                                                path, policy, store, shift,
                                                monkeypatch):
    from mpi_knn_tpu.obs import metrics as obs_metrics
    from mpi_knn_tpu.ivf.search import PROBE_GROUP, bucket_major_items
    from mpi_knn_tpu.serve import ServeSession
    from mpi_knn_tpu.serve.index import onepass_holds

    idx, X = walk_index if store == "fractional" else whole_walk_index
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(obs_metrics, "_default_registry", reg)
    # the gauges are stamped where an executable is built: build it here
    idx._cache.clear()
    sess = ServeSession(idx, config=idx.cfg.replace(
        precision_policy=policy, query_bucket=64))
    (out,) = list(sess.stream([X[:64] + np.float32(shift)]))
    probed = np.asarray(out.ivf_probe)
    # in one pass: every work item of the batch, or none
    onepass = store == "whole" and not shift and path == "bucket_major"
    assert probed[6] == (probed[5] if onepass else 0)
    text = reg.to_prometheus().splitlines()
    assert f"ivf_probe_groups_onepass_total {float(probed[6])}" in text
    assert f"ivf_index_onepass {float(store == 'whole')}" in text
    assert onepass_holds(idx) == (store == "whole")
    assert probed.shape == (7,) and probed[0] == 64 * 4
    assert f'ivf_probe_batches_total{{path="{path}"}} 1.0' in text
    assert not any(ln.startswith("ivf_probe_batches_total{")
                   and path not in ln for ln in text)
    assert f"ivf_probe_groups_total {float(probed[5])}" in text
    if path == "row_major":
        assert probed[5] == 0
    else:
        assert probed[3] <= probed[5] <= bucket_major_items(64, 4, _WALK_P)
        assert probed[5] * PROBE_GROUP >= probed[0]


@pytest.mark.parametrize("crowded", [False, True])
def test_bucket_walk_selection_against_a_sort(crowded):
    """The walk's kernel alone (interpreted) against a stable sort of the
    masked distances: two lists of 520 slots (five vreg groups, the last
    of 8 slots), dead slots, a tie, a list with fewer than k live slots.
    ``crowded``: a query's five nearest rows sit in ONE lane (slots 5,
    133, 261, 389, 517), descending by slot: what a selection that keeps
    a few entries a lane would lose."""
    import jax.numpy as jnp

    from mpi_knn_tpu.ops.bucket_walk import bucket_walk

    rng = np.random.default_rng(5)
    lists, cap, d, group, k = 2, 520, 128, 8, 10
    x = rng.integers(-40, 40, size=(lists, cap, d)).astype(np.float32)
    q = rng.integers(-40, 40, size=(3, group, d)).astype(np.float32)
    if crowded:
        for n, slot in enumerate((517, 389, 261, 133, 5)):
            x[0, slot] = q[0, 0] + (n + 1)  # nearer than anything random
    x[0, 40] = x[0, 41]  # a tie: the earlier slot first
    ids = np.arange(lists * cap, dtype=np.int32).reshape(lists, cap)
    ids[0, ::7] = -1
    ids[1, 6:] = -1  # six live slots: fewer than k
    sqs = (x * x).sum(-1)
    items = np.array([0, 0, 1, 1], np.int32)  # the fourth: past `walked`
    rows = np.concatenate([q, q[:1]])
    got = np.asarray(bucket_walk(
        jnp.asarray(items), jnp.int32(3), jnp.asarray(rows), None,
        jnp.asarray(x), jnp.asarray(ids), jnp.asarray(sqs), k=k,
        exclude_zero=True, zero_eps=0.0))[:3, :, :k]
    for w in range(3):
        p = items[w]
        dist = np.maximum(
            (rows[w] ** 2).sum(-1)[:, None] - 2.0 * rows[w] @ x[p].T
            + sqs[p][None, :], 0.0)
        dist[:, ids[p] < 0] = np.inf
        want = np.argsort(dist, axis=1, kind="stable")[:, :k]
        finite = np.take_along_axis(dist, want, axis=1) < np.inf
        assert finite.sum() == (group * k if p == 0 else group * 6)
        assert np.array_equal(got[w][finite], want[finite]), w
        assert np.all(got[w][~finite] == -1)  # no slot named twice
