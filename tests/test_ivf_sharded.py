"""The SHARDED clustered (IVF) index — the bucket store distributed over
the ring mesh with the routed all-to-all candidate exchange
(``mpi_knn_tpu.ivf.sharded``, ISSUE 8 / DESIGN.md ladder rung 5).

The gates:

- recall parity with the single-device clustered index at equal nprobe on
  CPU meshes P ∈ {1, 2, 4} — BIT-identical at every shard count when the
  tile shapes match (every per-query dot shape is shard-count-
  independent), which is the property that makes the shard layout a pure
  deployment decision;
- ``nprobe == partitions`` degenerates to the exact full scan: value
  parity and full recall vs the dense ring scan of the same corpus;
- one saved ``.npz`` serves on ANY shard count (the layout is derived,
  never stored): a 4-shard build saves through its single-device view and
  reloads bit-compatibly on 1 and 2 shards;
- serving through the bucketed AOT cache issues ZERO steady-state
  compiles across all shards and is bit-identical to the one-shot search;
- the probe-cap overflow path DROPS (and counts) probes, never returns
  wrong answers;
- the resilience ladder walks the sharded path: the nprobe/2 rung sheds
  probed bytes AND exchange bytes, at the index's own recall bar (its
  lowered program re-lints against the smaller per-shard budget — the
  ladder-nprobe cell in the default lint matrix);
- lint rule R4's sharded-exchange accounting catches its injected
  counterexamples (an unrouted full-bucket broadcast, an over-budget
  per-shard gather, a partial replica group, the exchange optimized
  away) and the default ivf-sharded cells are clean;
- the ISSUE 8 ACCEPTANCE bound: on a 4-device CPU mesh, SIFT-shaped 32k
  at the auto-tuned nprobe reaches measured recall@10 ≥ 0.95, the
  lint-asserted per-shard probed bytes stay < 25 % of one shard's
  resident slice, recall parity with the single-device index holds at
  equal nprobe, and serving across all shards is zero-steady-state-
  compile (jax.monitoring-counted).
"""

import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, query_knn
from mpi_knn_tpu.ivf import (
    build_ivf_index,
    load_ivf_index,
    save_ivf_index,
    search_ivf,
    search_ivf_sharded,
    shard_ivf_index,
    unshard_ivf_index,
)
from tests.oracle import oracle_all_knn, recall_against_oracle

K = 10
SHARD_COUNTS = (1, 2, 4)


def _clustered(rng, m=1024, d=32, centers=16, spread=0.25):
    cents = rng.standard_normal((centers, d)).astype(np.float32) * 4
    assign = rng.integers(0, centers, size=m)
    return (
        cents[assign] + rng.standard_normal((m, d)).astype(np.float32)
        * spread * 4
    ).astype(np.float32)


@pytest.fixture
def compile_counter():
    from mpi_knn_tpu.obs.metrics import watch_compiles

    with watch_compiles() as counts:
        yield counts


# ---------------------------------------------------------------------------
# parity with the single-device index across shard counts


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_parity_with_single_device_at_equal_nprobe(rng, shards):
    """The routed exchange reorders WHERE candidates come from, never
    WHICH candidates a query sees or the shape of any dot: at a common
    q_tile the sharded search is bit-identical to the single-device one
    at every shard count (P=1 is the trivially-identical base case)."""
    X = _clustered(rng)
    idx = build_ivf_index(
        X, KNNConfig(k=K, partitions=16, nprobe=4, query_tile=8)
    )
    Q = X[:64]
    qids = np.arange(64, dtype=np.int32)
    d0, i0 = search_ivf(idx, Q, query_ids=qids)
    sidx = shard_ivf_index(idx, shards=shards)
    d, i, stats = search_ivf_sharded(sidx, Q, query_ids=qids)
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)
    # exchange stats shape and sanity: nothing dropped at the safe cap,
    # every issued route was served by some shard
    assert stats.shape == (shards, 3)
    assert stats[:, 1].sum() == 0
    assert stats[:, 0].sum() == stats[:, 2].sum() > 0


def test_recall_parity_vs_oracle_across_shard_counts(rng):
    """Equal-nprobe recall vs the f64 oracle is identical at every shard
    count — the pruning decision (stage-1 routing) is replicated math,
    so sharding can never silently spend recall."""
    X = _clustered(rng, m=2048, d=48, centers=24)
    idx = build_ivf_index(X, KNNConfig(k=K, partitions=32, query_tile=8))
    sample = np.arange(0, 2048, 8)
    want_d, want_i = oracle_all_knn(X, k=K + 5, queries=X[sample],
                                    exclude_self=False)
    for r, s in enumerate(sample):
        want_d[r][want_i[r] == s] = np.inf
    order = np.argsort(want_d, axis=1, kind="stable")
    want_d = np.take_along_axis(want_d, order, axis=1)
    want_i = np.take_along_axis(want_i, order, axis=1)

    _, i0 = search_ivf(idx, X[sample], query_ids=sample.astype(np.int32))
    rec0 = recall_against_oracle(i0, want_d, want_i, K)
    assert rec0 >= idx.cfg.recall_target
    for shards in SHARD_COUNTS:
        sidx = shard_ivf_index(idx, shards=shards)
        _, i_s, _ = search_ivf_sharded(
            sidx, X[sample], query_ids=sample.astype(np.int32)
        )
        rec = recall_against_oracle(i_s, want_d, want_i, K)
        assert rec == rec0, (shards, rec, rec0)


def test_nprobe_equals_partitions_matches_dense_ring_scan(rng):
    """The degenerate full-probe case IS the exact scan: value parity and
    full recall vs the dense ring backend over the same corpus."""
    from mpi_knn_tpu import all_knn
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    X = _clustered(rng, m=512, d=32, centers=8)
    idx = build_ivf_index(
        X, KNNConfig(k=K, partitions=8, nprobe=8, query_tile=8)
    )
    sidx = shard_ivf_index(idx, shards=4)
    sample = np.arange(0, 512, 4)
    gd, gi, _ = search_ivf_sharded(
        sidx, X[sample], query_ids=sample.astype(np.int32)
    )
    want = all_knn(
        X, queries=X[sample], query_ids=sample,
        config=KNNConfig(k=K, backend="ring", query_tile=64,
                         corpus_tile=64),
        mesh=make_ring_mesh(4),
    )
    wd, wi = np.asarray(want.dists), np.asarray(want.ids)
    # value parity: the two programs sum the same products in different
    # tile orders (ring rotation vs whole-bucket rerank), so the bound is
    # fp accumulation noise, not exact bits
    np.testing.assert_allclose(gd, wd, rtol=2e-5, atol=1e-3)
    rec = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / K for a, b in zip(gi, wi)
    ])
    assert rec >= 0.999, rec


# ---------------------------------------------------------------------------
# save/load: the shard layout is derived, never stored


def test_sharded_save_loads_on_any_shard_count(rng, tmp_path):
    """A 4-shard build saves through its single-device view; the SAME
    artifact reloads and answers bit-identically unsharded and on 1 and
    2 shards — the property that makes re-sharding a deploy-time
    decision instead of a rebuild."""
    X = _clustered(rng, m=512, d=24, centers=8)
    sidx4 = build_ivf_index(
        X, KNNConfig(k=5, partitions=8, nprobe=3, query_tile=8,
                     ivf_shards=4)
    )
    assert sidx4.backend == "ivf-sharded" and sidx4.shards == 4
    Q = X[::16]
    d4, i4, _ = search_ivf_sharded(sidx4, Q)

    path = save_ivf_index(sidx4, str(tmp_path / "sharded"))
    loaded = load_ivf_index(path)
    # the saved artifact is a plain single-device index: no layout inside
    assert loaded.cfg.ivf_shards is None
    assert loaded.cfg.ivf_route_cap is None
    dl, il = search_ivf(loaded, Q)
    np.testing.assert_array_equal(il, i4)
    np.testing.assert_array_equal(dl, d4)

    for shards in (1, 2):
        re_sharded = shard_ivf_index(loaded, shards=shards)
        d, i, _ = search_ivf_sharded(re_sharded, Q)
        np.testing.assert_array_equal(i, i4)
        np.testing.assert_array_equal(d, d4)

    # unshard_ivf_index strips the derived padding clusters exactly
    plain = unshard_ivf_index(sidx4)
    assert plain.buckets.shape[0] == sidx4.partitions
    np.testing.assert_array_equal(
        np.asarray(plain.bucket_ids), np.asarray(loaded.bucket_ids)
    )


def test_uneven_partition_split_pads_with_unreachable_clusters(rng):
    """partitions not divisible by shards: the last shard carries derived
    padding clusters (id −1 rows) that no route can reach — answers stay
    identical to the single-device index."""
    X = _clustered(rng, m=600, d=16, centers=10)
    idx = build_ivf_index(
        X, KNNConfig(k=5, partitions=10, nprobe=3, query_tile=8)
    )
    sidx = shard_ivf_index(idx, shards=4)  # ceil(10/4)=3 -> 12 slots
    assert sidx.per_shard == 3
    assert sidx.buckets.shape[0] == 12
    assert (np.asarray(sidx.bucket_ids)[10:] == -1).all()
    d0, i0 = search_ivf(idx, X[::8])
    d, i, _ = search_ivf_sharded(sidx, X[::8])
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)


# ---------------------------------------------------------------------------
# serving: zero steady-state compiles, exchange observability


def test_serve_zero_steady_state_compiles_and_bit_parity(
    rng, compile_counter
):
    from mpi_knn_tpu.serve import ServeSession

    X = _clustered(rng, m=768, d=24, centers=8)
    idx = build_ivf_index(
        X, KNNConfig(k=6, partitions=8, nprobe=2, query_tile=8,
                     query_bucket=32)
    )
    sidx = shard_ivf_index(idx, shards=4)
    sess = ServeSession(sidx)
    sess.warm([32, 64])
    # one full submit+drain cycle per bucket: executables AND the tiny
    # host-visible glue ops cached (the test_serve.py warm convention)
    for n in (32, 64):
        sess.submit(X[:n])
    sess.drain()
    sess.reset_stats()  # exchange window restarts with the batches below
    compile_counter.clear()
    batches = [X[:20], X[20:52], X[52:115]]
    outs = list(sess.stream(batches))
    assert compile_counter == [], (
        f"steady-state sharded serving compiled {len(compile_counter)} "
        "program(s)"
    )
    # bit-identical to the one-shot sharded search, batch by batch
    for q, o in zip(batches, outs):
        d1, i1, _ = search_ivf_sharded(sidx, q)
        np.testing.assert_array_equal(o.ids, i1)
        np.testing.assert_array_equal(o.dists, d1)
    # ... and to query_knn through the same engine
    res = query_knn(X[:20], sidx)
    np.testing.assert_array_equal(res.ids, outs[0].ids)

    # the candidate-exchange story: per-batch stats surface on the
    # BatchResult, the session accumulates them, nothing dropped at the
    # safe cap
    per_batch = [o.exchange for o in outs]
    assert all(e is not None and e.shape == (4, 3) for e in per_batch)
    routed = sum(int(e[:, 0].sum()) for e in per_batch)
    assert sess.exchange["shards"] == 4
    assert sess.exchange["routed_total"] == routed > 0
    assert sess.exchange["dropped_total"] == 0
    assert sess.exchange["exchange_bytes_total"] > 0
    assert len(sess.exchange["served_per_shard"]) == 4
    assert sum(sess.exchange["served_per_shard"]) == routed


def test_exchange_metrics_and_shard_span_attrs(rng, tmp_path):
    """The obs wiring: exchange counters land in the shared metrics
    registry, serve batch spans carry the shard topology, and every
    retired batch leaves an exchange event with the per-shard served
    load — the record a flight reader pairs with an OPEN batch span to
    attribute a hang to a shard."""
    from mpi_knn_tpu.obs import metrics as obs_metrics
    from mpi_knn_tpu.obs.spans import (
        FlightRecorder,
        read_flight,
        reconstruct_spans,
        set_recorder,
        validate_flight,
    )
    from mpi_knn_tpu.serve import ServeSession

    X = _clustered(rng, m=512, d=16, centers=8)
    idx = build_ivf_index(
        X, KNNConfig(k=5, partitions=8, nprobe=2, query_tile=8,
                     query_bucket=32)
    )
    sidx = shard_ivf_index(idx, shards=2)
    reg = obs_metrics.get_registry()
    base = reg.counter("serve_exchange_routed_total").value
    base_b = reg.counter("serve_exchange_bytes_total").value

    path = str(tmp_path / "flight.jsonl")
    set_recorder(FlightRecorder(path))
    try:
        sess = ServeSession(sidx)
        sess.warm([32])
        list(sess.stream([X[:32], X[32:64]]))
    finally:
        set_recorder(None)

    assert reg.counter("serve_exchange_routed_total").value > base
    assert reg.counter("serve_exchange_bytes_total").value > base_b

    records = read_flight(path)
    assert validate_flight(records) == []
    spans, events = reconstruct_spans(records)
    batch_spans = [s for s in spans if s["name"] == "batch"]
    assert len(batch_spans) == 2
    for s in batch_spans:
        assert s["attrs"]["shards"] == 2  # hang -> shard attribution
    exch = [e for e in events if e["name"] == "exchange"]
    assert len(exch) == 2
    for e in exch:
        assert len(e["attrs"]["served_per_shard"]) == 2
        assert e["attrs"]["dropped"] == 0


def test_route_cap_overflow_drops_are_counted_never_wrong(rng):
    """A route cap below the worst-case routing skew DROPS overflow
    probes (graceful recall loss, counted per shard) — the answers that
    do come back are still exact over the candidates that were routed:
    valid ids, ascending finite distances, no fabricated rows."""
    X = _clustered(rng, m=512, d=16, centers=4, spread=0.05)
    idx = build_ivf_index(
        X, KNNConfig(k=5, partitions=8, nprobe=4, query_tile=8)
    )
    sidx = shard_ivf_index(idx, shards=4, route_cap=2)
    assert sidx.cfg.ivf_route_cap == 2
    d, i, stats = search_ivf_sharded(sidx, X[:64])
    dropped = int(stats[:, 1].sum())
    assert dropped > 0, "cap 2 under 4-probe routing skew must drop"
    assert int(stats[:, 0].sum()) + dropped == 64 * 4  # every route told
    # never wrong answers: returned ids are real corpus rows with exact
    # distances (a dropped probe can only REMOVE candidates)
    assert np.isfinite(d[i >= 0]).all()
    d_safe, i_safe, stats_safe = search_ivf_sharded(
        shard_ivf_index(idx, shards=4), X[:64]
    )
    assert int(stats_safe[:, 1].sum()) == 0
    # dropping probes can only REMOVE candidates, so the capped k-th
    # distance is never better than the safe one, row by row
    assert (d >= d_safe - 1e-6).all()
    # drop priority is probe-rank-major: a query keeps its rank-0 probe
    # unless rank-0 demand ALONE exceeds the cap at that owner. At
    # cap = q_tile the rank-0 demand always fits, so no row goes fully
    # blank even while later-ranked probes still drop — under query-major
    # ordering the same cap would blank later queries (the first two
    # queries alone could spend all 8 slots on their 4 probes each)
    d8, i8, stats8 = search_ivf_sharded(
        shard_ivf_index(idx, shards=4, route_cap=8), X[:64]
    )
    assert int(stats8[:, 1].sum()) > 0  # rank>0 probes still overflow
    assert (i8 >= 0).any(axis=1).all(), "a query lost ALL probes at cap 8"


def test_total_starvation_is_counted_loss_not_poison(rng):
    """route_cap below even the rank-0 demand starves some queries of
    every probe: their rows retire all-inf. Under a resilience policy
    that is the DOCUMENTED graceful recall loss (dropped counted per
    shard) — it must NOT trip the NaN/all-inf poison sentinel and kill
    the batch (review regression: a skewed production session with an
    explicit --route-cap died loudly instead of degrading)."""
    from mpi_knn_tpu.resilience import ResiliencePolicy
    from mpi_knn_tpu.serve import ServeSession

    # one tight blob: every query's rank-0 probe names the same owner,
    # so cap=1 < q_tile guarantees some fully-starved rows
    X = (rng.standard_normal((256, 16)) * 0.01).astype(np.float32) + 3.0
    idx = build_ivf_index(
        X, KNNConfig(k=4, partitions=4, nprobe=1, query_tile=16,
                     query_bucket=16, dispatch_depth=1)
    )
    sidx = shard_ivf_index(idx, shards=2, route_cap=1)
    d, i, stats = search_ivf_sharded(sidx, X[:16])
    assert int(stats[:, 1].sum()) > 0
    assert (i < 0).all(axis=1).any(), "expected fully-starved rows"
    sess = ServeSession(sidx, resilience=ResiliencePolicy())
    res = sess.submit(X[:16]) + sess.drain()  # must NOT raise
    assert np.isinf(res[0].dists).all(axis=1).any()
    assert res[0].exchange[:, 1].sum() > 0  # the loss is counted


# ---------------------------------------------------------------------------
# the resilience ladder on the sharded path


def test_ladder_walk_sharded_nprobe_rung(rng):
    """Deadline breach on a sharded session sheds nprobe first — halving
    probed bytes AND (at the safe cap) the exchange buffers — at the
    index's own recall bar. The rung's lowered program re-lints against
    the smaller per-shard budget as the ladder-nprobe cell of the
    default matrix (test_default_sharded_lint_cells_are_clean)."""
    from mpi_knn_tpu.data.synthetic import make_blobs
    from mpi_knn_tpu.resilience import ResiliencePolicy, install_faults
    from mpi_knn_tpu.serve import ServeSession

    X, _ = make_blobs(256, 16, num_classes=4, seed=7)
    Q = X[:16] + rng.normal(scale=0.01, size=(16, 16)).astype(np.float32)
    Q = Q.astype(np.float32)
    k = 4
    odists, oids = oracle_all_knn(X, k, queries=Q)

    idx = build_ivf_index(
        X, KNNConfig(k=k, partitions=4, nprobe=4, query_tile=16,
                     query_bucket=16, dispatch_depth=1)
    )
    sidx = shard_ivf_index(idx, shards=2)
    pol = ResiliencePolicy(
        batch_deadline_s=0.01, degrade_after=1, max_retries=0
    )
    sess = ServeSession(sidx, resilience=pol)
    assert sess.ladder[1][0] == "nprobe/2"
    assert sess.ladder[1][1].nprobe == 2
    sess.warm([16])
    with install_faults({"serve-batch": ("slow", 0.02)}):
        b1 = sess.submit(Q)[0]  # full: nprobe=4 == partitions, exact
        b2 = sess.submit(Q)[0]  # degraded: nprobe=2

    assert b1.degraded is None and b2.degraded == "nprobe/2"
    assert recall_against_oracle(b1.ids, odists, oids, k) == 1.0
    assert recall_against_oracle(b2.ids, odists, oids, k) >= \
        sess.cfg.recall_target
    # both rungs exchanged candidates; the degraded rung routed fewer
    assert b1.exchange is not None and b2.exchange is not None
    assert b2.exchange[:, 0].sum() < b1.exchange[:, 0].sum()


# ---------------------------------------------------------------------------
# config validation and CLI surface


def test_config_and_layout_validation(rng):
    with pytest.raises(ValueError, match="ivf_shards"):
        KNNConfig(k=3, ivf_shards=2)  # shards without partitions
    with pytest.raises(ValueError, match="ivf_shards"):
        KNNConfig(k=3, partitions=4, ivf_shards=0)
    with pytest.raises(ValueError, match="ivf_route_cap"):
        KNNConfig(k=3, partitions=4, ivf_route_cap=8)  # cap w/o shards
    with pytest.raises(ValueError, match="ivf_route_cap"):
        KNNConfig(k=3, partitions=4, ivf_shards=2, ivf_route_cap=0)
    with pytest.raises(ValueError, match="ivf_shards"):
        from mpi_knn_tpu.ivf import build_sharded_ivf_index

        build_sharded_ivf_index(
            np.zeros((64, 8), np.float32), KNNConfig(k=3, partitions=4)
        )

    X = _clustered(rng, m=256, d=16)
    idx = build_ivf_index(X, KNNConfig(k=5, partitions=4, nprobe=2))
    import jax

    with pytest.raises(ValueError, match="device"):
        shard_ivf_index(idx, shards=len(jax.devices()) + 1)
    from mpi_knn_tpu.parallel.mesh import make_mesh2d

    with pytest.raises(ValueError, match="1-D ring mesh"):
        shard_ivf_index(idx, shards=4, mesh=make_mesh2d(2, 2))

    # the shard count is corpus-side: serving a 4-shard layout with a
    # 2-shard config would route to devices that do not hold the clusters
    sidx = shard_ivf_index(idx, shards=4)
    with pytest.raises(ValueError, match="corpus-side"):
        sidx.compatible_cfg(sidx.cfg.replace(ivf_shards=2))
    # route cap is query-side: override allowed, keys the bucket cache
    assert sidx.compatible_cfg(
        sidx.cfg.replace(ivf_route_cap=3)
    ).ivf_route_cap == 3


def test_cli_sharded_build_and_serve(tmp_path):
    """`mpi-knn build-index --backend ring` is real support now (the old
    exit-2 refusal lifted): the artifact is the single-device one, and
    `mpi-knn query --index-load ... --backend ring --devices N` serves it
    sharded; the knobs that only mean something sharded are refused
    loudly everywhere else."""
    from mpi_knn_tpu.ivf import cli as ivf_cli
    from mpi_knn_tpu.serve import cli as serve_cli

    path = str(tmp_path / "ring.npz")
    assert ivf_cli.main(
        ["--data", "synthetic:256x16c4", "--partitions", "4", "--k", "3",
         "--backend", "ring", "--out", path, "-q"]
    ) == 0
    # sharded serving of the loaded artifact
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--backend", "ring", "--devices", "2", "--synthetic", "16",
         "--batch", "8", "--bucket", "8", "-q"]
    ) == 0
    # ... with an explicit route cap
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--backend", "ring", "--devices", "2", "--route-cap", "4",
         "--synthetic", "16", "--batch", "8", "--bucket", "8", "-q"]
    ) == 0
    # refusals: exchange knobs outside the sharded path, ring-overlap
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--devices", "2", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--route-cap", "4", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--index-load", path,
         "--backend", "ring-overlap", "--synthetic", "8"]
    ) == 2
    assert serve_cli.main(
        ["--data", "synthetic:256x16c4", "--route-cap", "4",
         "--synthetic", "8"]
    ) == 2


# ---------------------------------------------------------------------------
# lint: R4 exchange-accounting counterexamples + the default cells


def _sharded_ctx(**meta):
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis.lowering import LintTarget

    meta.setdefault("q_tile", 8)
    meta.setdefault("c_tile", 64)
    meta.setdefault("acc_bytes", 4)
    meta.setdefault("shards", 4)
    meta.setdefault("expected_alltoalls", 4)
    return engine.LintContext(
        target=LintTarget("ivf-sharded", "l2", "float32"),
        cfg=KNNConfig(k=4, partitions=8, nprobe=2, ivf_shards=4),
        meta=meta,
    )


def _run_r4(texts, ctx):
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis import rules as rules_mod

    r4 = [r for r in rules_mod.RULES if r.name == "R4-collective"]
    findings, ran = engine.run_rules(texts, ctx, r4)
    assert ran == ["R4-collective"]
    return findings


def _lower_shard_body(body, shape=(8, 32)):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mpi_knn_tpu.analysis import lowering
    from mpi_knn_tpu.parallel.mesh import make_ring_mesh

    mesh = make_ring_mesh(4)
    axis = mesh.axis_names[0]
    fn = jax.jit(jax.shard_map(
        lambda x: body(x, axis), mesh=mesh,
        in_specs=P(axis), out_specs=P(axis),
    ))
    return lowering.hlo_texts(fn.lower(jnp.zeros(shape, jnp.float32)))


def test_r4_catches_unrouted_full_bucket_broadcast():
    """The re-centralization mistake the routing exists to prevent: a
    shard body that all-gathers the whole bucket store to every shard
    instead of exchanging routed candidates. Results would stay correct
    — memory and ICI bytes silently stop scaling with the mesh."""
    import jax

    def leaky(x, axis):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)[:8]

    findings = _run_r4(_lower_shard_body(leaky), _sharded_ctx())
    strays = [f for f in findings if f.details.get("op") == "all-gather"]
    assert strays, "unrouted full-bucket broadcast not flagged"
    assert "unrouted" in strays[0].message


def test_r4_catches_over_budget_per_shard_gather():
    """An all-to-all moving more than the declared per-tile exchange
    budget: the shard is shipping whole bucket stores, not the routed
    candidate set the probe table named."""
    import jax

    def exchange(x, axis):
        return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)

    texts = _lower_shard_body(exchange, shape=(64, 256))
    # generous budget: clean (count pinned to what the body contains)
    ok_ctx = _sharded_ctx(expected_alltoalls=1,
                          exchange_bytes_tile=10**9)
    assert not _run_r4(texts, ok_ctx)
    # the same program against the budget it actually violates
    bad_ctx = _sharded_ctx(expected_alltoalls=1, exchange_bytes_tile=64)
    findings = _run_r4(texts, bad_ctx)
    assert any("over-budget" in f.message for f in findings), (
        [f.message for f in findings]
    )
    # wrong collective COUNT is its own finding (a second exchange the
    # cost model never declared)
    miscount = _sharded_ctx(expected_alltoalls=4,
                            exchange_bytes_tile=10**9)
    findings = _run_r4(texts, miscount)
    assert any("expected exactly 4 all-to-alls" in f.message
               for f in findings)


def test_r4_catches_exchange_optimized_away_and_partial_groups():
    from mpi_knn_tpu.analysis.rules import alltoall_census
    from mpi_knn_tpu.utils.hlo_graph import parse_hlo

    # after_opt with ZERO all-to-alls: the exchange was optimized away
    no_exchange = """\
HloModule m, entry_computation_layout={(f32[8,32]{1,0})->f32[8,32]{1,0}}

ENTRY %main.1 (a.1: f32[8,32]) -> f32[8,32] {
  %a.1 = f32[8,32]{1,0} parameter(0)
  ROOT %r.1 = f32[8,32]{1,0} add(%a.1, %a.1)
}
"""
    findings = _run_r4({"after_opt": no_exchange}, _sharded_ctx())
    assert any("optimized away" in f.message for f in findings)

    # a partial replica group cannot reach every owner the routing names
    partial = """\
HloModule m, entry_computation_layout={(f32[8,32]{1,0})->f32[8,32]{1,0}}

ENTRY %main.1 (a.1: f32[8,32]) -> f32[8,32] {
  %a.1 = f32[8,32]{1,0} parameter(0)
  %x.1 = f32[8,32]{1,0} all-to-all(%a.1), channel_id=1, \
replica_groups={{0,1},{2,3}}, dimensions={0}
  ROOT %r.1 = f32[8,32]{1,0} add(%x.1, %x.1)
}
"""
    mod = parse_hlo(partial)
    census = alltoall_census(mod, 4)
    assert census["count"] == 1 and census["bad_groups"]
    findings = _run_r4(
        {"before_opt": partial},
        _sharded_ctx(expected_alltoalls=1, exchange_bytes_tile=10**9),
    )
    assert any("full-" in f.message and "ring" in f.message
               for f in findings)


def test_default_sharded_lint_cells_are_clean():
    """The positive criterion: every default ivf-sharded cell lowers
    through the production paths and passes all applicable rules — R4's
    exchange accounting and strict-R2's per-shard budget run on every
    one, R5 on the serve cells, and the ladder-nprobe cell re-certifies
    the degraded program against its own SMALLER budget."""
    from mpi_knn_tpu.analysis import engine, lowering

    targets = [
        t for t in lowering.default_targets()
        if t.backend == "ivf-sharded"
    ]
    plain = [t for t in targets if not t.quant and not t.mutate]
    assert len(plain) == 5, targets
    assert sorted(t.ladder for t in plain) == [
        "", "", "", "", "nprobe",
    ]
    # the sharded live-mutation cell (ISSUE 14): the donated GSPMD
    # scatter — R5's aliasing contract must survive the partitioner
    assert [t.mutate for t in targets if t.mutate] == ["upsert"]
    # plus the quantized-exchange cells (ISSUE 9: rows ride the
    # all-to-alls as int8 code lanes + a fifth scales collective)
    assert sorted((t.quant, t.serve) for t in targets if t.quant) == [
        ("int8", False), ("int8", True),
    ]
    for t in targets:
        res = engine.lint_target(t)
        assert res.skipped is None, (t.label, res.skipped)
        assert res.ok, (t.label, [f.message for f in res.findings])
        ran = set(res.rules_run)
        if t.mutate:
            assert "R5-donation" in ran
            assert "R4-collective" not in ran  # GSPMD scatter, no
            # exchange to account (rules.R4Collectives.applies)
            continue
        assert {"R2-memory", "R4-collective", "R6-ivf-probe"} <= ran
        if t.serve:
            assert "R5-donation" in ran


# ---------------------------------------------------------------------------
# ISSUE 8 acceptance: SIFT-shaped 32k on the 4-device CPU mesh


def test_sift32k_sharded_acceptance(compile_counter):
    """On a 4-device CPU mesh, SIFT-shaped 32k sharded IVF at the
    auto-tuned nprobe: measured recall@10 ≥ 0.95, the lint-asserted
    per-shard probed bytes < 25 % of one shard's resident slice, recall
    parity with the single-device index at equal nprobe, zero
    steady-state compiles through serve across all shards."""
    from mpi_knn_tpu.analysis import engine
    from mpi_knn_tpu.analysis.lowering import (
        LintTarget,
        _ivf_sharded_meta,
        hlo_texts,
    )
    from mpi_knn_tpu.data.synthetic import make_sift_like
    from mpi_knn_tpu.ivf.sharded import sharded_query_shapes
    from mpi_knn_tpu.serve import ServeSession
    from mpi_knn_tpu.serve.engine import lower_bucket

    X = make_sift_like(m=32768, d=128, seed=0)
    cfg = KNNConfig(k=K, partitions=64, kmeans_iters=10, query_bucket=256,
                    ivf_shards=4)
    assert cfg.recall_target == 0.95  # the DEFAULT target is the subject
    sidx = build_ivf_index(X, cfg)  # trains, auto-tunes, then shards
    assert sidx.backend == "ivf-sharded" and sidx.shards == 4

    # measured recall@10 vs the f64 oracle at the auto-tuned nprobe
    sample = np.linspace(0, 32767, num=128, dtype=np.int64)
    _, got, _ = search_ivf_sharded(
        sidx, X[sample], query_ids=sample.astype(np.int32)
    )
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
    assert rec >= 0.95, f"auto-tuned nprobe={sidx.nprobe}: recall {rec}"

    # recall parity with the single-device index at equal nprobe
    plain = unshard_ivf_index(sidx)
    _, got0 = search_ivf(plain, X[sample],
                         query_ids=sample.astype(np.int32))
    rec0 = recall_against_oracle(got0, want_d, order.astype(np.int32), K)
    assert rec == rec0, (rec, rec0)

    # the per-shard probed-bytes bound, from the lint meta over the REAL
    # lowered serve program: R2-strict certifies the program materializes
    # nothing beyond the declared per-shard working set, and the probed
    # bytes per query (the routing moves exactly nprobe buckets) stay
    # under a quarter of ONE shard's resident slice
    serve_cfg = sidx.compatible_cfg(sidx.cfg)
    lowered, q_pad, q_tile = lower_bucket(sidx, serve_cfg, 256)
    _, _, route_cap = sharded_query_shapes(
        serve_cfg, serve_cfg.nprobe, sidx.bucket_cap, sidx.dim, 256,
        sidx.shards,
    )
    meta = {
        **_ivf_sharded_meta(sidx, serve_cfg, q_tile, route_cap, q_pad, 256),
        "serve": True,
        "donated_params": sidx.layout.donate_argnums,
        "resident_bytes": sidx.nbytes_resident,
    }
    assert sidx.probe_bytes < 0.25 * sidx.shard_nbytes_resident, (
        f"probed {sidx.probe_bytes} B/query vs shard slice "
        f"{sidx.shard_nbytes_resident} B"
    )
    target = LintTarget("ivf-sharded", "l2", "float32", serve=True)
    ctx = engine.LintContext(target=target, cfg=serve_cfg, meta=meta)
    findings, ran = engine.run_rules(hlo_texts(lowered), ctx)
    assert {"R2-memory", "R4-collective", "R6-ivf-probe"} <= set(ran)
    assert not findings, [f.message for f in findings]

    # zero steady-state compiles through serve across all shards
    sess = ServeSession(sidx)
    sess.warm([256])
    sess.submit(X[:200])
    sess.drain()
    compile_counter.clear()
    outs = list(sess.stream([X[:256], X[256:512], X[512:700]]))
    assert compile_counter == [], (
        f"steady-state compiled {len(compile_counter)} program(s)"
    )
    assert sum(o.rows for o in outs) == 700
