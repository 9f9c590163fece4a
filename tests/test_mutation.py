"""Live index mutation (ISSUE 14): static-shape upsert/delete with
donated in-place bucket updates, freelist/tombstone semantics, the
background re-cluster/compact pass, format compatibility, and the
zero-steady-state-compile contract over sustained churn.

The acceptance pins live here:

- zero compiles across a sustained interleave of upserts, deletes, and
  queries at ragged sizes (``watch_compiles``-counted), including after
  a simulated restart against a warm persistent AOT cache;
- deleted ids are NEVER returned (tombstone mask), and post-churn
  recall@10 on the live set matches a fresh rebuild of the same rows;
- S=1 sharded mutation is bit-identical to unsharded;
- a mutated index round-trips one ``.npz`` bit-identically, legacy
  pre-mutation artifacts load with their padding derived as headroom,
  and a 4-shard build with tombstones reloads on 1 and 2 shards;
- the sustained upsert path beats rebuild-per-batch by ≥10× rows/s
  (measured in miniature here; the committed bench_ops baseline carries
  the real rows).
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from mpi_knn_tpu.config import KNNConfig  # noqa: E402
from mpi_knn_tpu.ivf import (  # noqa: E402
    build_ivf_index,
    load_ivf_index,
    save_ivf_index,
    shard_ivf_index,
)
from mpi_knn_tpu.ivf.mutate import (  # noqa: E402
    BucketOverflowError,
    Freelist,
    freelist_of,
    plan_delete,
    plan_upsert,
    should_compact,
)
from mpi_knn_tpu.ivf.search import search_ivf  # noqa: E402
from mpi_knn_tpu.obs.metrics import watch_compiles  # noqa: E402
from mpi_knn_tpu.serve import ServeSession, build_index  # noqa: E402
from mpi_knn_tpu.serve import mutate as sm  # noqa: E402
from mpi_knn_tpu.serve.engine import query_knn  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _blobs(rng, m=256, d=16, nc=8, scale=5.0):
    cents = rng.standard_normal((nc, d)).astype(np.float32) * scale
    assign = rng.integers(0, nc, m)
    X = (cents[assign] + rng.standard_normal((m, d))).astype(np.float32)
    return X, cents


def _ivf(X, **kw):
    base = dict(k=5, partitions=8, nprobe=4, query_tile=32,
                query_bucket=32, mutation_bucket=32, dispatch_depth=1,
                kmeans_iters=8, bucket_headroom=0.5)
    base.update(kw)
    return build_ivf_index(X, KNNConfig(**base))


# ---------------------------------------------------------------------------
# Freelist math


def test_freelist_derivation_and_determinism():
    ids = np.full((3, 8), -1, np.int32)
    ids[0, :5] = [10, 11, 12, 13, 14]
    ids[2, 0] = 99
    fl = Freelist(ids, 3)
    assert fl.live == 6
    assert fl.where(10) == (0, 0) and fl.where(99) == (2, 0)
    # lowest free slot first, deterministically
    assert fl.lowest_free(0)[0] == 5 and fl.lowest_free(1)[0] == 0
    assert fl.max_fill == 5 / 8
    assert fl.tombstones == 0


def test_freelist_headroom_reflects_build(rng):
    X, _ = _blobs(rng)
    idx = _ivf(X, bucket_headroom=0.5)
    fl = freelist_of(idx)
    assert fl.live == 256
    # headroom: the fullest bucket still has spare capacity
    assert fl.max_fill < 1.0
    idx0 = _ivf(X, bucket_headroom=0.0)
    assert idx0.bucket_cap < idx.bucket_cap


# ---------------------------------------------------------------------------
# Upsert / delete correctness


def test_upsert_then_query_finds_new_rows(rng):
    X, cents = _blobs(rng)
    idx = _ivf(X)
    new = (cents[3] + 0.01 * rng.standard_normal((8, 16))
           ).astype(np.float32)
    ids = np.arange(1000, 1008)
    st = sm.upsert_rows(idx, ids, new)
    assert st["upserted"] == 8 and st["live"] == 264
    d, i = search_ivf(idx, new, config=idx.cfg.replace(k=5))
    # every query's neighborhood is the upserted clump (exclude_zero
    # masks each row's own stored copy, so assert on the set)
    assert set(ids.tolist()) & set(i[:, 0].tolist())
    assert idx.live_rows == 264


def test_deleted_ids_are_never_returned(rng):
    X, cents = _blobs(rng)
    idx = _ivf(X)
    new = (cents[2] + 0.01 * rng.standard_normal((6, 16))
           ).astype(np.float32)
    ids = np.arange(2000, 2006)
    sm.upsert_rows(idx, ids, new)
    st = sm.delete_rows(idx, ids[:4])
    assert st["deleted"] == 4 and st["tombstones"] == 4
    d, i = search_ivf(idx, new, config=idx.cfg.replace(k=10))
    assert not set(ids[:4].tolist()) & set(i.ravel().tolist())
    # idempotent: deleting again (or unknown ids) is counted, not an error
    st = sm.delete_rows(idx, [2000, 2001, 777777])
    assert st["deleted"] == 0 and st["missing"] == 3


def test_upsert_existing_id_is_an_update(rng):
    X, cents = _blobs(rng)
    idx = _ivf(X)
    before = freelist_of(idx).live
    moved = (cents[7] + 0.01 * rng.standard_normal(16)
             ).astype(np.float32)[None]
    sm.upsert_rows(idx, [3], moved)
    assert freelist_of(idx).live == before  # update, not insert
    # query NEAR the moved row: exclude_zero is scale-relative, so the
    # probe offset must clear the zero-distance resolution at |x| ~ 20
    probe = moved + np.float32(0.1)
    d, i = search_ivf(idx, probe, config=idx.cfg.replace(k=3))
    assert 3 in i[0].tolist()
    # the old location must not answer for id 3's old row
    ids_np = np.asarray(idx.bucket_ids)
    assert (ids_np == 3).sum() == 1


def test_upsert_dedupes_chunk_keeping_last(rng):
    X, cents = _blobs(rng)
    idx = _ivf(X)
    r1 = (cents[0] + 0.01 * rng.standard_normal(16)).astype(np.float32)
    r2 = (cents[5] + 0.01 * rng.standard_normal(16)).astype(np.float32)
    sm.upsert_rows(idx, [9000, 9000], np.stack([r1, r2]))
    assert (np.asarray(idx.bucket_ids) == 9000).sum() == 1
    d, i = search_ivf(idx, (r2 + np.float32(0.1))[None],
                      config=idx.cfg.replace(k=3))
    assert 9000 in i[0].tolist()


def test_upsert_validation(rng):
    X, _ = _blobs(rng)
    idx = _ivf(X)
    with pytest.raises(ValueError, match="must be >= 0"):
        sm.upsert_rows(idx, [-1], np.zeros((1, 16), np.float32))
    with pytest.raises(ValueError, match="ids but"):
        sm.upsert_rows(idx, [1, 2], np.zeros((1, 16), np.float32))
    with pytest.raises(ValueError, match=r"\(n, dim"):
        sm.upsert_rows(idx, [1], np.zeros((1, 8), np.float32))


def test_refusals_on_immutable_layouts(rng):
    X, _ = _blobs(rng)
    pidx = build_index(X, KNNConfig(backend="ring-overlap", query_bucket=32))
    with pytest.raises(ValueError, match="cannot honor live mutation"):
        sm.upsert_rows(pidx, [1], np.zeros((1, 16), np.float32))
    with pytest.raises(ValueError, match="cannot honor live mutation"):
        sm.delete_rows(pidx, [1])
    sidx = build_index(X, KNNConfig(backend="serial", query_bucket=32))
    with pytest.raises(ValueError, match="no re-cluster pass"):
        sm.compact_index(sidx)


# ---------------------------------------------------------------------------
# Serial (dense) layout


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_serial_upsert_delete_roundtrip(rng, metric):
    """(Cosine: an upserted row's slot gets the row's inverse norm, the
    build's own state — left at the old zeros it would sit at distance 1
    from its own query.)"""
    X, _ = _blobs(rng, m=200)
    idx = build_index(X, KNNConfig(
        k=5, backend="serial", query_bucket=32, query_tile=32,
        corpus_tile=64, mutation_bucket=32, exclude_zero=False,
        bucket_headroom=0.5, metric=metric,
    ))
    assert idx.live_rows == 200
    new = rng.standard_normal((9, 16)).astype(np.float32)
    sm.upsert_rows(idx, np.arange(7000, 7009), new)
    assert idx.live_rows == 209
    r = query_knn(new, idx, idx.cfg)
    # exclude_zero off: each upserted row is its own nearest neighbor
    assert (r.ids[:, 0] == np.arange(7000, 7009)).all()
    sm.delete_rows(idx, np.arange(7000, 7005))
    r = query_knn(new[:5], idx, idx.cfg, k=10)
    assert not set(range(7000, 7005)) & set(r.ids.ravel().tolist())
    assert idx.live_rows == 204


def test_serial_inplace_update_needs_no_headroom(rng):
    """Regression (review finding): updating ids that are already live
    must consume NO free slots — a zero-headroom serial index absorbs
    pure updates in place, exactly as config.py promises."""
    X, _ = _blobs(rng, m=64)
    idx = build_index(X, KNNConfig(
        backend="serial", query_bucket=16, corpus_tile=64,
        bucket_headroom=0.0, mutation_bucket=16, exclude_zero=False,
    ))
    assert freelist_of(idx).free_count.sum() == 0  # full stack
    moved = (X[:4] + 0.5).astype(np.float32)
    st = sm.upsert_rows(idx, np.arange(4), moved)
    assert st["upserted"] == 4 and st["live"] == 64
    r = query_knn(moved, idx, idx.cfg, k=1)
    assert (r.ids[:, 0] == np.arange(4)).all()


def test_serial_overflow_is_loud(rng):
    X, _ = _blobs(rng, m=64)
    idx = build_index(X, KNNConfig(
        backend="serial", query_bucket=16, corpus_tile=64,
        bucket_headroom=0.0, mutation_bucket=16,
    ))
    free = int(freelist_of(idx).free_count.sum())
    with pytest.raises(BucketOverflowError, match="tile stack is full"):
        sm.upsert_rows(
            idx, np.arange(10**6, 10**6 + free + 1),
            rng.standard_normal((free + 1, 16)).astype(np.float32),
        )


# ---------------------------------------------------------------------------
# Zero steady-state compiles


def test_zero_compiles_under_sustained_ragged_churn(rng):
    X, cents = _blobs(rng, m=384)
    idx = _ivf(X)
    ses = ServeSession(idx)
    ses.warm([32])
    # warm-up round pays the mutation cells + one-time eager helpers
    ses.upsert(np.arange(5000, 5010),
               rng.standard_normal((10, 16)).astype(np.float32))
    ses.submit(rng.standard_normal((20, 16)).astype(np.float32))
    ses.drain()
    ses.delete(np.arange(5000, 5005))
    ses.reset_stats()  # the window under test starts after warm-up
    nid = 100000
    with watch_compiles() as counts:
        for n in (3, 17, 32, 1, 29, 8):
            # cluster-shaped churn rows: spread over the trained
            # partitions so sustained churn stays inside headroom (a
            # one-spot burst legitimately triggers compaction, which is
            # its own test below)
            ses.upsert(
                np.arange(nid, nid + n),
                (cents[rng.integers(0, 8, n)]
                 + rng.standard_normal((n, 16))).astype(np.float32),
            )
            ses.submit(rng.standard_normal(
                (max(1, n % 21), 16)).astype(np.float32))
            ses.delete(np.arange(nid, nid + max(1, n // 2)))
            nid += n
        ses.drain()
        assert counts == [], f"churn compiled {len(counts)} programs"
    st = ses.stats_snapshot()["mutation"]
    assert st["upserts"] == 90 and st["calls"] == 12


def test_zero_compiles_after_restart_with_warm_cache(rng, tmp_path):
    """The restart half of the acceptance: a FRESH index (same shapes)
    against a warm persistent AOT cache revives every mutation cell
    with zero XLA compiles and no fallback warnings."""
    import warnings

    from mpi_knn_tpu.serve import aotcache

    aotcache.reset_for_tests()
    aotcache.set_cache_dir(tmp_path / "aot")
    try:
        X, _ = _blobs(rng)
        a = _ivf(X)
        sm.upsert_rows(a, np.arange(1000, 1010),
                       rng.standard_normal((10, 16)).astype(np.float32))
        sm.delete_rows(a, [1000])
        sm.compact_index(a, reason="seed-cache")
        # "restart": a fresh index object; the in-process jit caches are
        # keyed on the jitted fn + avals, so assert on the LOUD-fallback
        # warning channel too — a miss would both warn and (in a real
        # fresh process) compile
        b = _ivf(X)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with watch_compiles() as counts:
                sm.upsert_rows(
                    b, np.arange(2000, 2010),
                    rng.standard_normal((10, 16)).astype(np.float32),
                )
                sm.delete_rows(b, [2000])
            assert counts == []
    finally:
        aotcache.reset_for_tests()


# ---------------------------------------------------------------------------
# Recall under churn vs fresh rebuild


def test_post_churn_recall_matches_fresh_rebuild(rng):
    from tests.oracle import recall_against_oracle

    X, cents = _blobs(rng, m=512)
    idx = _ivf(X)
    # churn: delete a third of the corpus, upsert replacements near the
    # same clusters, update a handful in place
    dead = np.arange(0, 512, 3)
    sm.delete_rows(idx, dead)
    repl = (cents[rng.integers(0, 8, 128)]
            + rng.standard_normal((128, 16))).astype(np.float32)
    rid = np.arange(10000, 10128)
    sm.upsert_rows(idx, rid, repl)
    # the live set, as arrays (centered frame is handled by the index)
    live_ids = freelist_of(idx).live_ids()
    rows_by_id = {int(i): X[i] for i in range(512) if i not in set(dead)}
    rows_by_id.update({int(i): r for i, r in zip(rid, repl)})
    live_rows = np.stack([rows_by_id[int(i)] for i in live_ids])

    # the maintained index: churn + the background re-cluster pass
    sm.compact_index(idx, reason="post-churn")
    # fresh rebuild of exactly the live rows (ids = positions there)
    fresh = build_ivf_index(live_rows, idx.cfg.replace(nprobe=4))
    Q = (cents[rng.integers(0, 8, 64)]
         + rng.standard_normal((64, 16))).astype(np.float32)
    k = 10
    _, got_mut = search_ivf(idx, Q, config=idx.cfg.replace(k=k, nprobe=4))
    _, got_fresh = search_ivf(fresh, Q,
                              config=fresh.cfg.replace(k=k, nprobe=4))
    # map both to the same id space (the live-row positions)
    id_of_pos = {p: int(i) for p, i in enumerate(live_ids)}
    got_fresh_ids = np.vectorize(
        lambda p: id_of_pos.get(int(p), -1))(got_fresh)
    # oracle on the live set in f64
    X64 = live_rows.astype(np.float64)
    Q64 = Q.astype(np.float64)
    od = ((Q64**2).sum(1)[:, None] + (X64**2).sum(1)[None, :]
          - 2.0 * Q64 @ X64.T)
    wider = np.argsort(od, axis=1, kind="stable")
    wide_ids = np.vectorize(lambda p: id_of_pos[int(p)])(
        wider[:, : 4 * k])
    wide_dists = np.take_along_axis(od, wider[:, : 4 * k], 1)
    r_mut = recall_against_oracle(got_mut, wide_dists, wide_ids, k)
    r_fresh = recall_against_oracle(got_fresh_ids, wide_dists, wide_ids, k)
    # the configured gate: churned recall within 0.02 of the rebuild's
    # (both probe the same nprobe; clustering may differ slightly)
    assert r_mut >= r_fresh - 0.02, (r_mut, r_fresh)


# ---------------------------------------------------------------------------
# Sharded mutation


@pytest.fixture
def multi_device():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (virtual) devices")


def test_s1_sharded_mutation_bit_identical(rng):
    X, cents = _blobs(rng)
    cfg = dict(k=5, partitions=8, nprobe=4, query_tile=32,
               mutation_bucket=32, kmeans_iters=8, bucket_headroom=0.5)
    a = build_ivf_index(X, KNNConfig(**cfg))
    b = shard_ivf_index(build_ivf_index(X, KNNConfig(**cfg)), shards=1)
    ids = np.arange(2000, 2032)
    rows = (cents[rng.integers(0, 8, 32)]
            + rng.standard_normal((32, 16))).astype(np.float32)
    sm.upsert_rows(a, ids, rows)
    sm.upsert_rows(b, ids, rows)
    sm.delete_rows(a, ids[:8])
    sm.delete_rows(b, ids[:8])
    for name in ("buckets", "bucket_ids", "bucket_sqs"):
        av = np.asarray(getattr(a, name))
        bv = np.asarray(getattr(b, name))
        assert (av == bv).all(), name


def test_sharded_mutation_and_compact(rng, multi_device):
    from mpi_knn_tpu.ivf.sharded import search_ivf_sharded

    X, cents = _blobs(rng)
    shards = min(4, len(jax.devices()))
    idx = shard_ivf_index(
        build_ivf_index(X, KNNConfig(
            k=5, partitions=8, nprobe=8, query_tile=32,
            mutation_bucket=32, kmeans_iters=8, bucket_headroom=0.5,
        )),
        shards=shards,
    )
    ids = np.arange(3000, 3032)
    rows = (cents[rng.integers(0, 8, 32)]
            + rng.standard_normal((32, 16))).astype(np.float32)
    sm.upsert_rows(idx, ids, rows)
    sm.delete_rows(idx, ids[:16])
    probes = rows[16:20] + np.float32(0.1)  # exclude_zero is scale-
    # relative: probe NEAR the upserted rows, above its resolution
    d, i, _ = search_ivf_sharded(idx, probes, config=idx.cfg
                                 .replace(k=3))
    assert not set(ids[:16].tolist()) & set(i.ravel().tolist())
    assert set(i[:, 0].tolist()) == set(ids[16:20].tolist())
    st = sm.compact_index(idx, reason="test")
    assert st["live"] == 256 + 16
    d, i, _ = search_ivf_sharded(idx, probes, config=idx.cfg
                                 .replace(k=3))
    assert set(i[:, 0].tolist()) == set(ids[16:20].tolist())


# ---------------------------------------------------------------------------
# Compaction


def test_compact_triggers_and_reclaims(rng):
    X, _ = _blobs(rng, m=512)
    idx = _ivf(X, compact_tombstone_fraction=0.2)
    assert should_compact(idx, idx.cfg) is None
    sm.delete_rows(idx, np.arange(0, 200))
    assert should_compact(idx, idx.cfg) == "tombstones"
    st = sm.compact_index(idx, reason="tombstones")
    assert st["live"] == 312
    fl = freelist_of(idx)
    assert fl.tombstones == 0
    assert should_compact(idx, idx.cfg) is None
    # cap preserved -> the executable cache survives compaction
    assert st["cap_before"] == st["cap_after"]


def test_compact_preserves_answers(rng):
    X, cents = _blobs(rng, m=512)
    idx = _ivf(X, nprobe=8)
    Q = (cents[rng.integers(0, 8, 32)]
         + rng.standard_normal((32, 16))).astype(np.float32)
    sm.delete_rows(idx, np.arange(100, 150))
    d0, i0 = search_ivf(idx, Q, config=idx.cfg.replace(k=5))
    sm.compact_index(idx, retrain=True)
    d1, i1 = search_ivf(idx, Q, config=idx.cfg.replace(k=5))
    # nprobe == partitions: the scan is exact, so compaction (a
    # re-layout of the same live rows) must return the same neighbors
    assert (i0 == i1).all()
    np.testing.assert_allclose(d0, d1, rtol=1e-5, atol=1e-4)


def test_session_overflow_compacts_and_retries(rng):
    X, _ = _blobs(rng)
    idx = _ivf(X, bucket_headroom=0.1)
    ses = ServeSession(idx)
    # a skewed burst at one spot in space — outruns any balanced cap;
    # the session must compact (growing if it must) rather than fail
    burst = (np.ones((1, 16)) * 3.0
             + 0.01 * rng.standard_normal((200, 16))).astype(np.float32)
    st = ses.upsert(np.arange(40000, 40200), burst)
    assert st["upserted"] == 200
    assert ses.stats_snapshot()["mutation"]["compactions"] >= 1
    d, i = search_ivf(idx, burst[:4], config=idx.cfg.replace(k=3))
    assert set(i[:, 0].tolist()) <= set(range(40000, 40200))


def test_compactor_defers_under_shed(rng):
    from mpi_knn_tpu.resilience import ResiliencePolicy

    X, _ = _blobs(rng, m=512)
    idx = _ivf(X, compact_tombstone_fraction=0.1)
    ses = ServeSession(idx, resilience=ResiliencePolicy())
    comp = ses.start_compactor(interval_s=3600)  # tick manually
    try:
        sm.delete_rows(idx, np.arange(0, 200))
        assert should_compact(idx, ses.cfg) == "tombstones"
        assert ses.shed_rung(reason="test") is not None
        assert comp.tick() is None  # compaction is shed first
        snap = comp.snapshot()
        assert snap["deferred"] == 1 and snap["compactions"] == 0
        ses.restore_rung()
        st = comp.tick()
        assert st is not None and st["reason"] == "tombstones"
        assert comp.snapshot()["compactions"] == 1
    finally:
        comp.stop()


def test_compactor_thread_runs_and_flight_records(rng, tmp_path):
    from mpi_knn_tpu.obs.spans import FlightRecorder, set_recorder

    flight = tmp_path / "flight.jsonl"
    set_recorder(FlightRecorder(str(flight), fresh=True))
    try:
        X, _ = _blobs(rng, m=512)
        idx = _ivf(X, compact_tombstone_fraction=0.1)
        ses = ServeSession(idx)
        comp = ses.start_compactor(interval_s=0.05)
        try:
            sm.delete_rows(idx, np.arange(0, 200))
            import time as _time

            deadline = _time.time() + 30
            while (comp.snapshot()["compactions"] == 0
                   and _time.time() < deadline):
                _time.sleep(0.05)
            assert comp.snapshot()["compactions"] >= 1
        finally:
            comp.stop()
        from mpi_knn_tpu.obs.spans import read_flight, validate_flight

        records = read_flight(str(flight))
        problems = validate_flight(records)
        assert problems == [], problems
        assert any(r.get("name") == "compact" for r in records)
    finally:
        set_recorder(None)


# ---------------------------------------------------------------------------
# Format compatibility


def test_mutated_index_roundtrips_bit_identically(rng, tmp_path):
    X, cents = _blobs(rng)
    idx = _ivf(X)
    sm.upsert_rows(idx, np.arange(1000, 1032),
                   (cents[rng.integers(0, 8, 32)]
                    + rng.standard_normal((32, 16))).astype(np.float32))
    sm.delete_rows(idx, np.arange(0, 40))
    path = str(tmp_path / "mut.npz")
    save_ivf_index(idx, path)
    back = load_ivf_index(path)
    for name in ("buckets", "bucket_ids", "bucket_sqs", "centroids",
                 "centroid_sqs"):
        assert (np.asarray(getattr(idx, name))
                == np.asarray(getattr(back, name))).all(), name
    # the freelist re-derives: same occupancy, tombstoned slots free
    fa, fb = freelist_of(idx), freelist_of(back)
    assert fa.live == fb.live
    assert (fa.used == fb.used).all()
    # and the reloaded index keeps mutating
    sm.upsert_rows(back, [5], rng.standard_normal((1, 16))
                   .astype(np.float32))
    assert back.live_rows == fa.live + (0 if fa.where(5) is not None else 1)


def test_legacy_pre_mutation_artifact_loads_with_headroom(rng, tmp_path):
    """A pre-ISSUE-14 artifact has no live_rows meta and was built with
    no headroom knob — it must load, derive its padding as headroom,
    and accept mutations."""
    import json

    X, _ = _blobs(rng)
    idx = _ivf(X)
    path = str(tmp_path / "legacy.npz")
    save_ivf_index(idx, path)
    # strip the post-ISSUE-14 meta keys (live_rows; bucket_headroom and
    # the compact knobs out of cfg) to fake a legacy artifact
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(bytes(arrays["meta"]).decode())
    meta.pop("live_rows")
    for key in ("bucket_headroom", "mutation_bucket",
                "compact_fill_threshold", "compact_tombstone_fraction"):
        meta["cfg"].pop(key)
    arrays["meta"] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8
    )
    legacy = str(tmp_path / "legacy2.npz")
    with open(legacy, "wb") as f:
        np.savez(f, **arrays)
    back = load_ivf_index(legacy)
    fl = freelist_of(back)
    assert fl.live == 256
    assert fl.free_count.sum() == \
        back.partitions * back.bucket_cap - 256
    sm.upsert_rows(back, [7777], rng.standard_normal((1, 16))
                   .astype(np.float32))
    assert back.live_rows == 257


def test_4shard_build_with_tombstones_reloads_on_fewer_shards(
        rng, tmp_path, multi_device):
    X, cents = _blobs(rng)
    shards = min(4, len(jax.devices()))
    idx = shard_ivf_index(
        build_ivf_index(X, KNNConfig(
            k=5, partitions=8, nprobe=8, query_tile=32,
            mutation_bucket=32, kmeans_iters=8, bucket_headroom=0.5)),
        shards=shards,
    )
    ids = np.arange(6000, 6016)
    rows = (cents[rng.integers(0, 8, 16)]
            + rng.standard_normal((16, 16))).astype(np.float32)
    sm.upsert_rows(idx, ids, rows)
    sm.delete_rows(idx, ids[:8])
    path = str(tmp_path / "shard.npz")
    save_ivf_index(idx, path)
    plain = load_ivf_index(path)
    d0, i0 = search_ivf(plain, rows[8:12],
                        config=plain.cfg.replace(k=3))
    for s in (1, 2):
        re = shard_ivf_index(load_ivf_index(path), shards=s)
        fl = freelist_of(re)
        assert fl.live == 256 + 8
        from mpi_knn_tpu.ivf.sharded import search_ivf_sharded

        d, i, _ = search_ivf_sharded(re, rows[8:12],
                                     config=re.cfg.replace(k=3))
        assert (i == i0).all()
        assert not set(ids[:8].tolist()) & set(i.ravel().tolist())


# ---------------------------------------------------------------------------
# Perf: mutation vs rebuild-per-batch (miniature; the committed
# bench_ops baseline carries the real rows)


def test_upsert_beats_rebuild_per_batch_10x(rng):
    import time

    X, cents = _blobs(rng, m=1024, d=32)
    cfg = dict(k=5, partitions=16, nprobe=4, query_tile=64,
               mutation_bucket=64, bucket_headroom=0.5)
    idx = build_ivf_index(X, KNNConfig(**cfg))
    B = 64
    rows = (cents[rng.integers(0, 8, B)]
            + rng.standard_normal((B, 32))).astype(np.float32)
    sm.upsert_rows(idx, np.arange(50000, 50000 + B), rows)  # warm
    sm.delete_rows(idx, np.arange(50000, 50000 + B))
    t0 = time.perf_counter()
    reps = 5
    for j in range(reps):
        base = 60000 + j * B
        sm.upsert_rows(idx, np.arange(base, base + B), rows)
        sm.delete_rows(idx, np.arange(base, base + B))
    upsert_s = (time.perf_counter() - t0) / (2 * reps)
    t0 = time.perf_counter()
    build_ivf_index(X, KNNConfig(**cfg))
    rebuild_s = time.perf_counter() - t0
    # the tentpole bar: absorbing a batch by mutation must be >= 10x
    # the rows/s of absorbing it by rebuild (generous on CPU: measured
    # ~100-1000x)
    assert rebuild_s > 10 * upsert_s, (upsert_s, rebuild_s)


# ---------------------------------------------------------------------------
# Engine/serve integration details


def test_mutation_metrics_and_gauges(rng):
    from mpi_knn_tpu.obs.metrics import get_registry

    X, _ = _blobs(rng)
    idx = _ivf(X)
    sm.upsert_rows(idx, np.arange(8000, 8016),
                   rng.standard_normal((16, 16)).astype(np.float32))
    sm.delete_rows(idx, np.arange(8000, 8008))
    text = get_registry().to_prometheus()
    from mpi_knn_tpu.obs.metrics import parse_prometheus

    samples = parse_prometheus(text)
    assert samples["mutation_upserts_total"] >= 16
    assert samples["mutation_deletes_total"] >= 8
    assert samples["index_live_rows"] == freelist_of(idx).live
    assert 0 < samples["index_max_bucket_fill"] <= 1.0


def test_mutation_stats_reset_contract(rng):
    X, _ = _blobs(rng)
    ses = ServeSession(_ivf(X))
    ses.upsert(np.arange(8100, 8104),
               rng.standard_normal((4, 16)).astype(np.float32))
    assert ses.stats_snapshot()["mutation"]["upserts"] == 4
    ses.reset_stats()
    assert ses.stats_snapshot()["mutation"]["upserts"] == 0
    # the INDEX occupancy is not a window stat: it survives the reset
    assert ses.index.live_rows == 260


def test_mutation_interleaves_with_dispatch_depth(rng):
    """Mutations between submits at dispatch_depth > 1: in-flight
    batches retire against the store they were dispatched on; every
    answer is internally consistent (no ghost ids from mid-batch
    swaps)."""
    X, cents = _blobs(rng, m=384)
    idx = _ivf(X, dispatch_depth=3)
    ses = ServeSession(idx)
    ses.warm([32])
    Q = (cents[rng.integers(0, 8, 20)]
         + rng.standard_normal((20, 16))).astype(np.float32)
    done = []
    for j in range(6):
        done += ses.submit(Q)
        ses.upsert(np.arange(70000 + j * 10, 70000 + j * 10 + 10),
                   (cents[j % 8] + 0.01 * rng.standard_normal((10, 16))
                    ).astype(np.float32))
        ses.delete(np.arange(70000 + j * 10, 70000 + j * 10 + 5))
    done += ses.drain()
    assert len(done) == 6
    for res in done:
        assert np.isfinite(res.dists).all()


# ---------------------------------------------------------------------------
# ISSUE 34: the host mirror in array form, the tile-by-tile upsert, what a
# write holds the lock for


def _random_plane(rs, parts, cap, fill):
    ids = np.full((parts, cap), -1, np.int32)
    used = rs.random((parts, cap)) < fill
    ids[used] = rs.permutation(4 * parts * cap)[: int(used.sum())]
    return ids


@pytest.mark.parametrize("seed,parts,cap,fill", [
    (0, 4, 16, 0.5), (1, 9, 32, 0.8), (2, 3, 64, 0.2), (3, 16, 8, 0.6),
    (4, 1, 128, 0.9),
])
def test_array_mirror_chooses_the_old_freelist_s_slots(seed, parts, cap,
                                                       fill):
    """Random insert / update / move / delete chunks against the dict
    freelist of before ISSUE 34 (``tests/freelist_oracle.py``): the same
    scatter vectors chunk for chunk, the same position for every live id
    and the same free slots in every bucket after each commit — the replay
    contract (lowest bucket's lowest free slot first)."""
    from tests import freelist_oracle as oracle

    rs = np.random.default_rng(seed)
    plane = _random_plane(rs, parts, cap, fill)
    new, old = Freelist(plane, parts), oracle.DictFreelist(plane, parts)
    for step in range(40):
        live = np.array(sorted(old.pos), dtype=np.int64)
        if step % 3 == 2 and live.size:
            ids = np.unique(np.concatenate([
                rs.choice(live, size=min(live.size, 5), replace=False),
                rs.integers(10**6, 10**6 + 50, size=2)]))  # two unknown
            got, want = plan_delete(new, ids), oracle.plan_delete(old, ids)
            for g, w in zip(got[:2], want[:2]):
                assert (g == w).all()
            assert got[3] == want[3]
            got[2](), want[2]()
        else:
            fresh = rs.integers(0, 8 * parts * cap, size=rs.integers(1, 9))
            again = (rs.choice(live, size=min(live.size, 4), replace=False)
                     if live.size else live)
            ids = np.unique(np.concatenate([fresh, again]))
            to = rs.integers(0, parts, size=ids.size)
            try:
                want = oracle.plan_upsert(old, ids, to)
            except OverflowError:
                with pytest.raises(BucketOverflowError):
                    plan_upsert(new, ids, to)
                continue
            got = plan_upsert(new, ids, to)
            for g, w in zip(got[:4], want[:4]):
                assert (g == w).all(), step
            got[4](), want[4]()
        assert new.live == old.live
        live = np.array(sorted(old.pos), dtype=np.int64)
        assert (new.live_ids() == live).all()
        at = new.lookup(live)
        assert [divmod(int(f), cap) for f in at] == [old.pos[int(i)]
                                                     for i in live]
        for p in range(parts):
            assert new.lowest_free(p, cap).tolist() == sorted(old.free[p])
        assert new.stats()["free_slots"] == sum(map(len, old.free))
        assert new.max_fill == old.max_fill


def test_mirror_for_two_million_ids_is_arrays_and_takes_under_a_second():
    """The build-time bound: 2 M ids in under a second, and nothing a row
    on the heap (a dict entry and a tuple a row were ~200 bytes each)."""
    import time

    plane = np.arange(2_097_152, dtype=np.int32).reshape(256, 8192)
    plane[-6:] = -1  # headroom
    took = []
    for _ in range(3):  # (this sandbox pays seconds for memory's first touch)
        t = time.perf_counter()
        fl = Freelist(plane, 256)
        took.append(time.perf_counter() - t)
    assert min(took) < 1.0, took
    assert fl.live == 250 * 8192 and not fl._far
    assert fl.nbytes < 8 * plane.size  # ~5 bytes a slot
    t = time.perf_counter()
    part, slot, *_, commit = plan_upsert(
        fl, np.arange(3_000_000, 3_001_024), np.full(1024, 250))
    commit()
    assert time.perf_counter() - t < 0.1
    assert (part == 250).all() and (slot == np.arange(1024)).all()


def test_ids_past_the_table_keep_working():
    """Ids hashed over all of int32 (beyond ``dense_limit``) live in the
    mirror's dict, beside the table."""
    plane = np.full((2, 8), -1, np.int32)
    plane[0, :3] = [5, 2**31 - 7, 9]
    fl = Freelist(plane, 2)
    assert fl.where(2**31 - 7) == (0, 1) and fl.live == 3
    far = np.array([2**31 - 9, 2**30], dtype=np.int64)
    *_, commit = plan_upsert(fl, far, np.array([1, 1]))
    commit()
    assert fl.where(2**31 - 9) == (1, 0) and fl.where(2**30) == (1, 1)
    assert fl.live_ids().tolist() == [5, 9, 2**30, 2**31 - 9, 2**31 - 7]
    part, slot, commit, missing = plan_delete(
        fl, np.array([2**31 - 7, 2**31 - 9, 4], dtype=np.int64))
    commit()
    assert missing == 1 and fl.live == 3
    assert fl.where(2**31 - 7) is None and fl.lowest_free(0)[0] == 1


@pytest.mark.parametrize("dim,c_tile,chunk", [(100, 64, 32), (16, 128, 8)])
def test_upsert_tile_by_tile_equals_the_one_scatter(rng, dim, c_tile, chunk):
    """``by_tile`` (the form for a stack the device keeps rows-minor: a TPU
    at a width off its lane grid) writes what the one scatter writes, slot
    for slot, padding rows dropped, several tiles touched in one chunk."""
    tiles = rng.standard_normal((6, c_tile, dim)).astype(np.float32)
    ids = np.arange(6 * c_tile, dtype=np.int32).reshape(6, c_tile)
    sqs = (tiles ** 2).sum(-1)
    rows = rng.standard_normal((chunk, dim)).astype(np.float32)
    n = chunk - 3  # three padding rows
    flat = rng.choice(6 * c_tile, size=n, replace=False)
    tpos = np.concatenate([flat // c_tile, np.full(3, 6)]).astype(np.int32)
    spos = np.concatenate([flat % c_tile, np.zeros(3)]).astype(np.int32)
    new_ids = np.arange(10_000, 10_000 + chunk, dtype=np.int32)
    drop = np.full(chunk, 6, np.int32)
    zero = np.zeros(chunk, np.int32)
    cfg = KNNConfig(k=5, backend="serial")
    out = [sm.serial_upsert_jit(
        rows, new_ids, tpos, spos, drop, zero, jnp.asarray(tiles),
        jnp.asarray(ids), jnp.asarray(sqs), cfg=cfg, by_tile=by_tile)
        for by_tile in (False, True)]
    for a, b in zip(*out):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert (np.asarray(out[1][0])[tpos[:n], spos[:n]] == rows[:n]).all()
    assert len(set(tpos[:n].tolist())) > 1


def test_a_batch_sees_the_store_wholly_before_or_after_a_write(rng):
    """A batch dispatched while a write holds the mutation lock waits for
    it (``mutation_lock_wait_seconds_total{side="batch"}`` moves) and
    answers from the store as the write left it: rows, ids and norms
    together."""
    import threading

    from mpi_knn_tpu.obs.metrics import get_registry
    from mpi_knn_tpu.serve.engine import mutation_lock

    X = (rng.standard_normal((512, 100)) * 0.3).astype(np.float32)
    idx = build_index(X, KNNConfig(
        k=3, backend="serial", corpus_tile=128, query_bucket=32,
        mutation_bucket=32, bucket_headroom=0.25, exclude_zero=False))
    ses = ServeSession(idx)
    ses.warm([32])
    sm.warm_mutation(idx)
    assert "_freelist" in idx.__dict__  # made in set-up, not by a write
    new = (rng.standard_normal((32, 100)) * 0.3).astype(np.float32) + 4.0
    new_ids = np.arange(9000, 9032)
    query_knn(new, idx, ses.cfg)  # the read path once, before the clock
    entered, release = threading.Event(), threading.Event()
    real = sm.mutation_phase

    def slow_commit(name, **attrs):
        if name == "commit":  # under the lock, after the dispatch
            entered.set()
            assert release.wait(30)
        return real(name, **attrs)

    def waited():
        return get_registry().snapshot()["metrics"].get(
            'mutation_lock_wait_seconds_total{side="batch"}',
            {}).get("value", 0.0)

    sm.mutation_phase = slow_commit
    try:
        writer = threading.Thread(
            target=lambda: sm.upsert_rows(idx, new_ids, new))
        writer.start()
        assert entered.wait(30) and mutation_lock(idx).locked()
        before = waited()
        got = []
        reader = threading.Thread(
            target=lambda: got.append(query_knn(new, idx, ses.cfg)))
        reader.start()
        reader.join(0.5)
        assert reader.is_alive()  # the batch waits for the write
        release.set()
        writer.join(30), reader.join(30)
    finally:
        sm.mutation_phase = real
        release.set()
    d, i = np.asarray(got[0].dists), np.asarray(got[0].ids)
    assert (i[:, 0] == new_ids).all()  # wholly after: ids, rows and norms
    assert np.abs(d[:, 0]).max() < 0.05  # (the matmul form near |x|^2 = 1600)
    assert waited() - before > 0.2


@pytest.mark.parametrize("m,c_tile,headroom", [(1000, 128, 0.1),
                                               (1024, 128, 0.25),
                                               (300, 128, 0.0),
                                               # under one tile: no whole
                                               # tile to slice out
                                               (100, 128, 0.0)])
def test_stack_with_headroom_is_built_tile_by_tile(rng, m, c_tile, headroom):
    """A device corpus that needs padding goes into its stack by one
    program, tile by tile (no padded copy beside the stack): the same
    rows, ids and norms as the host build's pad + reshape."""
    X = rng.standard_normal((m, 24)).astype(np.float32)
    cfg = KNNConfig(k=3, backend="serial", corpus_tile=c_tile,
                    query_bucket=32, bucket_headroom=headroom)
    host, dev = build_index(X, cfg), build_index(jnp.asarray(X), cfg)
    assert dev.tiles.shape == host.tiles.shape
    assert dev.tiles.shape[0] * c_tile >= int(np.ceil(m * (1 + headroom)))
    assert np.allclose(np.asarray(dev.tiles), np.asarray(host.tiles),
                       atol=1e-6)
    assert (np.asarray(dev.tile_ids) == np.asarray(host.tile_ids)).all()
    assert (np.asarray(dev.tiles).reshape(-1, 24)[m:] == 0).all()


# ---------------------------------------------------------------------------
# writes under the kernel's three-pass screen (ISSUE 51)


def test_padded_index_under_the_fused_screen_answers_as_float64_does():
    """The streaming cell's form in miniature — fractional d = 100 rows at
    rest zero-padded to 128 columns, a 1024-row bucket, L2: every search
    ranks INSIDE the kernel (``path="fused_screen"``, slots made from the
    grid's index), and after upserts into free slots, updates of live ids
    and deletes the answer is the float64 top-10 over the rows live then:
    a written row is found at its slot, a tombstoned slot is read for its
    sign and never returned."""
    rng = np.random.default_rng(51)
    d, c_tile, rows, k = 100, 1024, 1024, 10
    n = 3 * c_tile - 50
    cen = rng.normal(size=(16, d))

    def draw(m):
        return (cen[rng.integers(0, 16, m)]
                + 0.5 * rng.normal(size=(m, d))).astype(np.float32)

    x, q = draw(n), draw(rows)
    index = build_index(x, KNNConfig(
        k=k, backend="serial", query_tile=rows, corpus_tile=c_tile,
        exclude_zero=False, bucket_headroom=0.3, mutation_bucket=256))
    tiles = index.tiles.shape[0]
    assert index.tiles.shape[-1] == 128 and index.dim == d

    def check(got, live):
        assert np.asarray(got.dist_steps).tolist() == [0] * 6 + [tiles]
        assert np.asarray(got.screen_rows).sum() == rows
        ids = np.fromiter(live, dtype=np.int64)
        rows64 = np.stack([live[i] for i in ids]).astype(np.float64)
        q64 = q.astype(np.float64)
        real = ((q64 * q64).sum(1)[:, None] - 2 * q64 @ rows64.T
                + (rows64 * rows64).sum(1)[None])
        want = np.sort(real, axis=1)[:, :k]
        # (relative to the pair's scale: the form cancels near a duplicate)
        scale = np.maximum(want, 0.1 * (q64 * q64).sum(1)[:, None])
        np.testing.assert_array_less(
            np.abs(np.asarray(got.dists) - want) / scale, 2e-5)
        best = ids[np.argsort(real, axis=1)[:, :k]]
        assert (best == np.asarray(got.ids)).mean() > 0.999
        return np.asarray(got.ids)

    live = dict(enumerate(x))
    check(query_knn(q, index), live)
    fresh = draw(300)
    fresh[:150] = q[:150] + 0.01 * rng.normal(size=(150, d)).astype(
        np.float32)
    moved = q[150:250] + 0.01 * rng.normal(size=(100, d)).astype(np.float32)
    sm.upsert_rows(index, np.arange(n, n + 300), fresh)
    sm.upsert_rows(index, np.arange(100), moved)
    gone = np.arange(200, 700)
    sm.delete_rows(index, gone)
    live.update(zip(range(n, n + 300), fresh))
    live.update(zip(range(100), moved))
    for i in gone:
        del live[int(i)]
    ids = check(query_knn(q, index), live)
    assert (ids[:150, 0] == np.arange(n, n + 150)).all()
    assert (ids[150:250, 0] == np.arange(100)).all()
    assert not np.isin(ids, gone).any()
