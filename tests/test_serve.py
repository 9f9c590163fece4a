"""Serving-engine gate (ISSUE 4 tentpole): parity of the streamed
query-serving path with the one-shot API, the bucketed AOT executable
cache's zero-recompile steady state (counted at the JAX compiler level,
not trusted from the engine's own bookkeeping), and the engine's loud
refusals.

Parity is asserted BIT-identical, not allclose: the serving path runs the
same tile reductions over the same centered values (the index precomputes
corpus norms under jit precisely so eager-vs-traced reduction bits cannot
diverge), so any difference is a real divergence, not noise. Data is
random normal — no distance ties, so merge order cannot permute ids.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_knn_tpu import KNNConfig, all_knn, build_index, query_knn
from mpi_knn_tpu.serve import ServeSession, bucket_rows
from mpi_knn_tpu.serve.engine import get_executable


def _data(rng, m=256, d=16):
    return rng.standard_normal((m, d)).astype(np.float32)


def _cfg(backend, **kw):
    kw.setdefault("k", 4)
    kw.setdefault("query_tile", 16)
    kw.setdefault("corpus_tile", 32)
    kw.setdefault("query_bucket", 16)
    return KNNConfig(backend=backend, **kw)


@pytest.fixture
def compile_counter():
    """Count XLA backend compiles — the machine check that a 'cache hit'
    really compiled nothing, independent of the engine's own cache
    bookkeeping. The shared obs-registry scope (the same events also
    feed `jax_compiles_total` in the process-wide registry) replaced the
    hand-rolled jax.monitoring listener this file used to carry."""
    from mpi_knn_tpu.obs.metrics import watch_compiles

    with watch_compiles() as counts:
        yield counts


# ---------------------------------------------------------------------------
# bucket math


def test_bucket_rows():
    assert bucket_rows(1, 16) == 16
    assert bucket_rows(16, 16) == 16
    assert bucket_rows(17, 16) == 32
    assert bucket_rows(33, 16) == 64
    assert bucket_rows(5, 5) == 5
    assert bucket_rows(11, 5) == 20
    with pytest.raises(ValueError):
        bucket_rows(0, 16)


# ---------------------------------------------------------------------------
# serving parity: query_knn vs the all_knn-derived oracle


@pytest.mark.parametrize("backend", ["serial", "ring", "ring-overlap"])
@pytest.mark.parametrize("policy", ["exact", "mixed"])
def test_query_parity_vs_all_knn(rng, backend, policy):
    """query_knn over a resident index is bit-identical to a fresh
    all_knn(corpus, queries=...) call — every backend, both precision
    policies (m=256/c_tile=32 keeps 4k=16 < c_tile so mixed genuinely
    compresses, including per ring block)."""
    X, Q = _data(rng), _data(rng, m=24)
    cfg = _cfg(backend, precision_policy=policy)
    want = all_knn(X, queries=Q, config=cfg)
    idx = build_index(X, cfg)
    got = query_knn(Q, idx)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(
        np.asarray(want.dists), np.asarray(got.dists)
    )


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_query_parity_metrics_serial(rng, metric):
    X, Q = _data(rng), _data(rng, m=24)
    cfg = _cfg("serial", metric=metric)
    want = all_knn(X, queries=Q, config=cfg)
    idx = build_index(X, cfg)
    got = query_knn(Q, idx)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(
        np.asarray(want.dists), np.asarray(got.dists)
    )


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_bucket_boundary_sizes(rng, metric):
    """Batch sizes straddling every bucket boundary (1, b−1, b, b+1, and
    the next bucket's boundary) all pad+mask to the all_knn answer — a
    ragged batch is bit-identical to its unpadded self. (Cosine: the
    padding rows are zero rows, which the query side's normalisation
    leaves zero.)"""
    X = _data(rng)
    cfg = _cfg("serial", metric=metric)
    idx = build_index(X, cfg)
    Qfull = _data(rng, m=40)
    for n in (1, 15, 16, 17, 31, 32, 33):
        Q = Qfull[:n]
        want = all_knn(X, queries=Q, config=cfg)
        got = query_knn(Q, idx)
        assert got.ids.shape == (n, cfg.k)
        np.testing.assert_array_equal(
            np.asarray(want.ids), np.asarray(got.ids)
        )
        np.testing.assert_array_equal(
            np.asarray(want.dists), np.asarray(got.dists)
        )


def test_device_and_host_queries_bit_identical(rng):
    """The same query batch, host numpy vs device-resident, produces
    bit-identical results over one index (the test_device_resident.py
    contract extended to the serving path)."""
    X, Q = _data(rng), _data(rng, m=24)
    for backend in ("serial", "ring-overlap"):
        idx = build_index(X, _cfg(backend))
        host = query_knn(Q, idx)
        dev = query_knn(jax.device_put(jnp.asarray(Q)), idx)
        np.testing.assert_array_equal(
            np.asarray(host.ids), np.asarray(dev.ids)
        )
        np.testing.assert_array_equal(
            np.asarray(host.dists), np.asarray(dev.dists)
        )


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_device_resident_corpus_index(rng, metric):
    """An index built from a device-resident corpus serves the same
    answers as all_knn over that device corpus (per-residency parity —
    the centering mean is residency-specific by documented contract; the
    cosine stack's inverse norms are one jitted function's on both
    sides)."""
    X, Q = _data(rng), _data(rng, m=24)
    Xd = jax.device_put(jnp.asarray(X))
    cfg = _cfg("serial", metric=metric)
    want = all_knn(Xd, queries=Q, config=cfg)
    idx = build_index(Xd, cfg)
    got = query_knn(Q, idx)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(
        np.asarray(want.dists), np.asarray(got.dists)
    )


# ---------------------------------------------------------------------------
# the executable cache: zero steady-state compiles, no fingerprint collisions


def test_steady_state_serving_is_recompile_free(rng, compile_counter):
    """After one warm pass per bucket, a stream of batches across ≥3
    bucket sizes — ragged sizes included — triggers ZERO XLA compiles
    (the acceptance bar: steady-state serving is recompile-free, counted
    at the compiler, not inferred from cache bookkeeping)."""
    X = _data(rng)
    idx = build_index(X, _cfg("serial"))
    session = ServeSession(idx)
    Qfull = _data(rng, m=64)

    # warm-up: one full submit+drain cycle per bucket (16, 32, 64) so the
    # executables AND the tiny host-visible glue ops are all cached
    for n in (16, 32, 64):
        session.submit(Qfull[:n])
    session.drain()
    assert len(idx._cache) == 3

    compile_counter.clear()
    served = []
    for n in (16, 9, 32, 33, 64, 1, 24):  # every bucket, ragged included
        served.extend(session.submit(Qfull[:n]))
    served.extend(session.drain())
    assert compile_counter == [], (
        f"steady-state serving compiled {len(compile_counter)} program(s)"
    )
    assert len(idx._cache) == 3  # no new executables either
    assert [r.rows for r in served] == [16, 9, 32, 33, 64, 1, 24]
    # one-shot query_knn is equally compile-free at a warm bucket for a
    # NEVER-SEEN ragged size: results strip on host, never via a
    # per-raw-size device slice program
    compile_counter.clear()
    ragged = query_knn(Qfull[:13], idx)
    assert compile_counter == [], "ragged one-shot query compiled"
    # and the served answers are right (ragged batches included)
    want = all_knn(X, queries=Qfull[:24], config=idx.cfg)
    np.testing.assert_array_equal(np.asarray(want.ids), served[-1].ids)
    np.testing.assert_array_equal(np.asarray(want.dists), served[-1].dists)
    want13 = all_knn(X, queries=Qfull[:13], config=idx.cfg)
    np.testing.assert_array_equal(np.asarray(want13.ids), ragged.ids)


def test_second_batch_of_each_bucket_is_a_cache_hit(rng, compile_counter):
    """Per bucket size: the first batch compiles (>0), the second batch of
    the SAME bucket compiles nothing. Shapes are unique to this test
    (d=24): jax's process-level compilation cache would otherwise satisfy
    the 'first' compile from another test's identical program and make
    the >0 half of the assertion vacuously fail."""
    X = _data(rng, m=192, d=24)
    idx = build_index(X, _cfg("serial"))
    Qfull = _data(rng, m=64, d=24)
    for n in (16, 32, 64):
        compile_counter.clear()
        query_knn(Qfull[:n], idx)
        assert len(compile_counter) > 0, f"first bucket-{n} batch cached?"
        compile_counter.clear()
        query_knn(Qfull[:n], idx)
        assert compile_counter == [], f"second bucket-{n} batch compiled"


def test_config_fingerprints_never_collide(rng):
    """Distinct query configs occupy distinct cache cells at the same
    bucket — and each serves its own (correct) program."""
    X = _data(rng)
    idx = build_index(X, _cfg("serial"))
    Q = _data(rng, m=16)
    r4 = query_knn(Q, idx)  # k=4 (index default)
    r5 = query_knn(Q, idx, k=5)
    r4b = query_knn(Q, idx, topk_method="block")
    nd = query_knn(Q, idx, donate=False)
    assert len(idx._cache) == 4  # (bucket 16) × 4 distinct fingerprints
    assert {b for b, _ in idx._cache} == {16}
    assert r5.ids.shape == (16, 5)
    np.testing.assert_array_equal(
        np.asarray(r4.ids), np.asarray(r5.ids[:, :4])
    )
    np.testing.assert_array_equal(np.asarray(r4.ids), np.asarray(r4b.ids))
    np.testing.assert_array_equal(np.asarray(r4.ids), np.asarray(nd.ids))


def test_donated_scratch_is_consumed(rng):
    """cfg.donate really donates: the carry buffers the engine passes are
    invalidated by the call (in-place reuse), and donate=False leaves
    donation off — both visible through the compiled executable's
    input_output_alias (asserted structurally in test_hlo_lint.py; here
    we pin the end-to-end behavioral difference: both configurations
    serve identical answers)."""
    X, Q = _data(rng), _data(rng, m=16)
    idx = build_index(X, _cfg("serial"))
    d = query_knn(Q, idx, donate=True)
    nd = query_knn(Q, idx, donate=False)
    np.testing.assert_array_equal(np.asarray(d.ids), np.asarray(nd.ids))
    np.testing.assert_array_equal(np.asarray(d.dists), np.asarray(nd.dists))


# ---------------------------------------------------------------------------
# the streaming session


def test_stream_order_latency_and_depth(rng):
    X = _data(rng)
    idx = build_index(X, _cfg("serial", dispatch_depth=2))
    session = ServeSession(idx)
    batches = [_data(rng, m=n) for n in (16, 16, 10, 16)]
    out = list(session.stream(iter(batches)))
    assert [r.rows for r in out] == [16, 16, 10, 16]
    assert session.queries_served == 58
    assert len(session.latencies) == 4
    assert all(lat > 0 for lat in session.latencies)
    # depth bound held: nothing left in flight after the stream
    assert not session._inflight
    for q, r in zip(batches, out):
        want = all_knn(X, queries=q, config=idx.cfg)
        np.testing.assert_array_equal(np.asarray(want.ids), r.ids)


def test_stream_depth_one_is_synchronous(rng):
    X = _data(rng)
    idx = build_index(X, _cfg("serial", dispatch_depth=1))
    session = ServeSession(idx)
    done = session.submit(_data(rng, m=16))
    assert len(done) == 1 and done[0].latency_s is not None
    assert not session._inflight


# ---------------------------------------------------------------------------
# refusals: combinations the engine cannot honor fail loudly


def test_refuses_corpus_side_config_changes(rng):
    idx = build_index(_data(rng), _cfg("serial"))
    with pytest.raises(ValueError, match="corpus-side"):
        query_knn(_data(rng, m=8), idx, corpus_tile=64)
    with pytest.raises(ValueError, match="corpus-side"):
        query_knn(_data(rng, m=8), idx, backend="ring-overlap")


def test_refuses_mixed_over_compressed_index(rng):
    idx = build_index(_data(rng), _cfg("serial", dtype="bfloat16"))
    with pytest.raises(ValueError):
        query_knn(_data(rng, m=8), idx, precision_policy="mixed")


def test_refuses_blocking_ring_on_2d_mesh(rng):
    from mpi_knn_tpu.parallel.mesh import make_mesh2d

    with pytest.raises(ValueError, match="multi-axis"):
        build_index(
            _data(rng), _cfg("ring"), mesh=make_mesh2d(2, 4)
        )


def test_config_serve_knob_validation():
    with pytest.raises(ValueError, match="query_bucket"):
        KNNConfig(query_bucket=0)
    with pytest.raises(ValueError, match="dispatch_depth"):
        KNNConfig(dispatch_depth=0)


def test_query_cli_refusals_exit_2():
    from mpi_knn_tpu.serve import cli as serve_cli

    # no query stream at all
    assert serve_cli.main(["--data", "synthetic:64x8c2"]) == 2
    # invalid knob combination caught at config level
    assert serve_cli.main(
        ["--data", "synthetic:64x8c2", "--synthetic", "8",
         "--dtype", "bfloat16", "--precision-policy", "mixed"]
    ) == 2


def test_query_cli_end_to_end(tmp_path):
    from mpi_knn_tpu.serve import cli as serve_cli

    report = tmp_path / "serve.json"
    rc = serve_cli.main(
        ["--data", "synthetic:128x16c4", "--synthetic", "40",
         "--batch", "16", "--bucket", "16", "--k", "3", "--backend",
         "serial", "--report", str(report), "-q"]
    )
    assert rc == 0
    import json

    doc = json.loads(report.read_text())
    assert doc["queries"] == 40
    assert doc["batches"] == 3
    assert doc["throughput_qps"] > 0
    assert doc["latency_p50_ms"] is not None


# ---------------------------------------------------------------------------
# compressed / sharded index layouts


def test_bf16_compressed_index_matches_bf16_all_knn(rng):
    """dtype='bfloat16' at build time IS the compressed-index mode: half
    the resident bytes, parity with the one-shot bf16 path."""
    X, Q = _data(rng), _data(rng, m=16)
    cfg = _cfg("serial", dtype="bfloat16")
    want = all_knn(X, queries=Q, config=cfg)
    idx = build_index(X, cfg)
    f32_idx = build_index(X, _cfg("serial"))
    assert idx.nbytes_resident * 2 == f32_idx.nbytes_resident
    got = query_knn(Q, idx)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))


def test_ring_index_with_transfer_compression(rng):
    """Ring serving composes with ring_transfer_dtype (the rotating block
    circulates at bf16) exactly like the one-shot ring path."""
    X, Q = _data(rng), _data(rng, m=24)
    cfg = _cfg("ring-overlap", ring_transfer_dtype="bfloat16")
    want = all_knn(X, queries=Q, config=cfg)
    idx = build_index(X, cfg)
    got = query_knn(Q, idx)
    np.testing.assert_array_equal(np.asarray(want.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(
        np.asarray(want.dists), np.asarray(got.dists)
    )


def test_get_executable_shapes(rng):
    """The executable's padded rows always cover the bucket and respect
    the tile alignment contract."""
    X = _data(rng)
    idx = build_index(X, _cfg("serial"))
    for bucket in (16, 32, 128):
        ex = get_executable(idx, idx.cfg, bucket)
        assert ex.q_pad >= bucket
        assert ex.q_pad % ex.q_tile == 0


@functools.lru_cache(maxsize=None)
def _index_of(backend):
    """One small index per backend name, built the way its own tests build
    it (the clustered kinds as test_ivf / test_ivf_sharded do)."""
    X = _data(np.random.default_rng(0))
    if backend.startswith("ivf"):
        from mpi_knn_tpu.ivf import build_ivf_index

        return build_ivf_index(X, KNNConfig(
            k=4, partitions=8, nprobe=2, query_bucket=16,
            ivf_shards=4 if backend == "ivf-sharded" else None,
        ))
    return build_index(X, _cfg(backend))


@pytest.mark.parametrize("bucket", [16, 256])
@pytest.mark.parametrize(
    "backend",
    ["serial", "ring", "ring-overlap", "ivf", "ivf-sharded"],
)
def test_layout_contract(backend, bucket):
    """What a kind's layout says of its batch program is what the program
    lowered from it carries: the signature the persistent cache checks,
    the shapes a cache hit rebuilds its dispatch state from, and the
    donated scratch."""
    from mpi_knn_tpu.analysis.rules import donor_params, output_aliases
    from mpi_knn_tpu.serve.engine import (
        bucket_shapes,
        expected_args,
        lower_bucket,
    )
    from mpi_knn_tpu.utils.hlo_graph import parse_hlo

    idx = _index_of(backend)
    assert idx.backend == backend
    cfg = idx.compatible_cfg(idx.cfg)
    lowered, q_pad, q_tile = lower_bucket(idx, cfg, bucket)
    assert bucket_shapes(idx, cfg, bucket) == (q_pad, q_tile)
    args = jax.tree.leaves(lowered.args_info)
    assert expected_args(idx, cfg, bucket) == [
        (tuple(a.shape), str(a.dtype)) for a in args
    ]
    donated = idx.layout.donate_argnums
    assert tuple(n for n, a in enumerate(args) if a.donated) == donated
    # and the module header carries one alias (or, before a sharded
    # program is optimized, one buffer_donor) for each of them
    mod = parse_hlo(lowered.compiler_ir(dialect="hlo").as_hlo_text())
    carried = set(output_aliases(mod).values()) | donor_params(mod)
    assert len(carried) == len(donated)


# ---------------------------------------------------------------------------
# session reuse across streams + per-tenant attribution (ISSUE 11
# satellite: the front end's reporting leans on these exact semantics)


def test_session_reusable_across_streams(rng, compile_counter):
    """One session, two streams: the second stream compiles NOTHING
    (the executable cache survives the window reset), reset_stats
    resets ONLY the window accumulators, seq keeps counting so batch
    provenance never aliases between streams, and results stay
    bit-identical stream to stream."""
    X = _data(rng)
    idx = build_index(X, _cfg("serial"))
    session = ServeSession(idx)
    q = _data(rng, m=16)
    out1 = list(session.stream([q, _data(rng, m=10)]))
    assert session.queries_served == 26 and len(session.latencies) == 2
    compile_counter.clear()

    session.reset_stats()
    assert session.queries_served == 0 and session.latencies == []
    assert session.tenant_stats == {}

    out2 = list(session.stream([q]))
    assert compile_counter == []  # warm across the window boundary
    # the new window counts only its own traffic
    assert session.queries_served == 16 and len(session.latencies) == 1
    # provenance is monotonic across streams, never re-zeroed
    assert out2[0].seq == out1[-1].seq + 1
    # bit-identity across windows (same query, same executable)
    np.testing.assert_array_equal(out1[0].ids, out2[0].ids)
    np.testing.assert_array_equal(out1[0].dists, out2[0].dists)


def test_reset_mid_flight_lands_batch_in_new_window(rng):
    """A batch in flight across reset_stats retires into the NEW window
    — never dropped, never double-counted (the documented contract)."""
    X = _data(rng)
    idx = build_index(X, _cfg("serial", dispatch_depth=4))
    session = ServeSession(idx)
    session.submit(_data(rng, m=16), tenants=(("t", 16),))
    assert session._inflight  # depth 4: not yet retired
    session.reset_stats()
    done = session.drain()
    assert len(done) == 1
    assert session.queries_served == 16 and len(session.latencies) == 1
    assert session.tenant_stats["t"]["queries"] == 16


def test_tenant_attribution_is_first_class(rng):
    """Per-tenant accumulators are session state, not deltas: a
    coalesced composition feeds each tenant's rows/batches/latency, the
    stream(tenant=...) form tags a whole stream, and the labeled
    registry counters carry the same numbers."""
    from mpi_knn_tpu.obs.metrics import get_registry

    X = _data(rng)
    idx = build_index(X, _cfg("serial"))
    session = ServeSession(idx)
    c0 = get_registry().counter(
        "serve_tenant_queries_total", labels={"tenant": "a"}
    ).value
    session.submit(
        _data(rng, m=16), tenants=(("a", 10), ("b", 6))
    )
    session.drain()
    list(session.stream([_data(rng, m=8)], tenant="a"))
    st = session.tenant_stats
    assert st["a"]["queries"] == 18 and st["b"]["queries"] == 6
    assert st["a"]["batches"] == 2 and st["b"]["batches"] == 1
    assert st["a"]["latency_sum_s"] >= st["a"]["latency_max_s"] > 0
    assert get_registry().counter(
        "serve_tenant_queries_total", labels={"tenant": "a"}
    ).value == c0 + 18
    # untagged legacy batches attribute nothing (zero-overhead default)
    session.submit(_data(rng, m=16))
    session.drain()
    assert sum(s["queries"] for s in st.values()) == 24


def test_tenant_composition_aggregates_parts(rng):
    """Several coalesced requests of ONE tenant in one batch are one
    batch (and one latency observation) for that tenant, and hostile
    tenant ids fail loudly at submit, not at retire inside a pump
    (review regressions)."""
    X = _data(rng)
    idx = build_index(X, _cfg("serial"))
    session = ServeSession(idx)
    session.submit(_data(rng, m=16), tenants=(("a", 8), ("a", 4), ("a", 4)))
    session.drain()
    st = session.tenant_stats["a"]
    assert st["queries"] == 16 and st["batches"] == 1
    assert st["latency_sum_s"] == st["latency_max_s"]  # ONE observation
    with pytest.raises(ValueError, match="metrics label"):
        session.submit(_data(rng, m=8), tenants=(('bad"id', 8),))


# ---------------------------------------------------------------------------
# the one-pass rule in serving (PR 29): the corpus side is a fact of the
# index, the query side is decided per batch inside the one program a bucket


@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("kind", ["whole", "gauss"])
def test_serving_is_bit_identical_to_all_knn_under_the_one_pass_rule(
        rng, dist_steps, kind, on_device):
    """Whole-number rows: the index holds the fact, 1024-row batches take
    the one-pass dot, a batch with one fractional row takes the configured
    dot from the SAME executable (no compile), and every answer is a fresh
    ``all_knn``'s bit for bit. Gaussian rows: no fact, today's program."""
    draw = (
        (lambda m: rng.integers(0, 256, (m, 32)).astype(np.float32))
        if kind == "whole" else (lambda m: _data(rng, m=m, d=32) * 50)
    )
    X, Q = draw(1024), draw(1024)
    Qf = Q.copy()
    Qf[17, 5] += 0.001
    put = jnp.asarray if on_device else (lambda a: a)
    cfg = _cfg("serial", k=10, query_tile=1024, corpus_tile=256,
               query_bucket=1024, matmul_precision="highest")
    idx = build_index(put(X), cfg)
    assert (idx.onepass is not None) == (kind == "whole")
    query_knn(put(Q), idx)  # warm the bucket and its glue
    from mpi_knn_tpu.obs.metrics import watch_compiles

    for batch, path in ((Q, 0), (Qf, 1), (Q, 0)):
        before = dist_steps()
        with watch_compiles() as compiles:
            got = query_knn(put(batch), idx)
        after = dist_steps()
        moved = [a - b for a, b in zip(after, before)]
        want_path = path if kind == "whole" else 1
        assert moved[want_path] == 4 and moved[1 - want_path] == 0
        if not on_device:  # a device batch's centring is an eager program
            assert compiles == []
        want = all_knn(put(X), queries=put(batch), config=cfg)
        np.testing.assert_array_equal(
            np.asarray(want.dists), np.asarray(got.dists))
        np.testing.assert_array_equal(
            np.asarray(want.ids), np.asarray(got.ids))


def test_small_buckets_of_a_whole_number_index_keep_todays_program(
        rng, dist_steps):
    """Under ``ONEPASS_MIN_ROWS`` the branch is not worth its copy of the
    tile and its set-up: the bucket's program has no branch (two outputs) though the index holds
    the fact, and its answers are still exact."""
    X = rng.integers(0, 256, (512, 16)).astype(np.float32)
    Q = rng.integers(0, 256, (16, 16)).astype(np.float32)
    cfg = _cfg("serial", k=4, matmul_precision="highest")
    idx = build_index(X, cfg)
    assert idx.onepass is not None
    before = dist_steps()
    got = query_knn(Q, idx)
    after = dist_steps()
    assert after[0] == before[0] and after[1] > before[1]
    ref = np.sort(((Q[:, None].astype(np.int64) - X[None].astype(np.int64))
                   ** 2).sum(-1), axis=1)[:, :4]
    np.testing.assert_array_equal(got.dists, ref.astype(np.float32))


def test_upsert_of_a_fractional_row_turns_the_index_fact_off_in_place(
        rng, dist_steps):
    """The fact is a device scalar the batch programs take as an argument:
    an upserted row that is not a bf16 number flips it, the warm executable
    takes its other branch, nothing recompiles, the answer holds the row."""
    from mpi_knn_tpu.obs.metrics import get_registry, watch_compiles
    from mpi_knn_tpu.serve.mutate import upsert_rows, warm_mutation

    X = rng.integers(0, 256, (1024, 16)).astype(np.float32)
    Q = rng.integers(0, 256, (1024, 16)).astype(np.float32)
    cfg = _cfg("serial", k=4, query_tile=1024, corpus_tile=256,
               query_bucket=1024, bucket_headroom=0.25, mutation_bucket=8,
               matmul_precision="highest")
    idx = build_index(X, cfg)
    warm_mutation(idx, cfg, sizes=[8])
    query_knn(Q, idx)
    assert bool(idx.onepass)
    assert get_registry().gauge("serve_index_onepass").value == 1.0
    upsert_rows(idx, [5000], rng.integers(0, 256, (1, 16)).astype(np.float32))
    assert bool(idx.onepass)  # a whole-number row keeps the fact
    row = Q[3:4] + 0.3
    with watch_compiles() as compiles:
        upsert_rows(idx, [5001], row)
        before = dist_steps()
        got = query_knn(Q, idx)
        after = dist_steps()
    assert compiles == []
    assert not bool(idx.onepass)
    assert get_registry().gauge("serve_index_onepass").value == 0.0
    assert after[0] == before[0] and after[1] - before[1] == 5
    assert got.ids[3, 0] == 5001
    # the matmul form at norms of 1e5: cancellation noise, not a miss
    np.testing.assert_allclose(got.dists[3, 0], 16 * 0.3 ** 2, atol=0.05)
