"""Serving front end × serve engine integration (ISSUE 11): coalesced
multi-tenant dispatch against a real session — bit-identity, the
zero-steady-state-compile contract, per-tenant attribution, and the
ISSUE 11 acceptance gate (≥ 8 tenant streams, coalesced throughput ≥ 2×
per-stream depth-1 sequential dispatch under one p99 bound, fairness
asserted, zero compiles across the measured run)."""

from __future__ import annotations

import numpy as np
import pytest

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.frontend import Frontend, Rejection, SLOPolicy
from mpi_knn_tpu.frontend import loadgen
from mpi_knn_tpu.obs.metrics import get_registry, watch_compiles
from mpi_knn_tpu.resilience import ResiliencePolicy
from mpi_knn_tpu.serve import ServeSession, build_index, query_knn

DIM = 32
BUCKET = 128


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(2048, DIM)).astype(np.float32)
    cfg = KNNConfig(k=5, backend="serial", query_bucket=BUCKET,
                    corpus_tile=512, query_tile=BUCKET)
    return build_index(X, cfg)


def _frontend(index, **slo_kw):
    session = ServeSession(index, resilience=ResiliencePolicy())
    kw = dict(max_batch_rows=BUCKET, max_wait_s=0.002,
              max_queue_rows=65536)
    kw.update(slo_kw)
    return Frontend(session, SLOPolicy(**kw)).start()


def test_coalesced_results_bit_identical_to_sequential(index):
    """Requests of ragged sizes from several tenants, coalesced into
    shared batches, must return BIT-identical results to the same
    queries served alone (the per-row independence the bucket-padding
    parity tests already pin, here across the whole front end)."""
    fe = _frontend(index)
    rng = np.random.default_rng(1)
    reqs = [
        (f"tenant-{i % 5}", rng.normal(size=(rows, DIM)).astype(np.float32))
        for i, rows in enumerate([1, 5, 16, 33, 7, 16, 64, 2, 31, 16])
    ]
    tickets = [(q, fe.submit(t, q)) for t, q in reqs]
    try:
        for q, ticket in tickets:
            assert not isinstance(ticket, Rejection)
            dists, ids = ticket.result(timeout=60)
            ref = query_knn(q, index)
            assert np.array_equal(ids, ref.ids)
            assert np.array_equal(dists, ref.dists)
    finally:
        fe.stop()


def test_per_tenant_attribution_and_batch_spans(index, tmp_path):
    """A coalesced batch feeds tenant_stats per tenant, the labeled
    registry counters, and stamps its tenant composition on the batch
    flight span."""
    from mpi_knn_tpu.obs.spans import (
        FlightRecorder,
        read_flight,
        reconstruct_spans,
        set_recorder,
        validate_flight,
    )

    flight = tmp_path / "flight.jsonl"
    set_recorder(FlightRecorder(str(flight), fresh=True))
    try:
        fe = _frontend(index)
        rng = np.random.default_rng(2)
        tickets = [
            fe.submit(t, rng.normal(size=(8, DIM)).astype(np.float32))
            for t in ("alice", "bob", "alice")
        ]
        for t in tickets:
            t.result(timeout=60)
        fe.stop()
        st = fe.session.tenant_stats
        assert st["alice"]["queries"] == 16 and st["bob"]["queries"] == 8
        assert st["alice"]["batches"] >= 1
        assert st["alice"]["latency_sum_s"] > 0
        reg = get_registry()
        assert reg.counter(
            "serve_tenant_queries_total", labels={"tenant": "alice"}
        ).value >= 16
    finally:
        set_recorder(None)
    records = read_flight(str(flight))
    assert validate_flight(records) == []
    spans, events = reconstruct_spans(records)
    batch_spans = [s for s in spans if s["name"] == "batch"]
    assert batch_spans, "no batch spans in the flight record"
    comps = [s["attrs"].get("tenants") for s in batch_spans]
    assert any(c and "alice" in c for c in comps)
    served = {}
    for c in comps:
        for t, n in (c or {}).items():
            served[t] = served.get(t, 0) + n
    assert served == {"alice": 16, "bob": 8}
    coalesced = [s for s in spans if s["name"] == "coalesce"
                 and s["end_attrs"].get("rows")]
    assert sum(s["end_attrs"]["rows"] for s in coalesced) == 24


def test_rate_limited_tenant_gets_structured_429(index):
    fe = _frontend(index, max_tenant_qps=0.5, burst=1)
    q = np.zeros((4, DIM), np.float32)
    try:
        first = fe.submit("limited", q)
        second = fe.submit("limited", q)
        assert not isinstance(first, Rejection)
        assert isinstance(second, Rejection)
        assert second.reason == "rate" and second.status == 429
        assert second.retry_after_s > 0
        # an unrelated tenant is not throttled by it
        assert not isinstance(fe.submit("other", q), Rejection)
        first.result(timeout=60)
    finally:
        fe.stop()


def test_stop_flushes_admitted_requests(index):
    """Shutdown serves what was admitted: a request parked far below
    the fill threshold with a huge wait budget still completes."""
    fe = _frontend(index, max_wait_s=300.0)
    q = np.ones((3, DIM), np.float32)
    ticket = fe.submit("parked", q)
    assert not ticket.done()
    fe.stop()
    dists, ids = ticket.result(timeout=1)
    ref = query_knn(q, index)
    assert np.array_equal(ids, ref.ids)


def test_acceptance_coalescing_throughput_fairness_zero_compiles(index):
    """The ISSUE 11 acceptance gate, on CPU:

    - 8 concurrent tenant streams through the open-loop load generator;
    - coalesced serving sustains >= 2x the row throughput of per-stream
      depth-1 sequential dispatch (each lone 16-row request pads to the
      same 128-row bucket — the pad waste coalescing reclaims);
    - both runs meet ONE p99 bound (the equal-SLO comparison);
    - round-robin fairness: every stream is fully served, max/min served
      ratio == 1;
    - zero steady-state compiles across the whole coalesced run,
      jax.monitoring-counted.
    """
    P99_BOUND_MS = 500.0  # one CPU-scale SLO bound applied to BOTH runs
    tenants, n_requests, rows = 8, 12, 16

    # per-stream depth-1 sequential dispatch over the SAME index (shared
    # executable cache: the comparison isolates coalescing, not compiles)
    seq_session = ServeSession(
        index, config=index.cfg.replace(dispatch_depth=1)
    )
    seq_session.submit(np.zeros((BUCKET, DIM), np.float32))
    seq_session.drain()
    seq_session.reset_stats()
    seq = loadgen.run_sequential_baseline(
        seq_session, tenants=tenants, n_requests=n_requests, rows=rows,
        lo=-1.0, hi=1.0,
    )
    assert seq["achieved_qps_rows"] > 0

    fe = _frontend(index)
    try:
        with watch_compiles() as compiles:
            rep = loadgen.run_inprocess(
                fe, tenants=tenants, qps=5000.0, n_requests=n_requests,
                rows=rows, lo=-1.0, hi=1.0,
            )
        assert compiles == [], (
            f"coalesced serving compiled {len(compiles)} executables in "
            "steady state — the front end must only fill warm buckets"
        )
    finally:
        fe.stop()

    # everything served, nothing rejected or failed
    assert rep["rejected"] == 0 and rep["errors"] == 0
    assert sum(rep["per_tenant"].values()) == tenants * n_requests
    # fairness bound: equal offered load -> equal service, exactly
    served = rep["per_tenant"]
    assert max(served.values()) / min(served.values()) == 1.0

    # throughput: >= 2x sequential rows/s (expected ~8x: 16/128 fill)
    assert rep["achieved_qps_rows"] >= 2.0 * seq["achieved_qps_rows"], (
        f"coalesced {rep['achieved_qps_rows']} rows/s vs sequential "
        f"{seq['achieved_qps_rows']} rows/s"
    )
    # the equal p99 bound, applied to both runs
    assert seq["p99_ms"] <= P99_BOUND_MS
    assert rep["p99_ms"] <= P99_BOUND_MS, (
        f"coalesced p99 {rep['p99_ms']}ms over the {P99_BOUND_MS}ms bound "
        f"(sequential p99 {seq['p99_ms']}ms)"
    )


def test_tenant_composition_must_sum_to_rows(index):
    session = ServeSession(index)
    q = np.zeros((8, DIM), np.float32)
    with pytest.raises(ValueError, match="mis-attribute"):
        session.submit(q, tenants=(("a", 4), ("b", 3)))
