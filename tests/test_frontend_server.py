"""The network shell (ISSUE 11): the stdlib HTTP server and the HTTP
load generator — POST /query (JSON and raw f32), the tenant header,
structured 429s on the wire, GET /metrics re-parsed with the strict
Prometheus parser, GET /healthz, and error routes. The behavioral logic
under all of this is tested in test_frontend*.py; these tests pin the
translation layer."""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.frontend import Frontend, FrontendHTTPServer, SLOPolicy
from mpi_knn_tpu.frontend import loadgen
from mpi_knn_tpu.obs.metrics import parse_prometheus
from mpi_knn_tpu.resilience import ResiliencePolicy
from mpi_knn_tpu.serve import ServeSession, build_index, query_knn

DIM = 16


@pytest.fixture(scope="module")
def served():
    """(server, frontend, index): one live loopback server for the
    module (ephemeral port)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1024, DIM)).astype(np.float32)
    index = build_index(
        X,
        KNNConfig(k=4, backend="serial", query_bucket=64, corpus_tile=256,
                  query_tile=64),
    )
    fe = Frontend(
        ServeSession(index, resilience=ResiliencePolicy()),
        SLOPolicy(max_batch_rows=64, max_wait_s=0.002,
                  max_queue_rows=8192),
    ).start()
    srv = FrontendHTTPServer(fe, port=0).start()
    yield srv, fe, index
    srv.stop()
    fe.stop()


def _post(url, path, data, headers):
    req = urllib.request.Request(
        url + path, data=data, headers=headers, method="POST"
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def test_json_query_roundtrip(served):
    srv, fe, index = served
    q = np.arange(2 * DIM, dtype=np.float32).reshape(2, DIM)
    status, doc = _post(
        srv.url, "/query",
        json.dumps({"queries": q.tolist()}).encode(),
        {"Content-Type": "application/json", "X-Tenant": "json-tenant"},
    )
    ref = query_knn(q, index)
    assert status == 200 and doc["rows"] == 2
    assert doc["ids"] == ref.ids.tolist()
    assert np.allclose(np.asarray(doc["dists"], np.float32), ref.dists)
    assert fe.session.tenant_stats["json-tenant"]["queries"] >= 2


def test_raw_f32_query_bit_identical(served):
    """The octet-stream body (little-endian f32 rows at the index dim)
    returns the same ids as the JSON path for the same queries."""
    srv, _, index = served
    rng = np.random.default_rng(3)
    q = rng.normal(size=(5, DIM)).astype("<f4")
    status, doc = _post(
        srv.url, "/query", q.tobytes(),
        {"Content-Type": "application/octet-stream", "X-Tenant": "raw"},
    )
    ref = query_knn(np.asarray(q, np.float32), index)
    assert status == 200 and doc["ids"] == ref.ids.tolist()


def test_malformed_bodies_are_400(served):
    srv, _, _ = served
    for data, ctype in [
        (b"not json", "application/json"),
        (json.dumps({"queries": [[1.0, 2.0]]}).encode(),
         "application/json"),  # wrong dim
        (b"\x00" * 7, "application/octet-stream"),  # not whole f32 rows
        (b"", "application/json"),  # empty body
    ]:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url, "/query", data, {"Content-Type": ctype})
        assert ei.value.code == 400
        assert "error" in json.loads(ei.value.read())


def test_unknown_routes_are_404(served):
    srv, _, _ = served
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(srv.url + "/nope", timeout=10)
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(srv.url, "/elsewhere", b"{}",
              {"Content-Type": "application/json"})
    assert ei.value.code == 404


def test_healthz_reports_serving_posture(served):
    srv, _, index = served
    doc = loadgen.probe_server(srv.url)
    assert doc["ok"] is True
    assert doc["dim"] == DIM and doc["k"] == index.cfg.k
    assert doc["backend"] == "serial"
    assert doc["rung"] == "full" and doc["ladder"][0] == "full"
    assert doc["max_batch_rows"] == 64
    assert doc["uptime_s"] >= 0


def test_metrics_exposition_reparses_strictly(served):
    """GET /metrics must round-trip through parse_prometheus — including
    the labeled per-tenant counters — and carry the serving counters."""
    srv, _, index = served
    q = np.zeros((3, DIM), np.float32)
    _post(srv.url, "/query",
          json.dumps({"queries": q.tolist()}).encode(),
          {"Content-Type": "application/json", "X-Tenant": "scraped"})
    text = loadgen.fetch_metrics(srv.url)
    samples = parse_prometheus(text)  # strict: malformed lines raise
    assert samples["serve_batches_total"] >= 1
    assert samples['serve_tenant_queries_total{tenant="scraped"}'] >= 3
    assert "frontend_queue_rows" in samples
    # one TYPE header per base family even with many tenant labels
    type_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("# TYPE serve_tenant_queries_total ")
    ]
    assert len(type_lines) == 1


def test_rate_limit_is_429_on_the_wire(served):
    """A throttled tenant sees HTTP 429 with the structured body and a
    Retry-After header (the scheduler's Rejection, translated)."""
    srv, fe, _ = served
    # drive through the frontend's real policy? the module fixture has no
    # rate limit, so spin up a throttled server alongside
    throttled = Frontend(
        ServeSession(fe.session.index),
        SLOPolicy(max_batch_rows=64, max_wait_s=0.002,
                  max_queue_rows=8192, max_tenant_qps=0.25, burst=1),
    ).start()
    srv2 = FrontendHTTPServer(throttled, port=0).start()
    try:
        body = json.dumps(
            {"queries": np.zeros((1, DIM)).tolist()}
        ).encode()
        hdr = {"Content-Type": "application/json", "X-Tenant": "hot"}
        status, _ = _post(srv2.url, "/query", body, hdr)
        assert status == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv2.url, "/query", body, hdr)
        assert ei.value.code == 429
        doc = json.loads(ei.value.read())
        assert doc["error"] == "rate" and doc["tenant"] == "hot"
        assert float(ei.value.headers["Retry-After"]) > 0
        assert doc["retry_after_s"] > 0
    finally:
        srv2.stop()
        throttled.stop()


def test_http_loadgen_end_to_end(served):
    """The open-loop HTTP load generator against the live server: all
    requests served, per-tenant fairness, sane latency fields — the same
    path `mpi-knn loadgen` drives in the CI gate."""
    srv, _, _ = served
    rep = loadgen.run_http(
        srv.url, tenants=3, qps=60.0, n_requests=6, rows=8,
    )
    assert rep["errors"] == 0 and rep["rejected"] == 0
    assert sum(rep["per_tenant"].values()) == 18
    assert set(rep["per_tenant"].values()) == {6}
    assert rep["p50_ms"] is not None and rep["p99_ms"] is not None
    assert rep["achieved_qps_rows"] > 0
    assert rep["offered_qps_total"] == pytest.approx(180.0)


def test_mutation_seq_gap_is_409_and_refusals_consume_position(served):
    """The gapless-mark wire contract on the REAL serve front end: a
    seq past applied+1 is refused 409 (nothing applied, mark
    unchanged), a deterministic 400 refusal CONSUMES its in-order seq
    (the stream has no skip marker — an unconsumed position would 409
    every later seq forever), and the next in-order seq applies."""
    srv, _fe, _index = served
    with urllib.request.urlopen(srv.url + "/healthz", timeout=30) as r:
        a0 = json.loads(r.read())["applied_seq"]
    hdr = {"Content-Type": "application/json"}
    row = json.dumps({"ids": [9001], "rows": [[0.0] * DIM]}).encode()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(srv.url, "/upsert", row,
              {**hdr, "X-Mutation-Seq": str(a0 + 5)})
    assert ei.value.code == 409
    assert json.loads(ei.value.read())["error"] == "seq-gap"
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(srv.url, "/upsert", b"not json",
              {**hdr, "X-Mutation-Seq": str(a0 + 1)})
    assert ei.value.code == 400
    assert json.loads(ei.value.read())["applied_seq"] == a0 + 1
    with urllib.request.urlopen(srv.url + "/healthz", timeout=30) as r:
        assert json.loads(r.read())["applied_seq"] == a0 + 1
    status, doc = _post(srv.url, "/delete",
                        json.dumps({"ids": [3]}).encode(),
                        {**hdr, "X-Mutation-Seq": str(a0 + 2)})
    assert status == 200 and doc["applied_seq"] == a0 + 2


# ---------------------------------------------------------------------------
# ISSUE 26: the serving path's spans on /metrics and in the flight record

_PHASES = ("idle", "hold", "coalesce", "prep", "enqueue", "wait", "d2h",
           "reply", "other")


def _scrape(url):
    with urllib.request.urlopen(url + "/metrics", timeout=10) as resp:
        return parse_prometheus(resp.read().decode())


def _phase_seconds(samples):
    return {p: samples.get(
        f'serve_batch_phase_seconds_total{{phase="{p}"}}', 0.0)
        for p in _PHASES}


def test_served_batch_moves_every_new_sample(served):
    """One request through HTTP -> coalescer -> engine moves the samples
    the benchmark's new per-layer metrics read: a queue wait and a
    request duration per request, the padded height of the batch beside
    its real rows, and a positive time in every phase of the pump."""
    srv, fe, _ = served
    # let the pump reach its idle wait, so "idle" moves in the window too
    import time

    time.sleep(0.12)
    before = _scrape(srv.url)
    q = np.ones((5, DIM), dtype="<f4")
    for _ in range(3):  # one at a time: three batches of 5 rows in 64
        status, _ = _post(
            srv.url, "/query", q.tobytes(),
            {"Content-Type": "application/octet-stream", "X-Tenant": "t26"},
        )
        assert status == 200
    time.sleep(0.12)
    after = _scrape(srv.url)

    def moved(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    assert moved("frontend_queue_wait_seconds_count") == 3
    assert moved("frontend_queue_wait_seconds_sum") > 0.0
    assert moved("frontend_request_seconds_count") == 3
    # the handler's span holds the queue wait of its request
    assert (moved("frontend_request_seconds_sum")
            > moved("frontend_queue_wait_seconds_sum"))
    assert moved("serve_batches_total") == 3
    assert moved("serve_queries_total") == 15
    assert moved("serve_padded_rows_total") == 3 * 64  # the bucket height
    b, a = _phase_seconds(before), _phase_seconds(after)
    for p in _PHASES:
        assert a[p] - b[p] > 0.0, f"phase {p} did not move"


def test_pump_phases_add_up_to_the_pump_threads_wall_time(served):
    """The phases are a partition of the pump thread's time by
    construction: what no span of a loop turn covered is ``other``,
    however large a loaded host makes it. Over a window with traffic
    their seconds sum to the window's length, but for the turn that each
    of the window's two ends cuts: an idle wait of at most 50 ms."""
    import time

    srv, fe, _ = served
    time.sleep(0.12)  # the pump in its idle waits at both ends
    reg_before = _phase_seconds(_scrape(srv.url))
    t0 = time.perf_counter()
    q = np.ones((3, DIM), np.float32)
    while time.perf_counter() - t0 < 2.0:
        fe.submit("t26w", q).result(timeout=30)
        time.sleep(0.01)
    time.sleep(0.12)
    wall = time.perf_counter() - t0
    reg_after = _phase_seconds(_scrape(srv.url))
    total = sum(reg_after[p] - reg_before[p] for p in _PHASES)
    assert abs(total - wall) <= 2 * 0.05, (total, wall)


def test_flight_spans_join_request_to_batch_to_phases(tmp_path):
    """Under a recorder: the handler's ``request`` span carries the
    request's seq, the pump's ``coalesce`` span lists it beside the batch
    seq it formed, and every phase of that batch names the engine's
    ``batch`` span as its parent and carries the batch seq."""
    from mpi_knn_tpu.obs.spans import (
        FlightRecorder,
        read_flight,
        reconstruct_spans,
        set_recorder,
        validate_flight,
    )

    rng = np.random.default_rng(1)
    X = rng.normal(size=(256, DIM)).astype(np.float32)
    index = build_index(
        X, KNNConfig(k=4, backend="serial", query_bucket=16,
                     corpus_tile=128, query_tile=16),
    )
    flight = tmp_path / "flight.jsonl"
    set_recorder(FlightRecorder(str(flight), fresh=True))
    try:
        fe = Frontend(
            ServeSession(index, resilience=ResiliencePolicy()),
            SLOPolicy(max_batch_rows=16, max_wait_s=0.002,
                      max_queue_rows=1024),
        ).start()
        srv = FrontendHTTPServer(fe, port=0).start()
        try:
            status, _ = _post(
                srv.url, "/query", X[:4].astype("<f4").tobytes(),
                {"Content-Type": "application/octet-stream",
                 "X-Tenant": "joined"},
            )
            assert status == 200
        finally:
            srv.stop()
            fe.stop()
    finally:
        set_recorder(None)
    records = read_flight(str(flight))
    assert validate_flight(records) == []
    spans, _ = reconstruct_spans(records)

    def named(cat, name):
        return [s for s in spans if (s["cat"], s["name"]) == (cat, name)]

    (request,) = named("http", "request")
    rseq = request["end_attrs"]["seq"]
    assert request["end_attrs"]["status"] == 200
    (coalesce,) = [s for s in named("pump", "coalesce")
                   if rseq in (s["end_attrs"].get("request_seqs") or ())]
    bseq = coalesce["end_attrs"]["seq"]
    (batch,) = [s for s in named("serve", "batch")
                if s["attrs"]["seq"] == bseq]
    for phase in ("prep", "enqueue", "wait", "d2h", "reply"):
        mine = [s for s in named("batch", phase) if s["attrs"]["seq"] == bseq]
        assert mine, f"no {phase} span for batch {bseq}"
        assert all(s["parent"] == batch["span"] for s in mine)
    assert not named("pump", "idle")  # kept out of the flight record


# ---------------------------------------------------------------------------
# ISSUE 34: a write's body in the form /query already takes


@pytest.fixture(scope="module")
def writable():
    """(server, index): a server over an index built with headroom."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(512, DIM)).astype(np.float32)
    index = build_index(X, KNNConfig(
        k=4, backend="serial", query_bucket=64, corpus_tile=128,
        query_tile=64, bucket_headroom=0.25, mutation_bucket=32,
        exclude_zero=False))
    fe = Frontend(ServeSession(index, resilience=ResiliencePolicy()),
                  SLOPolicy(max_batch_rows=64, max_wait_s=0.002,
                            max_queue_rows=8192)).start()
    srv = FrontendHTTPServer(fe, port=0).start()
    yield srv, index
    srv.stop()
    fe.stop()


RAW = {"Content-Type": "application/octet-stream", "X-Tenant": "raw-writer"}
JSON = {"Content-Type": "application/json", "X-Tenant": "json-writer"}


def test_raw_write_bodies_equal_the_json_ones(writable):
    """The same rows under two id ranges, one range written raw (int32 ids,
    then float32 rows at the index width) and one as JSON: the same
    acknowledgement, the same answers distance for distance; then both
    ranges deleted, one raw and one as JSON, and neither comes back."""
    srv, index = writable
    rng = np.random.default_rng(9)
    rows = (rng.normal(size=(8, DIM)) + 7.0).astype("<f4")
    raw_ids = np.arange(5000, 5008, dtype="<i4")
    json_ids = np.arange(6000, 6008)
    s1, d1 = _post(srv.url, "/upsert",
                   raw_ids.tobytes() + rows.tobytes(), RAW)
    s2, d2 = _post(srv.url, "/upsert", json.dumps(
        {"ids": json_ids.tolist(), "rows": rows.tolist()}).encode(), JSON)
    assert s1 == s2 == 200 and d1["upserted"] == d2["upserted"] == 8
    assert d2["live"] == d1["live"] + 8
    _, doc = _post(srv.url, "/query", rows.tobytes(), RAW)
    ids, dists = np.asarray(doc["ids"]), np.asarray(doc["dists"])
    # each row's two nearest are its two copies, at the same distance
    assert (np.sort(ids[:, :2], axis=1)
            == np.stack([raw_ids, json_ids], axis=1)).all()
    assert (dists[:, 0] == dists[:, 1]).all()
    s1, d1 = _post(srv.url, "/delete", raw_ids.tobytes(), RAW)
    s2, d2 = _post(srv.url, "/delete",
                   json.dumps({"ids": json_ids.tolist()}).encode(), JSON)
    assert s1 == s2 == 200 and d1["deleted"] == d2["deleted"] == 8
    assert d1["missing"] == d2["missing"] == 0
    _, doc = _post(srv.url, "/query", rows.tobytes(), RAW)
    assert not (np.asarray(doc["ids"]) >= 5000).any()
    samples = _scrape(srv.url)
    for phase in ("parse", "plan", "h2d", "dispatch", "commit"):
        assert samples[
            f'mutation_phase_seconds_total{{phase="{phase}"}}'] > 0
    assert samples['mutation_lock_waits_total{side="mutation"}'] >= 4
    assert samples['mutation_lock_waits_total{side="batch"}'] >= 2


@pytest.mark.parametrize("path,body,why", [
    ("/upsert", b"\x00" * (4 + 4 * DIM + 3), "not a whole number"),
    ("/upsert", np.arange(3, dtype="<i4").tobytes()
     + np.zeros((3, DIM + 1), "<f4").tobytes(), "not a whole number"),
    ("/upsert", np.array([-1], "<i4").tobytes()
     + np.zeros((1, DIM), "<f4").tobytes(), "must be >= 0"),
    ("/delete", b"\x00" * 6, "not a whole number"),
    ("/upsert", b"", "empty"),
])
def test_raw_write_refusals_are_400(writable, path, body, why):
    """Wrong length, wrong width (a body that is no whole number of id +
    row records), id -1, an empty body: 400 with the reason, nothing
    written."""
    srv, index = writable
    live = index.live_rows
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(srv.url, path, body, RAW)
    assert ei.value.code == 400
    assert why in json.loads(ei.value.read())["error"]
    assert index.live_rows == live


def test_one_parser_for_the_raw_form():
    from mpi_knn_tpu.frontend.server import raw_rows

    rows = np.arange(12, dtype="<f4").reshape(3, 4)
    ids = np.array([7, 8, 9], "<i4")
    got_ids, got = raw_rows(ids.tobytes() + rows.tobytes(), 4, ids=True)
    assert (got_ids == ids).all() and (got == rows).all()
    none, got = raw_rows(rows.tobytes(), 4, ids=False)
    assert none is None and (got == rows).all()
    with pytest.raises(ValueError):
        raw_rows(rows.tobytes(), 4, ids=True)  # 48 bytes, 20 a record


# ---------------------------------------------------------------------------
# ISSUE 36: the request's life, the dispatch lag and the server's occupancy
# in the program's own clock — on injected clocks, so nothing here asserts a
# wall-clock tolerance

_REQUEST_PHASES = ("read", "admit", "await", "wake", "encode", "write")


class _Ticking:
    """A clock that moves 1/1024 s at every reading, from any thread:
    every duration is a whole number of ticks, so sums are exact."""

    def __init__(self):
        import itertools

        self._reads = itertools.count(1)

    def __call__(self) -> float:
        return next(self._reads) / 1024.0


class _Set:
    """A clock that reads what the test sets."""

    t = 0.0

    def __call__(self) -> float:
        return self.t


def _tiny_index():
    rng = np.random.default_rng(36)
    X = rng.normal(size=(256, DIM)).astype(np.float32)
    return X, build_index(
        X, KNNConfig(k=4, backend="serial", query_bucket=16,
                     corpus_tile=128, query_tile=16))


def _moved(before, after, name):
    return after.get(name, 0.0) - before.get(name, 0.0)


def _phase_sample(phase, route="query"):
    return ('frontend_request_phase_seconds_total'
            f'{{phase="{phase}",route="{route}"}}')


@pytest.fixture(scope="module")
def phased(tmp_path_factory):
    """One /query through a server on a ticking clock, under a flight
    recorder: ``(flight records, /metrics before, /metrics after)``."""
    from mpi_knn_tpu.obs.spans import (
        FlightRecorder,
        read_flight,
        set_recorder,
    )

    X, index = _tiny_index()
    flight = tmp_path_factory.mktemp("phased") / "flight.jsonl"
    set_recorder(FlightRecorder(str(flight), fresh=True))
    try:
        fe = Frontend(
            ServeSession(index, resilience=ResiliencePolicy()),
            SLOPolicy(max_batch_rows=16, max_wait_s=0.002,
                      max_queue_rows=1024),
            clock=_Ticking(),
        ).start()
        srv = FrontendHTTPServer(fe, port=0).start()
        try:
            before = _scrape(srv.url)
            status, _ = _post(
                srv.url, "/query", X[:4].astype("<f4").tobytes(),
                {"Content-Type": "application/octet-stream",
                 "X-Tenant": "phased"},
            )
            assert status == 200
            after = _scrape(srv.url)
        finally:
            srv.stop()
            fe.stop()
    finally:
        set_recorder(None)
    return read_flight(str(flight)), before, after


def test_six_request_phases_sum_to_the_request_span(phased):
    """The phases come from consecutive readings of one clock: on
    /metrics they add up to ``frontend_request_seconds_sum``, in the
    flight record to the ``request`` span's own ``dur_s``, to the tick."""
    from mpi_knn_tpu.obs.spans import reconstruct_spans

    records, before, after = phased
    assert _moved(before, after, "frontend_request_seconds_count") == 1
    by_phase = {p: _moved(before, after, _phase_sample(p))
                for p in _REQUEST_PHASES}
    assert all(v > 0.0 for v in by_phase.values()), by_phase
    # the registry's counters hold other tests' seconds too: exact to the
    # rounding of their sums
    assert sum(by_phase.values()) == pytest.approx(
        _moved(before, after, "frontend_request_seconds_sum"), abs=1e-9)
    spans, _ = reconstruct_spans(records)
    (request,) = [s for s in spans
                  if (s["cat"], s["name"]) == ("http", "request")]
    children = [s for s in spans if s["parent"] == request["span"]]
    assert sum(s["dur_s"] for s in children) == request["dur_s"]
    assert request["dur_s"] * 1024 == round(request["dur_s"] * 1024)


def test_request_phases_are_children_of_the_request_carrying_its_seq(phased):
    from mpi_knn_tpu.obs.spans import reconstruct_spans, validate_flight

    records, _, _ = phased
    assert validate_flight(records) == []
    spans, _ = reconstruct_spans(records)
    (request,) = [s for s in spans
                  if (s["cat"], s["name"]) == ("http", "request")]
    seq = request["end_attrs"]["seq"]
    by_name = {s["name"]: s for s in spans
               if s["cat"] == "http" and s["name"] != "request"}
    assert tuple(by_name) == _REQUEST_PHASES  # in the order they ran
    for name, s in by_name.items():
        assert s["parent"] == request["span"], name
        assert s["dur_s"] > 0.0, name
    for name in _REQUEST_PHASES[2:]:  # await onwards
        assert by_name[name]["attrs"]["seq"] == seq, name
    assert "seq" not in by_name["read"]["attrs"]
    # the join to the batch stays as it is
    (coalesce,) = [s for s in spans if (s["cat"], s["name"])
                   == ("pump", "coalesce") and s["end_attrs"].get("rows")]
    assert coalesce["end_attrs"]["request_seqs"] == [seq]
    assert coalesce["end_attrs"]["lag_ms"] >= 0.0
    # the drain that retired the batch is in the record; the holds are not
    assert [s for s in spans if (s["cat"], s["name"]) == ("pump", "drain")]
    assert not [s for s in spans if s["name"] in ("hold", "idle")]


def test_occupancy_covers_the_requests_of_every_serving_route(phased,
                                                              writable):
    """``occupied`` holds the whole handler of a request, so at least its
    span; a write is inside the server too, and has the phases a write
    has, under its route."""
    _, before, after = phased
    occupied = 'frontend_occupancy_seconds_total{state="occupied"}'
    assert (_moved(before, after, occupied)
            >= _moved(before, after, "frontend_request_seconds_sum") > 0.0)
    assert _moved(
        before, after, 'frontend_occupancy_seconds_total{state="empty"}') > 0

    srv, _ = writable
    before = _scrape(srv.url)
    ids = np.arange(7000, 7004, dtype="<i4")
    rows = np.full((4, DIM), 3.0, "<f4")
    assert _post(srv.url, "/upsert", ids.tobytes() + rows.tobytes(),
                 RAW)[0] == 200
    assert _post(srv.url, "/delete", ids.tobytes(), RAW)[0] == 200
    after = _scrape(srv.url)
    assert _moved(before, after, occupied) > 0.0
    for route in ("upsert", "delete"):
        for phase in ("read", "encode", "write"):
            assert _moved(before, after, _phase_sample(phase, route)) > 0.0
        assert _phase_sample("await", route) not in after
    assert _moved(before, after, "frontend_request_seconds_count") == 0


def test_empty_plus_occupied_is_the_elapsed_clock_overlaps_counted_once():
    """Two requests overlap on two threads: the server is occupied from
    the first one's entry to the last one's exit, once; the two states
    add up to the clock's movement since the counter was made."""
    import threading

    from mpi_knn_tpu.frontend.server import Occupancy
    from mpi_knn_tpu.obs.metrics import get_registry

    def value(state):
        return get_registry().counter(
            "frontend_occupancy_seconds_total",
            labels={"state": state}).value

    clock = _Set()
    clock.t = 100.0
    occ = Occupancy(clock)
    empty0, occupied0 = value("empty"), value("occupied")
    second_in, first_out = threading.Event(), threading.Event()

    def second():
        clock.t = 101.5
        with occ:
            second_in.set()
            assert first_out.wait(10)
            clock.t = 103.0

    clock.t = 101.0
    t = threading.Thread(target=second)
    with occ:
        t.start()
        assert second_in.wait(10)
        clock.t = 102.0
    first_out.set()
    t.join(10)
    assert not t.is_alive()
    clock.t = 103.25
    occ.settle()
    assert value("empty") - empty0 == 1.0 + 0.25
    assert value("occupied") - occupied0 == 2.0
    clock.t = 104.0
    occ.settle()
    occ.settle()  # a reading with nothing to hand over
    assert (value("empty") - empty0) + (value("occupied") - occupied0) \
        == clock.t - 100.0


@pytest.mark.parametrize("late_s", [0.0, 0.005])
def test_dispatch_lag_is_the_pumps_clock_less_the_batchs_ripeness(late_s):
    """A batch handed over at its deadline has no lag; one the pump got to
    5 ms late has 5 ms, once a batch, and says so on its coalesce span's
    histogram beside the queue wait (policy + lag)."""
    from mpi_knn_tpu.obs.metrics import get_registry

    X, index = _tiny_index()
    clock = _Set()
    fe = Frontend(  # never started: the test is the pump
        ServeSession(index, resilience=ResiliencePolicy()),
        SLOPolicy(max_batch_rows=16, max_wait_s=0.010, max_queue_rows=1024),
        clock=clock,
    )
    fe.session.warm([16])  # its one bucket: admission does not say "warming"
    lag = get_registry().histogram("frontend_dispatch_lag_seconds")
    waited = get_registry().histogram("frontend_queue_wait_seconds")
    n0, lag0, wait0 = lag.count, lag.sum, waited.sum
    clock.t = 1.0
    ticket = fe.submit("lagging", X[:3])
    clock.t = 1.010 + late_s
    (batch,) = fe.scheduler.poll(clock())
    assert batch.reason == "deadline"
    fe._dispatch(batch)
    for res in fe.session.drain():
        fe._scatter(res)
    assert ticket.done() and ticket.done_s == clock.t
    assert lag.count - n0 == 1
    assert lag.sum - lag0 == pytest.approx(late_s, abs=1e-9)
    assert waited.sum - wait0 == pytest.approx(0.010 + late_s, abs=1e-9)


# ---------------------------------------------------------------------------
# ISSUE 39: a predicate on every query row — the request forms of an index
# built with tags, what is refused, and the pump's phases with ``plan``

TAG_DIM, TAG_ROWS = 24, 2048


@pytest.fixture(scope="module")
def tagged():
    """(server, frontend, corpus, membership): a server over an index
    built with tags — tag 0 on a third of the rows (frequent), tag 1 on
    forty (rare), tag 2 on three (fewer than k)."""
    rng = np.random.default_rng(39)
    X = rng.integers(0, 255, size=(TAG_ROWS, TAG_DIM)).astype(np.float32)
    member = np.zeros((TAG_ROWS, 3), dtype=bool)
    member[:, 0] = rng.random(TAG_ROWS) < 0.33
    member[rng.choice(TAG_ROWS, 40, replace=False), 1] = True
    member[rng.choice(TAG_ROWS, 3, replace=False), 2] = True
    rows, tag = np.nonzero(member)
    indptr = np.zeros(TAG_ROWS + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=TAG_ROWS), out=indptr[1:])
    index = build_index(
        X, KNNConfig(k=4, backend="serial", query_bucket=64,
                     corpus_tile=1024, query_tile=64, exclude_zero=False),
        tags=(indptr, tag.astype(np.int32)))
    fe = Frontend(
        ServeSession(index, resilience=ResiliencePolicy()),
        SLOPolicy(max_batch_rows=64, max_wait_s=0.002, max_queue_rows=8192),
    ).start()
    srv = FrontendHTTPServer(fe, port=0).start()
    yield srv, fe, X, member
    srv.stop()
    fe.stop()


def _nearest(X, member, q, tags, k=4):
    ok = np.ones(TAG_ROWS, dtype=bool)
    for t in tags:
        ok &= member[:, t] if 0 <= t < 3 else False
    d = ((X.astype(np.float64) - q) ** 2).sum(1)
    order = np.argsort(np.where(ok, d, np.inf), kind="stable")[:k]
    return [int(i) if ok[i] else -1 for i in order]


def test_raw_and_json_filters_answer_alike(tagged):
    srv, _, X, member = tagged
    q = X[[5, 6, 7, 8, 9]] + 1.0
    lists = [[0], [0, 1], [], [2], [999]]
    status, doc = _post(
        srv.url, "/query",
        json.dumps({"queries": q.tolist(), "filters": lists}).encode(),
        {"Content-Type": "application/json"})
    assert status == 200
    for row, tags in enumerate(lists):
        want = _nearest(X, member, q[row], tags)
        got = doc["ids"][row]
        ties = [d for d in doc["dists"][row]]
        assert got == want or sorted(ties) == ties, (row, got, want)
        assert sum(i >= 0 for i in got) == sum(i >= 0 for i in want)
    # fewer than k matches: the rows, then empty slots (-1, Infinity)
    assert doc["ids"][3][3] == -1 and doc["dists"][3][3] == float("inf")
    assert doc["ids"][4] == [-1] * 4  # an unknown tag matches nothing
    wide = np.full((5, 2), -1, dtype="<i4")
    for row, tags in enumerate(lists):
        wide[row, :len(tags)] = tags
    status, raw = _post(
        srv.url, "/query", q.astype("<f4").tobytes() + wide.tobytes(),
        {"Content-Type": "application/octet-stream", "X-Filter-Tags": "2"})
    assert status == 200 and raw["ids"] == doc["ids"]
    # without the header the body parses as it always did: rows alone
    status, plain = _post(
        srv.url, "/query", q.astype("<f4").tobytes(),
        {"Content-Type": "application/octet-stream"})
    assert status == 200 and plain["ids"][2] == doc["ids"][2]
    assert plain["ids"][4] != [-1] * 4


@pytest.mark.parametrize("body, headers, why", [
    (json.dumps({"queries": [[0.0] * TAG_DIM], "filters": [[0, 1, 2]]}),
     {}, "max_query_tags"),
    (json.dumps({"queries": [[0.0] * TAG_DIM], "filters": [[0], [1]]}),
     {}, "one list"),
    (json.dumps({"queries": [[0.0] * TAG_DIM], "filters": [[-3]]}),
     {}, "whole numbers"),
    (json.dumps({"queries": [[0.0] * TAG_DIM], "filters": [[0.5]]}),
     {}, "whole numbers"),
    (json.dumps({"queries": [[0.0] * TAG_DIM], "filters": "0"}),
     {}, "one list"),
    (b"\x00" * (4 * TAG_DIM + 8), {"X-Filter-Tags": "3"}, "whole number of"),
    (b"\x00" * (4 * TAG_DIM + 4), {"X-Filter-Tags": "x"}, "tag count"),
    (np.zeros(TAG_DIM, "<f4").tobytes() + np.array([-2], "<i4").tobytes(),
     {"X-Filter-Tags": "1"}, ">= 0"),
], ids=["too-many", "rows-mismatch", "negative", "fraction", "not-lists",
        "raw-length", "raw-header", "raw-negative"])
def test_malformed_filters_are_400(tagged, body, headers, why):
    srv, _, _, _ = tagged
    raw = isinstance(body, bytes)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(srv.url, "/query", body if raw else body.encode(),
              {"Content-Type": "application/octet-stream" if raw
               else "application/json", **headers})
    assert ei.value.code == 400
    assert why in json.loads(ei.value.read())["error"]


def test_a_filter_an_index_cannot_honour_is_400_never_ignored(served):
    srv, _, _ = served
    q = np.zeros((1, DIM), "<f4")
    for data, headers in (
        (json.dumps({"queries": q.tolist(), "filters": [[1]]}).encode(),
         {"Content-Type": "application/json"}),
        (q.tobytes() + np.array([1], "<i4").tobytes(),
         {"Content-Type": "application/octet-stream",
          "X-Filter-Tags": "1"}),
    ):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.url, "/query", data, headers)
        assert ei.value.code == 400
        assert "built without tags" in json.loads(ei.value.read())["error"]


@pytest.mark.parametrize("path, body", [
    ("/upsert", {"ids": [1], "rows": [[0.0] * TAG_DIM]}),
    ("/delete", {"ids": [1]}),
])
def test_writes_to_a_tagged_index_are_refused(tagged, path, body):
    srv, _, _, _ = tagged
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(srv.url, path, json.dumps(body).encode(),
              {"Content-Type": "application/json"})
    assert ei.value.code == 400
    assert "built with tags is frozen" in json.loads(
        ei.value.read())["error"]


def test_the_raw_parser_takes_tags_after_the_rows():
    from mpi_knn_tpu.frontend.server import raw_rows

    rows = np.arange(12, dtype="<f4").reshape(3, 4)
    tags = np.array([[1, -1], [2, 3], [-1, -1]], "<i4")
    none, got, f = raw_rows(rows.tobytes() + tags.tobytes(), 4, ids=False,
                            tags=2)
    assert none is None and (got == rows).all() and (f == tags).all()
    with pytest.raises(ValueError):
        raw_rows(rows.tobytes() + tags.tobytes(), 4, ids=False, tags=3)
