"""Unified observability layer (ISSUE 7): the metrics registry, the span
flight recorder, the xplane wire-format parser, device-time attribution,
the `mpi-knn metrics` CLI — and the three acceptance criteria:

(a) a ServeSession run proves ZERO steady-state compiles through the
    SHARED registry (the invariant test_serve/test_ivf/test_resilience
    assert via the same `watch_compiles` scope);
(b) the flight-recorder JSONL reconstructs every batch's dispatch→retire
    interval and every retry/rung event, and SURVIVES a SIGKILL of the
    worker mid-stream (the supervisor recovers and banks the partial
    record — an OPEN batch span in the file IS the kill diagnosis);
(c) a profiled run's per-category device-time split sums to the reported
    busy total (every event carries exactly one category — a split that
    sums past the total is a parser bug, not a measurement).

The xplane parser gets its own unit tests over HAND-BUILT protobuf wire
fixtures (empty plane, multi-line, unknown-field skip, truncated varint):
before ISSUE 7 the parser lived untested in scripts/trace_ops.py, where a
silent misparse would have corrupted every attribution number downstream.
"""

import gzip
import json
import math
import os
import textwrap

import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, build_index
from mpi_knn_tpu.obs.attribution import attribute_trace, pick_device_plane
from mpi_knn_tpu.obs.metrics import (
    COMPILE_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    load_snapshot,
    parse_prometheus,
    watch_compiles,
)
from mpi_knn_tpu.obs.spans import (
    FlightRecorder,
    read_flight,
    reconstruct_spans,
    set_recorder,
    summarize_flight,
    to_chrome_trace,
    validate_flight,
)
from mpi_knn_tpu.obs.xplane import (
    ParseError,
    analyze,
    categorize,
    parse_xplane,
    parse_xplane_bytes,
)
from mpi_knn_tpu.resilience import (
    ResiliencePolicy,
    install_faults,
    run_supervised,
)
from mpi_knn_tpu.resilience.ladder import FULL_RUNG
from mpi_knn_tpu.resilience.worker import python_worker_argv
from mpi_knn_tpu.serve import ServeSession


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """A test that installs a process recorder must never leak it into
    the next test's serve calls (the span helpers are process-global)."""
    yield
    set_recorder(None)


def _cfg(**kw):
    kw.setdefault("k", 4)
    kw.setdefault("query_tile", 16)
    kw.setdefault("corpus_tile", 32)
    kw.setdefault("query_bucket", 16)
    kw.setdefault("dispatch_depth", 1)
    return KNNConfig(backend="serial", **kw)


# ---------------------------------------------------------------------------
# metrics: counters / gauges / deterministic fixed-bucket histograms


def test_counter_monotonic_rejects_bad_increments():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(ValueError):
        c.inc(math.nan)


def test_gauge_set_add_rejects_nonfinite():
    g = Gauge("g")
    g.set(4.0)
    g.add(-1.5)
    assert g.value == 2.5
    with pytest.raises(ValueError):
        g.set(math.inf)
    with pytest.raises(ValueError):
        g.add(math.nan)


def test_histogram_percentiles_are_deterministic_bucket_bounds():
    """The assertable-percentile contract: the quantile's bucket UPPER
    BOUND, a pure function of the counts — never an interpolation."""
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 9.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 14.0
    assert h.percentile(25) == 1.0
    assert h.percentile(50) == 2.0
    assert h.percentile(75) == 4.0
    assert h.percentile(99) == math.inf  # the 9.0 overflow observation
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_empty_overflow_and_validation():
    h = Histogram("h", buckets=(1.0,))
    assert math.isnan(h.percentile(50))
    with pytest.raises(ValueError):
        h.observe(math.nan)  # a NaN latency is an upstream bug, loudly
    with pytest.raises(ValueError):
        Histogram("bad", buckets=())
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(1.0, 1.0))


def test_registry_get_or_create_and_kind_collision():
    reg = MetricsRegistry()
    c = reg.counter("x", help="first")
    assert reg.counter("x") is c  # get-or-create identity
    with pytest.raises(ValueError):
        reg.gauge("x")  # name re-requested with a different kind
    reg.histogram("lat", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("lat", buckets=(1.0, 3.0))  # different buckets


def test_prometheus_exposition_roundtrips_through_strict_parser():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests").inc(3)
    reg.gauge("rung").set(1)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    samples = parse_prometheus(text)
    assert samples["req_total"] == 3.0
    assert samples["rung"] == 1.0
    assert samples['lat_seconds_bucket{le="0.1"}'] == 1.0
    assert samples['lat_seconds_bucket{le="1.0"}'] == 2.0
    assert samples['lat_seconds_bucket{le="+Inf"}'] == 3.0
    assert samples["lat_seconds_count"] == 3.0
    assert samples["lat_seconds_sum"] == pytest.approx(5.55)


def test_parse_prometheus_rejects_malformed():
    for bad in (
        "",  # no samples at all
        "9leading_digit 1",
        "name&bad 1",
        "name not-a-number",
        "dup 1\ndup 2",
        'unterminated{le="x 1',
    ):
        with pytest.raises(ValueError):
            parse_prometheus(bad)


def test_load_snapshot_rejects_non_snapshot_json(tmp_path):
    p = tmp_path / "not-metrics.json"
    p.write_text(json.dumps({"hello": "world"}))
    with pytest.raises(ValueError):
        load_snapshot(str(p))
    reg = MetricsRegistry()
    reg.counter("ok").inc()
    p2 = tmp_path / "snap.json"
    p2.write_text(json.dumps(reg.snapshot()))
    assert "ok" in load_snapshot(str(p2))["metrics"]


def test_watch_compiles_counts_and_feeds_shared_registry():
    """The dedup target: the one scope behind every 'cache hit compiled
    nothing' assertion, AND the same events land in the process-wide
    registry's jax_compiles_total."""
    import jax
    import jax.numpy as jnp

    before = get_registry().counter("jax_compiles_total").value
    with watch_compiles() as counts:
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((3, 7)))
    assert len(counts) >= 1
    assert get_registry().counter("jax_compiles_total").value \
        >= before + len(counts)
    # the duration histogram recorded the same compiles
    assert get_registry().histogram(
        "jax_compile_seconds", buckets=COMPILE_BUCKETS_S
    ).count >= 1


# ---------------------------------------------------------------------------
# span flight recorder


def test_recorder_roundtrip_nesting_and_clean_validation(tmp_path):
    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    with rec.span("outer", cat="serve", a=1) as outer_id:
        with rec.span("inner", cat="serve"):
            rec.event("tick", cat="heartbeat", label="x")
    rec.close()
    records = read_flight(path)
    assert validate_flight(records) == []
    spans, events = reconstruct_spans(records)
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent"] == outer_id  # stack-derived nesting
    assert by_name["outer"]["parent"] is None
    assert all(s["dur_s"] is not None and s["dur_s"] >= 0 for s in spans)
    assert events[0]["name"] == "tick"


def test_open_span_is_the_kill_diagnosis(tmp_path):
    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    rec.begin("batch", cat="serve", seq=7)
    # no end: the process "died" here
    rec.close()
    summary = summarize_flight(read_flight(path))
    assert summary["spans_complete"] == 0
    assert summary["open_spans"] == [
        {"name": "batch", "cat": "serve", "attrs": {"seq": 7}}
    ]
    # Chrome export renders the dangling span as a B event
    trace = to_chrome_trace(read_flight(path))
    assert [e["ph"] for e in trace["traceEvents"]] == ["B"]


def test_validate_flight_catches_corruption():
    """Exactly the corruption classes the CI gate must refuse: NaN and
    negative durations, ends without opens, unknown parents, duplicate
    ids, unknown record kinds, unparseable interior lines."""
    ok_b = {"ev": "B", "span": 1, "parent": None, "name": "a", "cat": "",
            "ts": 1.0, "pid": 1, "tid": 1}
    cases = [
        ([{"ev": "Z", "ts": 1.0}], "unknown ev"),
        ([{"ev": "B", "span": 1, "name": "a", "ts": -5.0, "pid": 1}],
         "bad ts"),
        ([ok_b, {"ev": "E", "span": 1, "ts": 2.0, "dur_s": -0.1}],
         "bad dur_s"),
        ([ok_b, {"ev": "E", "span": 1, "ts": 2.0, "dur_s": math.nan}],
         "bad dur_s"),
        ([{"ev": "E", "span": 9, "ts": 1.0, "dur_s": 0.1}], "not open"),
        ([{"ev": "B", "span": 2, "parent": 99, "name": "b", "ts": 1.0,
           "pid": 1}], "never began"),
        ([ok_b, dict(ok_b)], "duplicate span id"),
        ([{"ev": "I", "cat": "", "ts": 1.0, "pid": 1}], "without name"),
        ([{"ev": "?", "raw": "garbage"}], "unparseable"),
    ]
    for records, needle in cases:
        problems = validate_flight(records)
        assert problems and any(needle in p for p in problems), (
            records, needle, problems,
        )
    assert validate_flight(
        [ok_b, {"ev": "E", "span": 1, "ts": 2.0, "dur_s": 0.5}]
    ) == []


def test_ring_rotation_bounds_disk_and_keeps_recent_history(tmp_path):
    path = str(tmp_path / "ring.jsonl")
    rec = FlightRecorder(path, max_bytes=4096)
    for i in range(120):
        rec.event("e", cat="bench", i=i, pad="x" * 64)
    rec.close()
    assert os.path.exists(path) and os.path.exists(path + ".1")
    # bounded at ~2 generations of max_bytes
    assert os.path.getsize(path) <= 4096
    assert os.path.getsize(path + ".1") <= 4096
    records = read_flight(path)
    # previous generation first, newest record last; rotation is one
    # generation deep so the oldest events fell off
    idx = [r["attrs"]["i"] for r in records if r.get("ev") == "I"]
    assert idx == sorted(idx) and idx[-1] == 119 and idx[0] > 0
    with pytest.raises(ValueError):
        FlightRecorder(str(tmp_path / "tiny"), max_bytes=100)


def test_read_flight_torn_tail_skipped_interior_garbage_reported(tmp_path):
    p = tmp_path / "f.jsonl"
    p.write_text(
        '{"ev":"I","name":"a","cat":"","ts":1.0,"pid":1}\n'
        "interior-garbage\n"
        '{"ev":"I","name":"b","cat":"","ts":2.0,"pid":1}\n'
        '{"ev":"B","span":3,"name":"torn-by-the-ki'  # SIGKILL mid-write
    )
    records = read_flight(str(p))
    # the torn TAIL is the one line a kill legitimately produces: skipped
    assert [r.get("name") for r in records if r.get("ev") == "I"] == \
        ["a", "b"]
    # interior garbage is impossible under write+flush: kept and REPORTED
    assert any(r.get("ev") == "?" for r in records)
    assert any("unparseable" in pb for pb in validate_flight(records))


def test_span_helpers_noop_without_recorder_env_arms_them(
    tmp_path, monkeypatch
):
    from mpi_knn_tpu.obs import spans as spans_mod

    monkeypatch.delenv(spans_mod.RECORDER_ENV, raising=False)
    assert spans_mod.get_recorder() is None
    spans_mod.event("nothing")  # must not write anywhere / crash
    h = spans_mod.begin_span("x")  # jax is loaded: an inert annotation
    assert h is None or h.sid is None  # nothing for a flight record
    spans_mod.end_span(h)
    spans_mod.end_span(None)

    # the env var is read at import and by get_recorder/set_recorder,
    # never per span: a process that sets it late re-arms explicitly
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv(spans_mod.RECORDER_ENV, path)
    assert spans_mod.get_recorder().path == path
    with spans_mod.span("from-env", cat="bench"):
        pass
    spans_mod.get_recorder().close()
    names = [s["name"] for s in reconstruct_spans(read_flight(path))[0]]
    assert names == ["from-env"]


def test_chrome_trace_export_shape(tmp_path):
    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    with rec.span("work", cat="serve", seq=0):
        rec.event("mark", cat="retry")
    rec.close()
    doc = to_chrome_trace(read_flight(path))
    assert doc["displayTimeUnit"] == "ms"
    phases = sorted(e["ph"] for e in doc["traceEvents"])
    assert phases == ["X", "i"]
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert x["name"] == "work" and x["dur"] >= 0 and x["args"]["seq"] == 0
    # events are time-sorted for the viewer
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# xplane wire-format parser, over hand-built protobuf fixtures


def _vint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _ld(fno: int, payload: bytes) -> bytes:  # length-delimited field
    return _vint((fno << 3) | 2) + _vint(len(payload)) + payload


def _vf(fno: int, val: int) -> bytes:  # varint field
    return _vint(fno << 3) + _vint(val)


def _meta(mid: int, name: str, display: str | None = None) -> bytes:
    xmeta = _vf(1, mid) + _ld(2, name.encode())
    if display is not None:
        xmeta += _ld(3, display.encode())
    return _ld(4, _vf(1, mid) + _ld(2, xmeta))  # map<id, XEventMetadata>


def _event(mid: int, off_ps: int, dur_ps: int) -> bytes:
    return _ld(4, _vf(1, mid) + _vf(2, off_ps) + _vf(3, dur_ps))


def _line(name: str, ts_ns: int, events: bytes) -> bytes:
    return _ld(3, _ld(2, name.encode()) + _vf(3, ts_ns) + events)


def _plane(name: str, body: bytes = b"") -> bytes:
    return _ld(1, _ld(2, name.encode()) + body)


def test_xplane_empty_plane_parses_to_no_events():
    raw = _plane("/device:TPU:0")
    assert parse_xplane_bytes(raw) == []
    assert analyze([]) == {}


def test_xplane_multi_line_multi_plane_fixture():
    raw = (
        _plane(
            "/device:TPU:0",
            _meta(1, "dot.1")
            + _meta(2, "sort.2")
            + _line("XLA Ops", 10, _event(1, 5, 100) + _event(2, 200, 50))
            + _line("Steps", 0, _event(1, 0, 7)),
        )
        + _plane("/host:CPU", _meta(9, "hostfn") + _line("t0", 0,
                                                         _event(9, 1, 2)))
    )
    evs = parse_xplane_bytes(raw)
    assert len(evs) == 4
    first = evs[0]
    # start_ps = line timestamp_ns * 1000 + offset_ps
    assert first == {"plane": "/device:TPU:0", "line": "XLA Ops",
                     "name": "dot.1", "start_ps": 10_005, "dur_ps": 100}
    assert {e["plane"] for e in evs} == {"/device:TPU:0", "/host:CPU"}
    assert [e["name"] for e in evs[:3]] == ["dot.1", "sort.2", "dot.1"]


def test_xplane_display_name_wins_and_unknown_metadata_is_labeled():
    raw = _plane(
        "/device:TPU:0",
        _meta(1, "raw-name", display="fusion.7")
        + _line("XLA Ops", 0, _event(1, 0, 5) + _event(42, 0, 3)),
    )
    evs = parse_xplane_bytes(raw)
    assert evs[0]["name"] == "fusion.7"  # display_name overrides name
    assert evs[1]["name"] == "meta:42"  # unknown id labeled, not dropped


def test_xplane_scope_is_the_tf_op_stat_of_the_events_metadata():
    """A TPU's device planes name an operation by its bare HLO line; the
    ``op_name`` with the program's ``knn.*`` scopes is the ``tf_op`` stat of
    the event's metadata, a string or a reference to a stat metadata's
    name. An event without it keeps the five keys it always had."""
    op = "jit(f)/shard_map/while/body/knn.ring/permute/ppermute:"
    by_ref = "jit(f)/knn.dist/dot_general:"

    def stat_meta(sid, name):  # map<id, XStatMetadata>
        return _ld(5, _vf(1, sid) + _ld(2, _vf(1, sid) + _ld(2, name.encode())))

    def meta_with(mid, name, stats):
        xmeta = _vf(1, mid) + _ld(2, name.encode()) + stats
        return _ld(4, _vf(1, mid) + _ld(2, xmeta))

    raw = _plane(
        "/device:TPU:0",
        stat_meta(7, "tf_op") + stat_meta(8, "hlo_category")
        + stat_meta(9, by_ref)
        + meta_with(1, "%collective-permute-start = ...",
                    _ld(5, _vf(1, 8) + _ld(5, b"data formatting"))
                    + _ld(5, _vf(1, 7) + _ld(5, op.encode())))
        + meta_with(2, "%fusion.26 = ...", _ld(5, _vf(1, 7) + _vf(7, 9)))
        + _meta(3, "%copy.1 = ...")
        + _line("XLA Ops", 0,
                _event(1, 0, 5) + _event(2, 5, 7) + _event(3, 12, 1)),
    )
    evs = parse_xplane_bytes(raw)
    assert [e.get("scope") for e in evs] == [op, by_ref, None]
    assert set(evs[2]) == {"plane", "line", "name", "start_ps", "dur_ps"}


def test_xplane_unknown_fields_skipped_by_wire_type():
    """Fields the real schema carries beyond our subset must be skipped
    exactly as a generated proto reader would — varint, fixed64, fixed32
    and length-delimited unknowns at every nesting level."""
    fixed64 = _vint((99 << 3) | 1) + (1234).to_bytes(8, "little")
    fixed32 = _vint((98 << 3) | 5) + (99).to_bytes(4, "little")
    unknown_ld = _ld(97, b"opaque-submessage")
    unknown_varint = _vf(96, 7)
    raw = (
        unknown_varint  # XSpace-level unknown
        + _plane(
            "/device:TPU:0",
            fixed64  # XPlane-level unknown
            + _meta(1, "dot.1")
            + _line(
                "XLA Ops", 0,
                _ld(4, _vf(1, 1) + _vf(2, 11) + _vf(3, 13)
                    + fixed32 + unknown_ld)  # XEvent-level unknowns
            ),
        )
    )
    evs = parse_xplane_bytes(raw)
    assert evs == [{"plane": "/device:TPU:0", "line": "XLA Ops",
                    "name": "dot.1", "start_ps": 11, "dur_ps": 13}]


def test_xplane_truncated_and_garbage_raise_parse_error():
    with pytest.raises(ParseError):
        parse_xplane_bytes(b"\xff")  # truncated varint
    with pytest.raises(ParseError):
        parse_xplane_bytes(b"\xff" * 12)  # varint overruns 64 bits
    with pytest.raises(ParseError):
        parse_xplane_bytes(_vint(1 << 3 | 2) + _vint(100) + b"short")
    with pytest.raises(ParseError):
        parse_xplane_bytes(_vint(1 << 3 | 3))  # group wire type
    # truncation INSIDE a nested message surfaces too (plane payload is
    # length-delimited, so the inner parse sees a clean truncated buffer)
    good = _plane("/device:TPU:0", _meta(1, "dot.1"))
    with pytest.raises(ParseError):
        parse_xplane_bytes(good[:-3])


def test_parse_xplane_reads_gz_files(tmp_path):
    raw = _plane("/device:TPU:0",
                 _meta(1, "dot.1") + _line("l", 0, _event(1, 0, 9)))
    p = tmp_path / "t.xplane.pb.gz"
    p.write_bytes(gzip.compress(raw))
    evs = parse_xplane(str(p))
    assert len(evs) == 1 and evs[0]["dur_ps"] == 9


def test_categorize_and_analyze_busy_split_with_overlap():
    assert categorize("collective-permute-start.1") == "collective"
    assert categorize("sort.42") == "sort-topk"
    assert categorize("loop_fusion.3") == "matmul"
    assert categorize("dynamic-update-slice.9") == "copy"
    assert categorize("parameter.0") == "other"

    ms = 1_000_000_000  # 1 ms in ps
    events = [
        {"plane": "p", "line": "l", "name": "dot.1",
         "start_ps": 0, "dur_ps": 10 * ms},
        {"plane": "p", "line": "l", "name": "ppermute.2",
         "start_ps": 5 * ms, "dur_ps": 10 * ms},  # 5 ms under the dot
        {"plane": "p", "line": "l", "name": "zero-dur", "start_ps": 0,
         "dur_ps": 0},  # zero-duration events are not busy time
    ]
    rep = analyze(events)["p"]
    assert rep["busy_ms_by_category"] == {"collective": 10.0,
                                          "matmul": 10.0}
    assert rep["collective_total_ms"] == 10.0
    assert rep["collective_overlapped_with_matmul_ms"] == 5.0
    assert rep["collective_span_ms"] == 0  # no async start/done pairs
    assert rep["top_ops_ms"] == {"dot.1": 10.0, "ppermute.2": 10.0}


def test_dma_wait_is_its_own_category_not_matmul(tmp_path):
    """A kernel's own semaphore stalls must never be counted as compute: a collective span overlapping a stalled kernel
    is time the overlap FAILED to hide, and folding the wait into
    'matmul' would credit exactly that time to overlap_fraction."""
    assert categorize("DmaWait.3") == "dma-wait"
    assert categorize("wait-semaphore.1") == "dma-wait"
    assert categorize("dma_wait (ring)") == "dma-wait"
    # '-done' halves of async collectives keep their collective category
    # (the span pairing depends on it)
    assert categorize("collective-permute-done.2") == "collective"

    ms = 1_000_000_000
    raw = _plane(
        "/device:TPU:0",
        _meta(1, "dot.1") + _meta(2, "dma-wait.2")
        + _meta(3, "collective-permute-start.3")
        + _meta(4, "collective-permute-done.3")
        + _line(
            "XLA Ops", 0,
            _event(1, 0, 10 * ms)          # compute 0–10
            + _event(2, 10 * ms, 4 * ms)   # kernel stalls on the wire 10–14
            + _event(3, 8 * ms, 1 * ms)    # DMA in flight 8–14
            + _event(4, 13 * ms, 1 * ms),
        ),
    )
    (tmp_path / "t.xplane.pb").write_bytes(raw)
    out = attribute_trace(str(tmp_path))
    assert out["busy_ms"]["dma-wait"] == 4.0
    assert out["dma_wait_ms"] == 4.0
    assert out["busy_ms"]["matmul"] == 10.0
    # the invariant: every event still lands in exactly one category
    assert out["busy_total_ms"] == pytest.approx(
        sum(out["busy_ms"].values()), abs=1e-6
    )
    # span 8–14 overlaps true compute only on 8–10: 2 of 6 ms hidden.
    # Were the stall miscategorized as matmul, this would read 6/6.
    assert out["collective_span_ms"] == 6.0
    assert out["collective_span_overlapped_with_matmul_ms"] == 2.0
    assert out["overlap_fraction"] == pytest.approx(2 / 6, abs=1e-4)


# ---------------------------------------------------------------------------
# device-time attribution


def test_attribute_trace_split_sums_and_casualties(tmp_path):
    ms = 1_000_000_000
    raw = _plane(
        "/device:TPU:0",
        _meta(1, "dot.1") + _meta(2, "sort.2") + _meta(3, "copy.3")
        + _line("XLA Ops", 0,
                _event(1, 0, 8 * ms) + _event(2, 8 * ms, 3 * ms)
                + _event(3, 11 * ms, 1 * ms)),
    )
    (tmp_path / "good.xplane.pb").write_bytes(raw)
    (tmp_path / "bad.xplane.pb").write_bytes(b"\xff\xff\xff")
    out = attribute_trace(str(tmp_path))
    assert out["plane"] == "/device:TPU:0"
    # the acceptance invariant: categories sum to the busy total
    assert out["busy_total_ms"] == pytest.approx(
        sum(out["busy_ms"].values()), abs=1e-6
    )
    assert out["busy_ms"] == {"matmul": 8.0, "sort-topk": 3.0, "copy": 1.0}
    assert out["overlap_fraction"] is None  # no collectives in this trace
    # the truncated sibling is a recorded casualty, not an abort
    assert [c["file"] for c in out["casualties"]] == [
        str(tmp_path / "bad.xplane.pb")
    ]


def test_attribute_trace_errors_are_explicit(tmp_path):
    out = attribute_trace(str(tmp_path))
    assert "error" in out and "no .xplane.pb" in out["error"]
    (tmp_path / "bad.xplane.pb").write_bytes(b"\xff\xff\xff")
    out = attribute_trace(str(tmp_path))
    assert "error" in out and out["casualties"]


def test_pick_device_plane_prefers_device_over_busier_host():
    planes = {
        "/host:CPU": {"busy_ms_by_category": {"other": 100.0}},
        "/device:TPU:0": {"busy_ms_by_category": {"matmul": 1.0}},
        "/device:TPU:1": {"busy_ms_by_category": {"matmul": 2.0}},
    }
    assert pick_device_plane(planes) == "/device:TPU:1"
    assert pick_device_plane({}) is None
    # CPU traces put the op events on a host plane: the right (only) story
    assert pick_device_plane(
        {"/host:CPU": {"busy_ms_by_category": {"other": 1.0}}}
    ) == "/host:CPU"


# ---------------------------------------------------------------------------
# `mpi-knn metrics` CLI


def _snapshot_file(tmp_path) -> str:
    reg = MetricsRegistry()
    reg.counter("req_total").inc(2)
    reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
    p = tmp_path / "snap.json"
    p.write_text(json.dumps(reg.snapshot()))
    return str(p)


def test_metrics_cli_renders_and_checks_snapshot(tmp_path, capsys):
    from mpi_knn_tpu.obs.cli import main as metrics_main

    snap = _snapshot_file(tmp_path)
    assert metrics_main([snap]) == 0
    out = capsys.readouterr().out
    assert "req_total 2.0" in out and 'lat_bucket{le="+Inf"} 1' in out
    assert metrics_main([snap, "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["metrics"]["req_total"]
    assert metrics_main([snap, "--check"]) == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True


def test_metrics_cli_flight_modes(tmp_path, capsys):
    from mpi_knn_tpu.obs.cli import main as metrics_main

    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    with rec.span("batch", cat="serve", seq=0):
        pass
    rec.begin("open-at-death", cat="bench")
    rec.close()

    assert metrics_main(["--flight", path]) == 0  # summary
    summary = json.loads(capsys.readouterr().out)
    assert summary["records"] == 3
    assert summary["open_spans"][0]["name"] == "open-at-death"

    assert metrics_main(["--flight", path, "--validate"]) == 0
    chrome = str(tmp_path / "trace.json")
    assert metrics_main(["--flight", path, "--chrome", chrome]) == 0
    assert json.load(open(chrome))["traceEvents"]

    # schema problems and empty records exit 1 (the CI gate)
    with open(path, "a") as f:
        f.write('{"ev":"E","span":99,"ts":1.0,"dur_s":-2}\n'
                '{"ev":"I","name":"pad","cat":"","ts":1.0,"pid":1}\n')
    assert metrics_main(["--flight", path, "--validate"]) == 1
    empty = str(tmp_path / "none.jsonl")
    open(empty, "w").close()
    assert metrics_main(["--flight", empty, "--validate"]) == 1
    assert metrics_main(["--flight", empty]) == 1


def test_metrics_cli_usage_and_load_errors(tmp_path, capsys):
    from mpi_knn_tpu.obs.cli import main as metrics_main

    snap = _snapshot_file(tmp_path)
    assert metrics_main([]) == 2  # neither snapshot nor --flight
    assert metrics_main([snap, "--flight", "x.jsonl"]) == 2  # both
    assert metrics_main([snap, "--validate"]) == 2  # flight-only flag
    assert metrics_main([str(tmp_path / "missing.json")]) == 1
    bad = tmp_path / "bad.json"
    bad.write_text('{"not": "a snapshot"}')
    assert metrics_main([str(bad)]) == 1
    capsys.readouterr()


def test_metrics_subcommand_routed_from_main_cli(tmp_path, capsys):
    from mpi_knn_tpu.cli import main as cli_main

    assert cli_main(["metrics", _snapshot_file(tmp_path)]) == 0
    assert "req_total" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# acceptance (a): zero steady-state compiles, proven via the SHARED registry


def test_serve_zero_steady_state_compiles_via_shared_registry(rng):
    X = rng.standard_normal((96, 12)).astype(np.float32)
    sess = ServeSession(build_index(X, _cfg()))
    sess.warm([16, 32])
    sizes = (5, 16, 17, 32, 9)  # ragged sizes, both warmed buckets
    for rows in sizes:  # first pass: reach steady state at every shape
        sess.submit(rng.standard_normal((rows, 12)).astype(np.float32))
    sess.drain()
    reg = get_registry()
    compiles_before = reg.counter("jax_compiles_total").value
    batches_before = reg.counter("serve_batches_total").value
    lat_before = reg.histogram("serve_batch_latency_seconds").count
    for rows in sizes:  # steady state: same shapes again
        sess.submit(rng.standard_normal((rows, 12)).astype(np.float32))
    sess.drain()
    # the same invariant test_serve/test_ivf assert, now a registry fact
    assert reg.counter("jax_compiles_total").value == compiles_before
    assert reg.counter("serve_batches_total").value == batches_before + 5
    assert reg.histogram("serve_batch_latency_seconds").count == \
        lat_before + 5


# ---------------------------------------------------------------------------
# acceptance (b): the flight record reconstructs the stream — and survives


def test_flight_reconstructs_batches_retries_and_rung_walk(rng, tmp_path):
    path = str(tmp_path / "flight.jsonl")
    set_recorder(FlightRecorder(path, fresh=True))
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _cfg(query_tile=16, corpus_tile=32))
    # deadline wide enough that a clean (or retried) CPU batch never
    # breaches it; only the injected 0.5 s slow batch does
    pol = ResiliencePolicy(
        max_retries=3, backoff_base_s=0.01, batch_deadline_s=0.25,
        degrade_after=1, min_bucket=16,
    )
    sess = ServeSession(idx, resilience=pol)
    sess.warm([8])
    Q = rng.standard_normal((8, 16)).astype(np.float32)
    with install_faults({"serve-batch": ("transient", 2)}):
        sess.submit(Q)  # batch 0: retried twice, then served
    with install_faults({"serve-batch": ("slow", 0.5)}):
        sess.submit(Q)  # batch 1: breaches the deadline → rung shed
    sess.submit(Q)      # batch 2: clean, at the degraded rung
    set_recorder(None)  # close + flush

    records = read_flight(path)
    assert validate_flight(records) == []
    spans, events = reconstruct_spans(records)

    # the index build and warm/compile story is in the same record
    assert any(s["name"] == "index-build" and s["cat"] == "index"
               for s in spans)
    assert any(s["name"] == "compile" and s["cat"] == "compile"
               for s in spans)

    # every batch's dispatch→retire interval reconstructs, closed, with
    # the same honest latency the session reported
    batches = sorted((s for s in spans if s["name"] == "batch"),
                     key=lambda s: s["attrs"]["seq"])
    assert [b["attrs"]["seq"] for b in batches] == [0, 1, 2]
    for b, res_lat in zip(batches, sess.latencies):
        assert b["dur_s"] is not None and b["dur_s"] >= 0
        assert b["end_attrs"]["latency_s"] == res_lat
    assert batches[0]["end_attrs"]["retries"] == 2
    assert batches[1]["end_attrs"]["deadline_breached"] is True
    assert batches[0]["attrs"]["rung"] == FULL_RUNG
    assert batches[2]["attrs"]["rung"] != FULL_RUNG  # walked

    # retry and rung-change events carry their provenance
    retry = next(e for e in events if e["name"] == "retry")
    assert retry["attrs"]["seq"] == 0 and retry["attrs"]["retries"] == 2
    assert retry["attrs"]["backoffs"] == [0.01, 0.02]
    degrade = next(e for e in events if e["name"] == "degrade")
    assert degrade["attrs"]["after_batch"] == 1
    assert degrade["attrs"]["rung"] == batches[2]["attrs"]["rung"]
    # heartbeat marks mirror into the same timeline
    assert any(e["name"] == "beat" for e in events)


def test_flight_record_survives_sigkill_of_worker_mid_stream(tmp_path):
    """The BENCH_r01/r03/r04/r05 failure mode, closed: a worker
    SIGKILLed mid-batch leaves a readable record up to the instant of
    death; the supervisor recovers it, banks the summary, and the open
    batch span IS the diagnosis."""
    script = textwrap.dedent("""
        import os, signal, threading
        import numpy as np
        from mpi_knn_tpu import KNNConfig, build_index
        from mpi_knn_tpu.serve import ServeSession

        rng = np.random.default_rng(0)
        X = rng.standard_normal((96, 8)).astype(np.float32)
        cfg = KNNConfig(backend="serial", k=3, query_tile=16,
                        corpus_tile=32, query_bucket=16, dispatch_depth=1)
        sess = ServeSession(build_index(X, cfg))
        sess.warm([16])
        Q = rng.standard_normal((16, 8)).astype(np.float32)
        sess.submit(Q)
        sess.submit(Q)
        # batch 2's dispatch hangs at the injected fault site; the timer
        # SIGKILLs this process mid-batch — no cleanup, no atexit
        threading.Timer(
            1.0, lambda: os.kill(os.getpid(), signal.SIGKILL)
        ).start()
        sess.submit(Q)
    """)
    flight = str(tmp_path / "flight.jsonl")
    env = dict(os.environ, TKNN_FAULTS="serve-batch=hang:3")
    res = run_supervised(
        python_worker_argv("-c", script),
        env=env, beat_timeout_s=None, wall_timeout_s=240.0,
        flight_path=flight,
    )
    assert res.status == "crashed"  # SIGKILL, not a supervisor kill
    # the supervisor banked the partial record alongside the failure
    assert res.flight is not None and res.flight["records"] > 0
    assert any(s["name"] == "batch" for s in res.flight["open_spans"])
    # the caller-owned JSONL reconstructs the stream up to the kill:
    # two retired batches, the third open at the instant of death
    spans, _ = reconstruct_spans(read_flight(flight))
    batches = sorted((s for s in spans if s["name"] == "batch"),
                     key=lambda s: s["attrs"]["seq"])
    assert [b["attrs"]["seq"] for b in batches] == [0, 1, 2]
    assert batches[0]["dur_s"] is not None
    assert batches[1]["dur_s"] is not None
    assert batches[2]["dur_s"] is None  # the kill diagnosis


# ---------------------------------------------------------------------------
# acceptance (c): profiled run — per-category split sums to the busy total


def test_profile_device_time_split_sums_to_busy_total(rng, tmp_path):
    X = rng.standard_normal((96, 12)).astype(np.float32)
    sess = ServeSession(build_index(X, _cfg()))
    sess.warm([16])
    Q = rng.standard_normal((16, 12)).astype(np.float32)
    sess.submit(Q)  # steady state: the profiled batches compile nothing
    out = sess.profile([Q, Q], trace_dir=str(tmp_path / "prof"))
    assert out["batches_profiled"] == 2
    assert out["trace_dir"] == str(tmp_path / "prof")
    assert "busy_ms" in out, out
    assert out["busy_total_ms"] > 0
    assert set(out["busy_ms"]) <= {
        "matmul", "sort-topk", "collective", "copy", "other"
    }
    assert all(v >= 0 for v in out["busy_ms"].values())
    # the acceptance invariant: categories sum to ≤ the busy total (they
    # sum EXACTLY to it — every event carries exactly one category; the
    # tolerance covers the per-category ms rounding)
    assert sum(out["busy_ms"].values()) <= out["busy_total_ms"] + 1e-6
    assert out["busy_total_ms"] == pytest.approx(
        sum(out["busy_ms"].values()), abs=1e-6
    )
    if out["overlap_fraction"] is not None:
        assert 0.0 <= out["overlap_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# review regressions: survivable errors close their spans, doctor-verdict
# snapshots load, inert CLI knobs refuse, profile pre-compiles its buckets


def test_poisoned_and_exhausted_batches_close_their_spans(rng, tmp_path):
    """An OPEN span is the contract's kill diagnosis — a raised-and-CAUGHT
    serving error (sentinel trip, retries exhausted) must close the batch
    span with an error attr, not forge a mid-batch death for a process
    that is still serving."""
    from mpi_knn_tpu.resilience.ladder import PoisonedResultError
    from mpi_knn_tpu.resilience.retry import RetryExhausted

    path = str(tmp_path / "flight.jsonl")
    set_recorder(FlightRecorder(path, fresh=True))
    X = rng.standard_normal((96, 12)).astype(np.float32)
    pol = ResiliencePolicy(max_retries=1, backoff_base_s=0.01)
    sess = ServeSession(build_index(X, _cfg()), resilience=pol)
    sess.warm([16])
    Q = rng.standard_normal((16, 12)).astype(np.float32)

    with install_faults({"serve-nan": "nan"}):
        with pytest.raises(PoisonedResultError):
            sess.submit(Q)  # sentinel trips at retire (dispatch_depth=1)
    with install_faults({"serve-batch": ("transient", 5)}):
        with pytest.raises(RetryExhausted):
            sess.submit(Q)  # 1 retry allowed, 5 needed: exhausted
    sess.submit(Q)  # the session survives and serves on
    sess.drain()
    set_recorder(None)

    records = read_flight(path)
    assert validate_flight(records) == []
    spans, _ = reconstruct_spans(records)
    batches = [s for s in spans if s["name"] == "batch"]
    assert len(batches) == 3
    assert all(s["dur_s"] is not None for s in batches)  # none left open
    errors = [s["end_attrs"].get("error") for s in batches]
    assert "poisoned-result" in errors and "RetryExhausted" in errors
    assert errors.count(None) == 1  # the clean batch
    assert summarize_flight(records)["open_spans"] == []


def test_load_snapshot_unwraps_doctor_verdict(tmp_path, capsys):
    """The CLI help documents reading a doctor verdict; the verdict nests
    the registry snapshot under its "metrics" key. load_snapshot unwraps
    by schema marker instead of crashing in to_prometheus."""
    from mpi_knn_tpu.obs.cli import main as metrics_main

    reg = MetricsRegistry()
    reg.counter("jax_compiles_total").inc()
    p = tmp_path / "verdict.json"
    p.write_text(json.dumps(
        {"ok": True, "status": "ok", "metrics": reg.snapshot(),
         "flight": None}
    ))
    assert "jax_compiles_total" in load_snapshot(str(p))["metrics"]
    assert metrics_main([str(p)]) == 0  # renders, no traceback
    assert "jax_compiles_total" in capsys.readouterr().out
    assert metrics_main([str(p), "--check"]) == 0
    capsys.readouterr()
    # a verdict whose probe died before printing metrics refuses loudly
    p2 = tmp_path / "verdict-null.json"
    p2.write_text(json.dumps({"ok": False, "metrics": None}))
    assert metrics_main([str(p2)]) == 1
    capsys.readouterr()


def test_metrics_cli_refuses_snapshot_flags_with_flight(tmp_path, capsys):
    """The inert-knob refusal convention: `--flight F --check` must exit
    2, not print a span summary while the CI check silently never ran."""
    from mpi_knn_tpu.obs.cli import main as metrics_main

    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    with rec.span("batch", cat="serve"):
        pass
    rec.close()
    assert metrics_main(["--flight", path, "--check"]) == 2
    assert metrics_main(["--flight", path, "--format", "json"]) == 2
    capsys.readouterr()


def test_profile_compiles_unserved_bucket_before_trace(rng, tmp_path):
    """A profile batch size the stream never served must compile BEFORE
    the jax.profiler trace opens — a cold compile inside the trace lands
    in "other" and the "steady-state" split measures compilation."""
    path = str(tmp_path / "flight.jsonl")
    set_recorder(FlightRecorder(path, fresh=True))
    X = rng.standard_normal((96, 12)).astype(np.float32)
    sess = ServeSession(build_index(X, _cfg()))
    sess.warm([16])
    # 48 rows pads to bucket 64 — a cell warm() never compiled
    Q = rng.standard_normal((48, 12)).astype(np.float32)
    sess.profile([Q], trace_dir=str(tmp_path / "trace"))
    set_recorder(None)

    spans, _ = reconstruct_spans(read_flight(path))
    prof = next(s for s in spans if s["name"] == "profile")
    compiles = [s for s in spans if s["name"] == "compile"]
    assert any(s["attrs"]["bucket"] == 64 for s in compiles)
    assert all(s["ts"] + s["dur_s"] <= prof["ts"] for s in compiles)


def test_compile_failure_closes_its_span(rng, tmp_path, monkeypatch):
    """A raised lowering/compile failure is survivable by the caller —
    the compile span must close with the error, not forge an open-span
    'killed mid-compile' diagnosis."""
    from mpi_knn_tpu.serve import engine as serve_engine

    path = str(tmp_path / "flight.jsonl")
    set_recorder(FlightRecorder(path, fresh=True))
    X = rng.standard_normal((96, 12)).astype(np.float32)
    sess = ServeSession(build_index(X, _cfg()))

    def boom(*a, **k):
        raise RuntimeError("injected lowering failure")

    monkeypatch.setattr(serve_engine, "lower_bucket", boom)
    with pytest.raises(RuntimeError):
        sess.submit(rng.standard_normal((16, 12)).astype(np.float32))
    set_recorder(None)

    records = read_flight(path)
    spans, _ = reconstruct_spans(records)
    comp = [s for s in spans if s["name"] == "compile"]
    assert comp and all(s["dur_s"] is not None for s in comp)
    assert any(s["end_attrs"].get("error") == "RuntimeError" for s in comp)
    # the enclosing batch span closed too: nothing left open
    assert summarize_flight(records)["open_spans"] == []


def test_validate_tolerates_rotated_ring_prefix(tmp_path):
    """A long-lived server's ring file that rotated twice starts at a
    generation marker; ends/parents referencing the dropped prefix are
    the ring working as designed, not corruption — the CI gate must not
    fail a healthy server's record."""
    path = str(tmp_path / "ring.jsonl")
    rec = FlightRecorder(path, max_bytes=4096)
    for i in range(400):
        with rec.span("batch", cat="serve", i=i, pad="x" * 64):
            pass
    rec.close()
    records = read_flight(path)
    assert records[0]["ev"] == "R"  # first retained record: ring marker
    assert validate_flight(records) == []
    # genuine corruption still reports on a truncated record
    assert any("bad dur_s" in p for p in validate_flight(
        records + [{"ev": "E", "span": 10 ** 9, "ts": 1.0, "dur_s": -1.0}]
    ))
    # and WITHOUT a truncation marker a dangling end is still a problem
    assert any("not open" in p for p in validate_flight(
        [{"ev": "E", "span": 5, "ts": 1.0, "dur_s": 0.1}]
    ))
    # a marker with a bad generation is itself a problem
    assert any("ring marker" in p for p in validate_flight(
        [{"ev": "R", "gen": 0, "ts": 1.0}]
    ))


def test_metrics_cli_validate_and_chrome_compose(tmp_path, capsys):
    """`--validate --chrome OUT` must write OUT, not silently drop the
    export because validation returned first."""
    from mpi_knn_tpu.obs.cli import main as metrics_main

    path = str(tmp_path / "f.jsonl")
    rec = FlightRecorder(path)
    with rec.span("batch", cat="serve"):
        pass
    rec.close()
    out = str(tmp_path / "t.json")
    assert metrics_main(
        ["--flight", path, "--validate", "--chrome", out]
    ) == 0
    assert json.load(open(out))["traceEvents"]
    capsys.readouterr()


# ---------------------------------------------------------------------------
# labeled counters/gauges (ISSUE 11: the per-tenant axis)


def test_labeled_counters_round_trip_prometheus():
    """Labeled series render as canonical samples under ONE HELP/TYPE
    header per base family, and the strict parser reads them back."""
    from mpi_knn_tpu.obs.metrics import (
        MetricsRegistry,
        parse_prometheus,
        to_prometheus,
    )

    reg = MetricsRegistry()
    reg.counter("served_total", help="rows", labels={"tenant": "a"}).inc(3)
    reg.counter("served_total", help="rows", labels={"tenant": "b"}).inc(5)
    reg.counter("served_total", help="rows",
                labels={"tenant": "a"}).inc(2)  # same series, get-or-create
    reg.gauge("depth", labels={"queue": "q0"}).set(7)
    text = to_prometheus(reg.snapshot())
    samples = parse_prometheus(text)
    assert samples['served_total{tenant="a"}'] == 5.0
    assert samples['served_total{tenant="b"}'] == 5.0
    assert samples['depth{queue="q0"}'] == 7.0
    type_lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE")]
    assert type_lines.count("# TYPE served_total counter") == 1


def test_label_canonicalization_and_validation():
    """Key order never forks a series; hostile values are refused (an
    escaping-needed value would corrupt the exposition silently)."""
    from mpi_knn_tpu.obs.metrics import MetricsRegistry, sample_name

    assert sample_name("m", {"b": 1, "a": 2}) == 'm{a="2",b="1"}'
    reg = MetricsRegistry()
    c1 = reg.counter("m", labels={"a": "x", "b": "y"})
    c2 = reg.counter("m", labels={"b": "y", "a": "x"})
    assert c1 is c2
    with pytest.raises(ValueError, match="escaping"):
        reg.counter("m", labels={"a": 'inj"ect'})
    with pytest.raises(ValueError, match="bad label name"):
        reg.counter("m", labels={"0bad": "v"})
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("bad name")


def test_histograms_refuse_labels():
    """A labeled histogram cannot be rendered correctly by name-keyed
    storage (the _bucket suffix belongs before the labels) — refused
    loudly rather than emitting malformed exposition."""
    from mpi_knn_tpu.obs.metrics import MetricsRegistry

    with pytest.raises(ValueError, match="labels are not supported"):
        MetricsRegistry().histogram("lat", labels={"tenant": "a"})


def test_mixed_kind_family_guard_spans_labels():
    """A labeled counter and a bare gauge (or any other kind) sharing
    one BASE family name must collide loudly — they would render a
    mixed-kind family under one TYPE header (review regression)."""
    from mpi_knn_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("fam_total", labels={"tenant": "a"})
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("fam_total")
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.histogram("fam_total")
    # same kind, other labels (or bare) stays fine
    reg.counter("fam_total", labels={"tenant": "b"})
    reg.counter("fam_total")
    reg.clear()
    reg.gauge("fam_total")  # clear() resets the family map too


# ---------------------------------------------------------------------------
# ISSUE 26: one span instrument, three sinks (flight record, profiler
# annotation, /metrics), and stable scope names on the kernels


def test_span_off_path_reads_no_environ_and_writes_nothing(
    tmp_path, monkeypatch
):
    """No recorder, no profiler session: the helpers resolve nothing per
    call (the env var was read when the recorder was last installed or
    asked for) and leave no file behind."""
    from mpi_knn_tpu.obs import spans as spans_mod

    monkeypatch.delenv(spans_mod.RECORDER_ENV, raising=False)
    set_recorder(None)
    assert spans_mod._active is None

    class NoEnviron:
        def __getattr__(self, name):
            raise AssertionError(f"os.environ.{name} read on the off path")

        __getitem__ = __contains__ = __getattr__

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(spans_mod.os, "environ", NoEnviron())
    took = []
    with spans_mod.span("a", cat="t", seq=1, sink=took.append):
        h = spans_mod.begin_span("b", cat="t", seq=1)
        spans_mod.end_span(h, rows=3)
    spans_mod.event("c", cat="t")
    monkeypatch.undo()
    assert len(took) == 1 and took[0] >= 0.0  # the sink still got its span
    assert list(tmp_path.iterdir()) == []


def test_spans_module_stays_jax_free_and_inert_without_jax():
    """``obs/spans.py`` is imported by supervisors that must never load
    jax: it looks jax up in ``sys.modules`` and never imports it, and with
    jax absent and no recorder a span has nowhere to go at all."""
    import subprocess
    import sys

    code = (
        "import sys, os; os.environ.pop('TKNN_FLIGHT_RECORD', None); "
        "import mpi_knn_tpu.obs.spans as s; "
        "assert 'jax' not in sys.modules, 'obs.spans imported jax'; "
        "assert s.begin_span('x', cat='t', seq=1) is None; "
        "took = []; h = s.begin_span('y', sink=took.append); "
        "assert h.ann is None and h.sid is None; s.end_span(h); "
        "assert len(took) == 1; "
        "assert 'jax' not in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_spans_chained_on_the_call_sites_readings_partition_their_parent(
        tmp_path):
    """``at=``: a call site hands each span of a chain ITS reading of its
    clock, the end of one being the beginning of the next; the sinks and
    the flight record's ``dur_s`` get the differences, so the chain adds
    up to its parent to the bit, whatever ``perf_counter`` read meanwhile.
    A span begun with a reading must be ended with one."""
    from mpi_knn_tpu.obs import spans as spans_mod

    path = str(tmp_path / "f.jsonl")
    set_recorder(FlightRecorder(path))
    took = {}
    try:
        request = spans_mod.begin_span(
            "request", cat="http", at=2.0,
            sink=lambda s: took.__setitem__("request", s))
        read = spans_mod.begin_span(
            "read", cat="http", parent=request, at=2.0,
            sink=lambda s: took.__setitem__("read", s))
        spans_mod.end_span(read, at=2.25)
        write = spans_mod.begin_span(
            "write", cat="http", parent=request, at=2.25, seq=3,
            sink=lambda s: took.__setitem__("write", s))
        with pytest.raises(ValueError, match="at="):
            spans_mod.end_span(write)
        spans_mod.end_span(write, at=2.75)
        spans_mod.end_span(request, at=2.75, status=200)
    finally:
        set_recorder(None)
    assert took == {"read": 0.25, "write": 0.5, "request": 0.75}
    records = read_flight(path)
    assert validate_flight(records) == []
    spans, _ = reconstruct_spans(records)
    by_name = {s["name"]: s for s in spans}
    assert {n: s["dur_s"] for n, s in by_name.items()} == took
    assert by_name["write"]["parent"] == by_name["request"]["span"]
    assert by_name["write"]["attrs"] == {"seq": 3}
    assert by_name["request"]["end_attrs"] == {"status": 200}


def test_span_feeds_flight_record_sink_and_profiler_annotation(tmp_path):
    """One call site, three sinks: under a recorder and a profiler
    session a span is a flight record with its parent, a ``knn:<cat>.
    <name>`` event on the host plane of the ``.xplane.pb`` carrying its
    scalar attrs (those known only at its end too), and a duration handed
    to ``sink``. ``flight=False`` keeps a span out of the file only."""
    import glob

    import jax
    import jax.numpy as jnp

    from mpi_knn_tpu.obs import spans as spans_mod

    path = str(tmp_path / "f.jsonl")
    set_recorder(FlightRecorder(path))
    took = []
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        batch = spans_mod.begin_span("batch", cat="serve", seq=7, rows=3)
        with spans_mod.span("prep", cat="batch", seq=7, parent=batch,
                            sink=took.append, words="a b", bad="x=1,y"):
            jnp.ones((8, 8)).block_until_ready()
        with spans_mod.span("idle", cat="pump", flight=False,
                            sink=took.append):
            pass
        spans_mod.end_span(batch, latency_ms=2)
    finally:
        jax.profiler.stop_trace()
        set_recorder(None)

    spans, _ = reconstruct_spans(read_flight(path))
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"batch", "prep"}  # idle: flight=False
    assert by_name["prep"]["parent"] == by_name["batch"]["span"]
    assert by_name["prep"]["attrs"]["seq"] == 7
    assert len(took) == 2 and all(t >= 0.0 for t in took)
    assert took[0] == pytest.approx(by_name["prep"]["dur_s"], abs=0.05)

    (pb,) = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    found = {}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans_mod.TRACE_PREFIX):
                    found[e.name] = dict(e.stats)
    assert set(found) == {"knn:serve.batch", "knn:batch.prep",
                          "knn:pump.idle"}
    assert found["knn:serve.batch"]["seq"] == 7
    assert found["knn:serve.batch"]["latency_ms"] == 2  # set at its end
    prep = found["knn:batch.prep"]
    assert prep["seq"] == 7 and prep["words"] == "a b"
    assert prep["parent"] == by_name["batch"]["span"]
    assert "bad" not in prep  # would break the trace's name#k=v,...# form


def test_cache_load_event_feeds_jax_cache_loads_total():
    """The listener behind ``jax_compiles_total`` also counts jax's
    persistent-cache retrievals; other events move neither."""
    from mpi_knn_tpu.obs import metrics as metrics_mod

    reg = get_registry()
    loads = reg.counter("jax_cache_loads_total")
    compiles = reg.counter("jax_compiles_total")
    l0, c0 = loads.value, compiles.value
    metrics_mod._jax_compile_listener(metrics_mod.JAX_CACHE_LOAD_EVENT, 0.01)
    metrics_mod._jax_compile_listener("/jax/some/other/event", 0.01)
    assert (loads.value, compiles.value) == (l0 + 1, c0)
    metrics_mod._jax_compile_listener(metrics_mod.JAX_COMPILE_EVENT, 0.01)
    assert (loads.value, compiles.value) == (l0 + 1, c0 + 1)


@pytest.mark.parametrize("program", ["all_knn_step", "serve_bucket"])
def test_kernel_scope_names_are_in_the_compiled_hlo(rng, program):
    """The stable ``knn.*`` names the trace reduction will key on are in
    the ``op_name`` metadata of the programs the benchmark's cells run:
    the serial all-kNN step and a serve bucket."""
    import re

    import jax.numpy as jnp

    from mpi_knn_tpu.backends.serial import knn_chunk_update
    from mpi_knn_tpu.ops.topk import init_topk_tiles
    from mpi_knn_tpu.serve.engine import lower_bucket

    cfg = _cfg()
    X = rng.standard_normal((64, 8)).astype(np.float32)
    if program == "all_knn_step":
        carry_d, carry_i = init_topk_tiles(1, 16, cfg.k)
        lowered = knn_chunk_update.lower(
            jnp.asarray(X[:16]).reshape(1, 16, 8),
            jnp.arange(16, dtype=jnp.int32).reshape(1, 16),
            jnp.asarray(X).reshape(2, 32, 8),
            jnp.arange(64, dtype=jnp.int32).reshape(2, 32),
            carry_d, carry_i, cfg,
        )
    else:
        lowered, _, _ = lower_bucket(build_index(X, cfg), cfg, 16)
    names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    scopes = {part for n in names for part in n.split("/")
              if part.startswith("knn.")}
    assert {"knn.dist", "knn.select", "knn.ids", "knn.merge",
            "knn.norms"} <= scopes, sorted(scopes)
