"""The always-on overrun record (ISSUE 50): the host's own evidence
(``obs/host.py``: the collector's hook, ``host_sample``), the rule on the
serving pump's batch cycle (``ServeSession._judge``) and on the one-shot
call's period (``api._CallWatch``), and what is kept of an overrun.

Planted stalls are real sleeps of at most 0.2 s, measured where they are
planted, on batches of a few milliseconds: the rule's floor is 50 ms."""

from __future__ import annotations

import gc
import logging
import threading
import time

import jax
import numpy as np
import pytest

from mpi_knn_tpu import all_knn, api
from mpi_knn_tpu.config import KNNConfig
from mpi_knn_tpu.obs import host as obs_host
from mpi_knn_tpu.obs import spans as obs_spans
from mpi_knn_tpu.obs.metrics import MetricsRegistry, get_registry
from mpi_knn_tpu.serve import ServeSession, build_index
from mpi_knn_tpu.serve import engine

DIM = 16
STALL_S = 0.15
FAMILY = "serve_batch_overrun"


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, DIM)).astype(np.float32)
    return build_index(X, KNNConfig(
        k=4, backend="serial", query_bucket=16, corpus_tile=128,
        query_tile=16, dispatch_depth=2))


@pytest.fixture
def session(index):
    """A session at depth 2 with a registry of its own, both buckets
    built."""
    s = ServeSession(index)
    s._metrics = MetricsRegistry()
    s.warm([16, 32])
    return s


def _overruns(registry) -> dict:
    """``{where: (count, seconds)}`` of the serve family."""
    snap = registry.snapshot()["metrics"]
    out = {}
    for name, m in snap.items():
        if name.startswith(FAMILY + "s_total{"):
            where = name.split('"')[1]
            out[where] = (m["value"], snap[
                f'{FAMILY}_seconds_total{{where="{where}"}}']["value"])
    return out


def _queries(rows: int = 16):
    return np.random.default_rng(rows).normal(
        size=(rows, DIM)).astype(np.float32)


class _Sync:
    """``device_sync``'s stand-in: the real one, then the device's time as a
    sleep (``busy_s`` a batch), a planted one on top for batch ``stall``.
    ``late`` says who was late: the device (the batch behind the stalled one
    takes its usual time after it), or the host (the thread came back late
    and finds the batch behind long finished)."""

    def __init__(self, real, stall: int, late: str, busy_s: float = 0.01):
        self.real, self.stall, self.late = real, stall, late
        self.busy_s, self.calls, self.planted_s = busy_s, 0, None

    def __call__(self, *arrays):
        n, self.calls = self.calls, self.calls + 1
        self.real(*arrays)
        if not (self.late == "host" and n == self.stall + 1):
            time.sleep(self.busy_s)
        if n == self.stall:
            t = time.perf_counter()
            time.sleep(STALL_S)
            self.planted_s = time.perf_counter() - t


@pytest.mark.parametrize("late", ["device", "host"])
def test_a_planted_wait_is_told_apart_by_the_batch_behind_it(
        session, monkeypatch, caplog, late):
    """A long ``wait`` with a batch in flight behind it: that batch's own
    wait near its usual length says the device delivered late, near zero
    that the thread came back late. Counted once, the excess what was
    planted, and ``device_sync`` runs once a batch as ever."""
    sync = _Sync(engine.device_sync, stall=12, late=late)
    monkeypatch.setattr(engine, "device_sync", sync)
    q = _queries()
    with caplog.at_level(logging.WARNING, logger="mpi_knn_tpu"):
        for _ in range(18):
            session.submit(q)
        session.drain()
    assert sync.calls == 18  # no synchronisation of the record's own
    seen = _overruns(session._metrics)
    assert list(seen) == [f"wait-{late}"], seen
    count, seconds = seen[f"wait-{late}"]
    assert count == 1  # not again in the batch behind it
    assert seconds == pytest.approx(sync.planted_s, rel=0.10)
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("overrun serve")]
    fields = dict(w.split("=", 1) for w in line.split()[2:])
    assert fields["seq"] == "12" and fields["where"] == f"wait-{late}"
    assert fields["bucket"] == "16" and fields["inflight"] == "1"
    assert float(fields["excess_ms"]) == pytest.approx(
        1e3 * sync.planted_s, rel=0.10)
    assert float(fields["gc_ms"]) < 20 and float(fields["cpu_ms"]) < 100
    next_wait_ms = float(fields["next_wait_ms"])
    assert next_wait_ms < 5 if late == "host" else next_wait_ms >= 5
    assert "wait:" in fields["phases_ms"] and "other:" in fields["phases_ms"]


def test_a_long_wait_with_nothing_behind_it_is_plain_wait(
        index, monkeypatch):
    """At depth 1 nothing is in flight behind a batch: the record closes at
    once and says ``wait``."""
    s = ServeSession(index, dispatch_depth=1)
    s._metrics = MetricsRegistry()
    sync = _Sync(engine.device_sync, stall=10, late="device")
    monkeypatch.setattr(engine, "device_sync", sync)
    q = _queries()
    for _ in range(12):
        s.submit(q)
    seen = _overruns(s._metrics)
    assert list(seen) == ["wait"] and seen["wait"][0] == 1
    assert seen["wait"][1] == pytest.approx(sync.planted_s, rel=0.10)


@pytest.mark.parametrize("phase,target", [
    ("prep", "_prep_queries"), ("enqueue", "_run")])
def test_a_planted_long_phase_names_itself(
        session, monkeypatch, phase, target):
    real = getattr(engine, target)
    calls = []

    def slow(*args, **kwargs):
        calls.append(1)
        if len(calls) == 13:
            time.sleep(STALL_S)
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, target, slow)
    q = _queries()
    for _ in range(16):
        session.submit(q)
    session.drain()
    seen = _overruns(session._metrics)
    assert list(seen) == [phase], seen
    assert seen[phase][0] == 1
    assert seen[phase][1] == pytest.approx(STALL_S, rel=0.15)


class _Res:
    """What ``_judge`` reads of a retired batch."""

    latency_s, backoffs, deadline_breached = 0.01, (), False

    def __init__(self, seq: int, bucket: int):
        self.seq, self.bucket = seq, bucket


def _cycle(session, monkeypatch, at: float, bucket: int, seq: int,
           inflight: int = 0, **phases):
    """One retire whose cycle held ``phases`` and ended at ``at`` on the
    samples' clock: the rule without the wall clock."""
    monkeypatch.setattr(
        obs_host, "host_sample",
        lambda: obs_host.Sample(at, 0.5 * at, 0, 0, 0.0))
    session._cycle = dict(phases)
    session._inflight.clear()
    session._inflight.extend([None] * inflight)
    try:
        session._judge(_Res(seq, bucket))
    finally:
        session._inflight.clear()


def test_a_clean_run_at_two_bucket_heights_ticks_nothing(
        session, monkeypatch):
    """64 batches, the heights alternating in runs of four, one five times
    as long as the other: against ONE median every tall batch would
    overrun; against its own height's none does. Idle and hold are no part
    of a cycle however long they were."""
    at = 0.0
    for seq in range(64):
        tall = (seq // 4) % 2 == 1
        wait = 0.200 if tall else 0.040
        idle = 3.0 if seq % 7 == 0 else 0.0
        at += wait + 0.004 + idle
        _cycle(session, monkeypatch, at, 32 if tall else 16, seq,
               inflight=1, wait=wait, prep=0.001, enqueue=0.002,
               idle=idle)
    assert _overruns(session._metrics) == {}
    # the pump's CPU time is counted all the same, from the samples
    cpu = session._metrics.counter("serve_pump_cpu_seconds_total").value
    assert cpu == pytest.approx(0.5 * (at - 0.044 - 3.0))


def test_the_rule_arms_after_eight_cycles_and_wants_50_ms(
        session, monkeypatch):
    at, seq = 0.0, 0

    def cycle(wait):
        nonlocal at, seq
        at += wait + 0.001
        _cycle(session, monkeypatch, at, 16, seq, wait=wait)
        seq += 1

    cycle(0.010)  # the first retire begins a cycle, it ends none
    for _ in range(7):
        cycle(0.010)
    cycle(1.0)  # seven cycles before it: not armed
    assert _overruns(session._metrics) == {}
    cycle(0.010)
    cycle(0.055)  # five times the median, 44 ms over it: under the floor
    assert _overruns(session._metrics) == {}
    cycle(0.070)
    seen = _overruns(session._metrics)
    assert list(seen) == ["wait"] and seen["wait"][0] == 1
    assert seen["wait"][1] == pytest.approx(0.060, abs=1e-6)


def test_the_largest_excess_over_its_own_median_names_the_phase(
        session, monkeypatch):
    """``wait`` is the longest phase of every cycle; the one that grew is
    ``reply``. ``other`` is what no span covered."""
    at = 0.0
    for seq in range(12):
        at += 0.050
        _cycle(session, monkeypatch, at, 16, seq, wait=0.040, reply=0.002)
    at += 0.250
    _cycle(session, monkeypatch, at, 16, 12, wait=0.045, reply=0.197)
    at += 0.250
    _cycle(session, monkeypatch, at, 16, 13, wait=0.040, reply=0.002)
    seen = _overruns(session._metrics)
    assert sorted(seen) == ["other", "reply"]
    assert seen["reply"] == (1, pytest.approx(0.200))
    assert seen["other"] == (1, pytest.approx(0.200))


def test_overrun_event_and_mark(session, monkeypatch, tmp_path):
    """The flight record holds one ``overrun`` event with the record's
    fields; the rule changes nothing the session does."""
    path = str(tmp_path / "f.jsonl")
    obs_spans.set_recorder(obs_spans.FlightRecorder(path))
    try:
        at = 0.0
        for seq in range(10):
            at += 0.020
            _cycle(session, monkeypatch, at, 16, seq, wait=0.015)
        at += 0.520
        _cycle(session, monkeypatch, at, 16, 10, inflight=1, wait=0.515)
        at += 0.006  # the batch behind it had long finished
        _cycle(session, monkeypatch, at, 16, 11, inflight=1, wait=0.001)
    finally:
        obs_spans.set_recorder(None)
    _, events = obs_spans.reconstruct_spans(obs_spans.read_flight(path))
    (ev,) = [e for e in events if e["name"] == "overrun"]
    attrs = ev["attrs"]
    assert ev["cat"] == "serve" and attrs["where"] == "wait-host"
    assert attrs["seq"] == 10 and attrs["bucket"] == 16
    assert attrs["excess_ms"] == pytest.approx(500.0)
    assert attrs["next_wait_ms"] == pytest.approx(1.0)
    assert attrs["inflight"] == 1
    assert attrs["phases_ms"]["wait"] == pytest.approx(515.0)
    assert {"gc_ms", "cpu_ms", "nivcsw", "majflt", "median_ms"} <= set(attrs)
    assert attrs["cpu_ms"] == pytest.approx(260.0)
    assert session._rung == 0 and session.deadline_breaches == 0


def test_the_log_line_is_rate_limited_and_owns_up(caplog):
    now = [100.0]
    report = obs_host.OverrunReport("serve_batch", "serve",
                                    clock=lambda: now[0])
    reg = MetricsRegistry()
    with caplog.at_level(logging.WARNING, logger="mpi_knn_tpu"):
        for seq in range(4):
            report(reg, "wait", 0.1, seq=seq)
            now[0] += 0.2
        now[0] += 1.0
        report(reg, "wait", 0.1, seq=4)
        # a stall in the same second as a line already said is said too
        now[0] += 0.2
        report(reg, "wait", 0.15, seq=5)  # not double the last: unsaid
        report(reg, "wait-host", 3.9, seq=6)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 3
    assert "seq=0" in lines[0] and "unsaid_before" not in lines[0]
    assert "seq=4" in lines[1] and "unsaid_before=3" in lines[1]
    assert "seq=6" in lines[2] and "unsaid_before=1" in lines[2]
    # every one of them counted
    assert reg.counter("serve_batch_overruns_total",
                       labels={"where": "wait"}).value == 6


# ---------------------------------------------------------------------------
# the host's own evidence


@pytest.fixture
def quiet_collector():
    """No collection but the test's own."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _gc_counts() -> dict:
    snap = get_registry().snapshot()["metrics"]
    return {name: m["value"] for name, m in snap.items()
            if name.startswith("python_gc_")}


def test_gc_hook_counts_the_old_generations(quiet_collector):
    obs_host.install_gc_hook()
    assert obs_host.install_gc_hook() is False  # once
    assert gc.callbacks.count(obs_host._gc_watch) == 1
    before = _gc_counts()
    assert set(before) == {
        f'python_gc_{what}_total{{generation="{g}"}}'
        for what in ("seconds", "collections") for g in (1, 2)}
    gc.collect(0)
    assert _gc_counts() == before
    junk = [[i] for i in range(20000)]
    for j in junk:
        j.append(junk)
    del junk, j
    sample = obs_host.host_sample()
    gc.collect(2)
    after = _gc_counts()
    n2 = 'python_gc_collections_total{generation="2"}'
    s2 = 'python_gc_seconds_total{generation="2"}'
    assert after[n2] == before[n2] + 1
    assert after[s2] > before[s2]
    assert after['python_gc_collections_total{generation="1"}'] == before[
        'python_gc_collections_total{generation="1"}']
    # the same seconds in the next sample of any thread
    delta = obs_host.host_delta(sample, obs_host.host_sample())
    assert delta["gc_ms"] == pytest.approx(
        1e3 * (after[s2] - before[s2]), abs=1e-3)
    gc.collect(1)
    assert _gc_counts()['python_gc_collections_total{generation="1"}'] == (
        before['python_gc_collections_total{generation="1"}'] + 1)


def test_a_generation_2_collection_is_on_the_traces_host_plane(
        tmp_path, quiet_collector):
    import glob

    obs_host.install_gc_hook()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        gc.collect(1)
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(
        str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = [dict(e.stats) for plane in
             jax.profiler.ProfileData.from_file(pb).planes
             for line in plane.lines for e in line.events
             if e.name == "knn:host.gc"]
    assert len(found) == 1 and found[0]["generation"] == 2


def test_host_sample_reads_this_thread():
    a = obs_host.host_sample()
    x = 0
    for i in range(200000):
        x += i * i
    b = obs_host.host_sample()
    assert b.at > a.at and b.cpu_s > a.cpu_s
    assert b.nivcsw >= a.nivcsw and b.majflt >= a.majflt
    delta = obs_host.host_delta(a, b)
    assert delta["cpu_ms"] > 0 and delta["gc_ms"] >= 0


def test_a_sample_is_this_threads_own(index):
    """CPU seconds are the calling thread's: a thread that only sleeps
    gains none while another one computes, and a session driven from it
    counts its own in ``serve_pump_cpu_seconds_total``."""
    out = {}

    def run():
        s = ServeSession(index)
        s._metrics = MetricsRegistry()
        a = obs_host.host_sample()
        time.sleep(0.1)
        out["slept"] = obs_host.host_delta(a, obs_host.host_sample())
        for _ in range(3):
            s.submit(_queries())
        s.drain()
        out["names"] = set(s._metrics.snapshot()["metrics"])

    t = threading.Thread(target=run)
    t.start()
    x = 0
    while t.is_alive() and x < 10**9:
        x += 1
    t.join(60)
    assert not t.is_alive()
    assert out["slept"]["cpu_ms"] < 50
    assert "serve_pump_cpu_seconds_total" in out["names"]


def test_the_registry_settles_at_every_snapshot_and_once_a_settler():
    reg = MetricsRegistry()
    seen = []
    reg.on_snapshot(seen.append)
    reg.on_snapshot(seen.append)
    reg.snapshot()
    reg.clear()
    reg.to_prometheus()
    assert seen == [reg, reg]


# ---------------------------------------------------------------------------
# the one-shot call


def _call_overruns() -> dict:
    reg = get_registry()
    return {w: reg.counter("knn_call_overruns_total",
                           labels={"where": w}).value
            for w in ("dispatch", "outside")}


@pytest.fixture
def sliced_job():
    """``call()`` is one slice of a job over one device array; nine of them
    have run, so the rule is armed."""
    X = jax.numpy.asarray(np.random.default_rng(1).normal(
        size=(256, DIM)).astype(np.float32))
    cfg = KNNConfig(k=4, backend="serial", corpus_tile=128, query_tile=32)
    api._remembered.clear()
    api._calls.reset()

    def call():
        return all_knn(X, queries=X[:32], config=cfg).ids.block_until_ready()

    for _ in range(10):
        call()
    yield call
    api._remembered.clear()
    api._calls.reset()


def test_a_long_gap_after_the_return_reads_outside(sliced_job, caplog):
    before = _call_overruns()
    hist = get_registry().histogram("knn_call_host_seconds")
    calls = hist.count
    time.sleep(STALL_S)  # the caller, the device, its runtime: not the call
    with caplog.at_level(logging.WARNING, logger="mpi_knn_tpu"):
        sliced_job()
    sliced_job()
    after = _call_overruns()
    assert after["outside"] == before["outside"] + 1
    assert after["dispatch"] == before["dispatch"]
    assert hist.count == calls + 2  # the span's own seconds, once a call
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("overrun api")]
    fields = dict(w.split("=", 1) for w in line.split()[2:])
    assert fields["where"] == "outside" and fields["rows"] == "32"
    assert float(fields["excess_ms"]) == pytest.approx(
        1e3 * STALL_S, rel=0.15)


def test_a_slow_dispatch_reads_dispatch(sliced_job, monkeypatch):
    from mpi_knn_tpu.backends import serial

    before = _call_overruns()
    real = serial.SerialCorpus.search

    def slow(self, *args, **kwargs):
        time.sleep(STALL_S)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(serial.SerialCorpus, "search", slow)
    sliced_job()  # its own host span holds the excess ...
    monkeypatch.setattr(serial.SerialCorpus, "search", real)
    sliced_job()  # ... which the next call's entry closes
    after = _call_overruns()
    assert after["dispatch"] == before["dispatch"] + 1
    assert after["outside"] == before["outside"]


def test_a_miss_begins_anew(sliced_job):
    before = _call_overruns()
    api._remembered.clear()  # the next call prepares its own: a miss
    sliced_job()
    time.sleep(STALL_S)
    sliced_job()  # the first hit after it: nothing to compare with
    sliced_job()
    assert _call_overruns() == before
