"""Host concurrency lint (ISSUE 13): injected counterexamples through
the production rule path, the clean production sweep, the runtime
witness layer, and regression tests for the real races the lint
surfaced in the pre-existing code.

Convention (since R1): every counterexample is a deliberately broken
input fed through the EXACT production engine (``run_host_lint`` — the
function ``mpi-knn lint --host`` calls), never a hand-driven rule
object. The production sweep itself is asserted clean — zero non-waived
findings, waivers enumerated with rationale, lock-acquisition graph
acyclic FROM THE REPORT — via the real CLI.
"""

from __future__ import annotations

import json
import textwrap
import threading

import pytest

from mpi_knn_tpu.analysis.host import (
    ClassGuard,
    GuardMap,
    HostTarget,
    run_host_lint,
)
from mpi_knn_tpu.analysis.host.witness import (
    InstrumentedLock,
    WitnessLog,
    instrument,
)


def _target(tmp_path, name: str, src: str) -> HostTarget:
    p = tmp_path / f"{name}.py"
    p.write_text(textwrap.dedent(src))
    return HostTarget(name, ((name, str(p)),))


def _findings(report, rule=None):
    return [
        f for f in report.findings if rule is None or f.rule == rule
    ]


# ---------------------------------------------------------------------------
# injected counterexamples (>= 8, each through run_host_lint)


def test_unguarded_write_fires(tmp_path):
    """H1: a guarded attribute written with no lock held."""
    t = _target(tmp_path, "cx1", """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def _run(self):
                self.count += 1  # no lock

            def start(self):
                threading.Thread(target=self._run).start()

            def read(self):
                with self._lock:
                    return self.count
    """)
    g = GuardMap()
    g.classes["cx1.W"] = ClassGuard(guarded={"count": "_lock"})
    rep = run_host_lint([t], guards=g)
    f = _findings(rep, "H1-lock-discipline")
    assert len(f) == 1 and f[0].where == "cx1.W._run"
    assert "with no lock held" in f[0].message
    assert not rep.ok


def test_wrong_lock_guard_fires(tmp_path):
    """H1: the access holds A lock — just not the declared one."""
    t = _target(tmp_path, "cx2", """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._other = threading.Lock()
                self.items = []

            def _run(self):
                with self._other:
                    self.items.append(1)

            def start(self):
                threading.Thread(target=self._run).start()
    """)
    g = GuardMap()
    g.classes["cx2.W"] = ClassGuard(guarded={"items": "_lock"})
    rep = run_host_lint([t], guards=g)
    f = _findings(rep, "H1-lock-discipline")
    assert len(f) == 1 and "WRONG lock" in f[0].message
    assert "cx2.W._other" in f[0].message


def test_lock_order_cycle_fires(tmp_path):
    """H2: A->B lexically, B->A through the call graph — a cycle, found
    statically and named in the report's lock graph."""
    t = _target(tmp_path, "cx3", """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def forward():
            with _a:
                with _b:
                    pass

        def backward():
            with _b:
                helper()

        def helper():
            with _a:
                pass
    """)
    rep = run_host_lint([t], guards=GuardMap())
    f = _findings(rep, "H2-lock-order")
    assert len(f) == 1 and "cycle" in f[0].message
    assert rep.lock_graph.cycles == [["cx3:_a", "cx3:_b"]]
    assert not rep.lock_graph.acyclic and not rep.ok


def test_self_deadlock_fires(tmp_path):
    """H2: re-acquiring a held non-reentrant lock through a call."""
    t = _target(tmp_path, "cx3b", """
        import threading

        _m = threading.Lock()

        def outer():
            with _m:
                inner()

        def inner():
            with _m:
                pass
    """)
    rep = run_host_lint([t], guards=GuardMap())
    f = _findings(rep, "H2-lock-order")
    assert len(f) == 1 and "self-deadlock" in f[0].message


def test_confinement_breach_from_http_handler_fires(tmp_path):
    """H3: a pump-confined attribute reachable from a declared
    HTTP-handler root."""
    t = _target(tmp_path, "cx4", """
        import threading

        class Pump:
            def __init__(self):
                self.inflight = []

            def _run(self):
                self.inflight.append(1)

            def start(self):
                threading.Thread(target=self._run).start()

        class Handler:
            def do_GET(self):
                return peek(self)

        def peek(handler):
            return len(PUMP.inflight)
    """)
    g = GuardMap()
    g.classes["cx4.Pump"] = ClassGuard(confined={"inflight": "pump"})
    g.roots["pump"] = ["cx4.Pump._run"]
    g.roots["http-handler"] = ["cx4.Handler.do_GET"]
    g.name_types["cx4"] = {"PUMP": "cx4.Pump"}
    rep = run_host_lint([t], guards=g)
    f = _findings(rep, "H3-confinement")
    assert len(f) == 1 and f[0].where == "cx4.peek"
    assert "http-handler" in f[0].message


def test_bare_open_w_in_cache_writer_fires(tmp_path):
    """H4: a bare truncating write in a threaded cache-entry writer —
    and the temp+os.replace idiom in the same module passes."""
    t = _target(tmp_path, "cx5", """
        import os

        def store_entry(path, blob):
            with open(path, "wb") as f:   # torn-read window
                f.write(blob)

        def store_entry_atomic(path, blob):
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
    """)
    rep = run_host_lint([t], guards=GuardMap())
    f = _findings(rep, "H4-atomic-publish")
    assert len(f) == 1 and f[0].where == "cx5.store_entry"
    # the atomic variant is untouched; a waiver silences the bare one
    g = GuardMap()
    g.h4_waivers["cx5.store_entry"] = "test-only artifact, single writer"
    rep2 = run_host_lint([t], guards=g)
    assert not _findings(rep2, "H4-atomic-publish")
    assert any("store_entry" in str(w["where"]) for w in rep2.waivers)


def test_undeclared_shared_attribute_fires(tmp_path):
    """H1 enforcement teeth: an attribute in NO guard map, mutated
    outside __init__, touched from two thread roots."""
    t = _target(tmp_path, "cx6", """
        import threading

        class S:
            def __init__(self):
                self.state = {}

            def _writer(self):
                self.state["x"] = 1

            def _reader(self):
                return dict(self.state)

            def start(self):
                threading.Thread(target=self._writer).start()
                threading.Thread(target=self._reader).start()
    """)
    rep = run_host_lint([t], guards=GuardMap())
    f = _findings(rep, "H1-lock-discipline")
    assert len(f) == 1 and "undeclared shared attribute" in f[0].message
    assert "cx6.S.state" == f[0].attr


def test_waiver_honored_and_counted(tmp_path):
    """The same undeclared-shared module goes green under an explicit
    waiver — and the waiver is enumerated in the report (it cannot
    accrete silently)."""
    t = _target(tmp_path, "cx6", """
        import threading

        class S:
            def __init__(self):
                self.state = {}

            def _writer(self):
                self.state["x"] = 1

            def _reader(self):
                return dict(self.state)

            def start(self):
                threading.Thread(target=self._writer).start()
                threading.Thread(target=self._reader).start()
    """)
    g = GuardMap()
    g.classes["cx6.S"] = ClassGuard(
        waivers={"state": "benign last-write-wins cache (test)"}
    )
    rep = run_host_lint([t], guards=g)
    assert rep.ok and not rep.findings
    assert rep.waivers == [{
        "where": "cx6.S.state",
        "rationale": "benign last-write-wins cache (test)",
    }]
    assert rep.to_json()["summary"]["waivers"] == 1


def test_clean_module_green(tmp_path):
    """A correctly-locked module produces zero findings and the right
    lock-order edge."""
    t = _target(tmp_path, "cx7", """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._inner = threading.Lock()
                self.count = 0

            def _run(self):
                with self._lock:
                    self.count += 1
                    with self._inner:
                        pass

            def start(self):
                threading.Thread(target=self._run).start()

            def read(self):
                with self._lock:
                    return self.count
    """)
    g = GuardMap()
    g.classes["cx7.W"] = ClassGuard(guarded={"count": "_lock"})
    rep = run_host_lint([t], guards=g)
    assert rep.ok and not rep.findings
    assert ("cx7.W._lock", "cx7.W._inner") in set(rep.lock_graph.edges)
    assert rep.lock_graph.acyclic


def test_undeclared_global_fires_and_module_guard_passes(tmp_path):
    """H1 on module globals: an unguarded lazy singleton fires; the
    declared module lock silences it when actually held."""
    src = """
        import threading

        _lock = threading.Lock()
        _cached = None

        def get(make):
            global _cached
            {body}

        def worker(make):
            get(make)

        def start(make):
            threading.Thread(target=worker, args=(make,)).start()
            threading.Thread(target=worker, args=(make,)).start()
    """
    bad = _target(tmp_path, "cx8", src.format(body="""
            if _cached is None:
                _cached = make()
            return _cached"""))
    rep = run_host_lint([bad], guards=GuardMap())
    f = _findings(rep, "H1-lock-discipline")
    assert f and "module global" in f[0].message
    good = _target(tmp_path, "cx8b", src.format(body="""
            with _lock:
                if _cached is None:
                    _cached = make()
                return _cached"""))
    g = GuardMap()
    g.module_guards["cx8b"] = {"_cached": "cx8b:_lock"}
    rep2 = run_host_lint([good], guards=g)
    assert rep2.ok


def test_stale_guard_map_is_a_problem(tmp_path):
    """A declared root naming a function that no longer exists makes
    the report NOT ok — config rot cannot silently hollow the lint."""
    t = _target(tmp_path, "cx9", """
        def f():
            return 1
    """)
    g = GuardMap()
    g.roots["pump"] = ["cx9.gone"]
    rep = run_host_lint([t], guards=g)
    assert not rep.ok and rep.problems


# ---------------------------------------------------------------------------
# the production sweep, via the production CLI


def test_production_sweep_clean_via_cli(tmp_path, capsys):
    """``mpi-knn lint --host``: exit 0 over all seven threaded-module
    targets, zero non-waived findings, waivers enumerated with
    rationale, and the lock-acquisition graph asserted acyclic FROM THE
    REPORT (the ISSUE 13 acceptance)."""
    from mpi_knn_tpu.analysis.cli import main as lint_main

    rc = lint_main(["--host", "-q", "--out", str(tmp_path)])
    assert rc == 0
    doc = json.loads((tmp_path / "host_report.json").read_text())
    assert doc["ok"] is True
    assert doc["summary"]["findings"] == 0
    assert doc["summary"]["problems"] == 0
    # all seven targets, each individually ok (serve.mutate joined in
    # ISSUE 14: the background compactor thread)
    names = {t["name"] for t in doc["targets"]}
    assert names == {
        "frontend", "serve.engine", "serve.mutate", "serve.aotcache",
        "obs.metrics", "obs.spans", "resilience.worker",
    }
    assert all(t["ok"] for t in doc["targets"])
    # the lock graph is present, non-trivial, and acyclic
    lg = doc["lock_graph"]
    assert lg["acyclic"] is True and lg["cycles"] == []
    assert "serve.engine.ServeSession._stats_lock" in lg["nodes"]
    assert ["frontend.server.Frontend._lock",
            "serve.engine.ServeSession._stats_lock"] in lg["edges"]
    # waivers are enumerated, each with a non-empty rationale
    assert doc["summary"]["waivers"] == len(doc["waivers"]) > 0
    assert all(w["rationale"].strip() for w in doc["waivers"])
    # the thread roots the rules reasoned about are the serving stack's
    assert "dispatch-pump" in doc["roots"]
    assert "http-handler" in doc["roots"]
    assert "warm-pool" in doc["roots"]


def test_host_rule_filter_and_usage_error(tmp_path):
    from mpi_knn_tpu.analysis.cli import main as lint_main

    assert lint_main(["--host", "-q", "--out", str(tmp_path),
                      "--rule", "H2-lock-order"]) == 0
    doc = json.loads((tmp_path / "host_report.json").read_text())
    assert list(doc["rules"]) == ["H2-lock-order"]
    assert lint_main(["--host", "--rule", "H9-nope"]) == 2


def test_production_sweep_would_catch_the_fixed_races(tmp_path):
    """The regression pin for the real pre-existing bugs this PR fixed:
    re-introduce the old unguarded patterns in a fixture mirroring the
    production classes and guard map — warm_state published without its
    lock, a histogram snapshot reading counts barewise, the /healthz
    path reading session window stats raw — and the production rules
    fire on every one."""
    t = _target(tmp_path, "old", """
        import threading

        class Session:
            def __init__(self):
                self._warm_lock = threading.Lock()
                self._stats_lock = threading.Lock()
                self.warm_state = {}
                self.latencies = []

            def warm(self):
                self.warm_state = {"total": 1}  # old bug: no lock

            def retire(self):
                with self._stats_lock:
                    self.latencies.append(1.0)

        class Front:
            def __init__(self, session):
                self._lock = threading.Lock()
                self.session = session

            def _run(self):
                self.session.retire()

            def start(self):
                threading.Thread(target=self._run).start()

            def stats(self):
                ses = self.session
                with self._lock:
                    return len(ses.latencies), dict(ses.warm_state)
    """)
    g = GuardMap()
    g.classes["old.Session"] = ClassGuard(guarded={
        "warm_state": "_warm_lock", "latencies": "_stats_lock",
    })
    g.attr_types["old.Front.session"] = "old.Session"
    g.roots["http-handler"] = ["old.Front.stats"]
    g.roots["warm-pool"] = ["old.Session.warm"]
    rep = run_host_lint([t], guards=g)
    assert {f.attr for f in rep.findings} == {
        "old.Session.latencies", "old.Session.warm_state",
    }
    assert len(rep.findings) == 3  # warm write + two raw stats reads
    assert {f.where for f in rep.findings} == {
        "old.Session.warm", "old.Front.stats",
    }


# ---------------------------------------------------------------------------
# runtime witnesses (armed in tests only)


def test_witness_observes_lock_order_inversion():
    """The dynamic twin of the H2 counterexample: both orders of a lock
    pair observed at runtime → a reported inversion. (The two orders
    run sequentially — observing an inversion must not require actually
    deadlocking.)"""
    log = WitnessLog()
    a = InstrumentedLock("A", log)
    b = InstrumentedLock("B", log)

    def forward():
        with a:
            with b:
                pass

    def backward():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=forward)
    t1.start(); t1.join()
    t2 = threading.Thread(target=backward)
    t2.start(); t2.join()
    assert log.inversions() == {("A", "B")}
    assert {("A", "B"), ("B", "A")} <= log.ordered_pairs()


def test_witness_observes_guard_violation():
    """The dynamic twin of the H1 counterexample: an access recorded
    without its declared lock held is a violation; the guarded access
    is not."""
    log = WitnessLog()
    lock = InstrumentedLock("W._lock", log)
    state = {"count": 0}

    def guarded():
        with lock:
            state["count"] += 1
            log.note_access("W.count", "write")

    def unguarded():
        state["count"] += 1
        log.note_access("W.count", "write")

    t = threading.Thread(target=guarded)
    t.start(); t.join()
    t = threading.Thread(target=unguarded)
    t.start(); t.join()
    bad = log.guard_violations({"W.count": "W._lock"})
    assert len(bad) == 1 and bad[0].held == ()


def test_witness_instruments_production_registry():
    """instrument() swaps a REAL MetricsRegistry's lock for the
    recording wrapper: driving the production get-or-create path shows
    the acquisition, and no ordering is ever observed against a metric's
    own lock (the registry releases before the metric snapshots — the
    disjoint-critical-section design the lock graph also shows)."""
    from mpi_knn_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    log = WitnessLog()
    with instrument(reg, log, "_lock", prefix="obs."):
        c = reg.counter("witness_total", help="x")
        c.inc()
        reg.snapshot()
    names = [ev.lock for ev in log.acquires]
    assert names.count("obs.MetricsRegistry._lock") >= 2  # create + snapshot
    assert log.inversions() == set()


# ---------------------------------------------------------------------------
# regression tests for the real races the lint surfaced


def test_histogram_snapshot_consistent_under_concurrent_observe():
    """Pre-fix, Histogram.snapshot read counts/sum/count outside the
    lock: a scrape racing observe() could export counts summing to
    count±1. Post-fix every snapshot is internally consistent."""
    from mpi_knn_tpu.obs.metrics import Histogram

    h = Histogram("lat", buckets=(0.1, 1.0, 10.0))
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            h.observe((i % 40) * 0.3)
            i += 1

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(300):
            snap = h.snapshot()
            assert sum(snap["counts"]) == snap["count"]
    finally:
        stop.set()
        t.join()
    assert h.count == sum(h.snapshot()["counts"])


def test_counter_snapshot_takes_lock():
    from mpi_knn_tpu.obs.metrics import Counter, Gauge

    c = Counter("c_total")
    c.inc(2.5)
    assert c.snapshot()["value"] == 2.5 and c.value == 2.5
    g = Gauge("g")
    g.set(4.0)
    g.add(-1.0)
    assert g.snapshot()["value"] == 3.0


def test_get_recorder_returns_one_instance_across_threads(
    tmp_path, monkeypatch
):
    """Pre-fix, two threads could lazily construct two FlightRecorders
    onto one TKNN_FLIGHT_RECORD path (interleaved ring generations).
    Post-fix the module lock makes the singleton real."""
    from mpi_knn_tpu.obs import spans

    monkeypatch.setenv(spans.RECORDER_ENV, str(tmp_path / "fl.jsonl"))
    monkeypatch.setattr(spans, "_env_recorder", None)
    monkeypatch.setattr(spans, "_recorder", None)
    monkeypatch.setattr(spans, "_active", None)  # get_recorder publishes it
    got = []
    barrier = threading.Barrier(8)

    def grab():
        barrier.wait()
        got.append(spans.get_recorder())

    threads = [threading.Thread(target=grab) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len({id(r) for r in got}) == 1 and got[0] is not None


def test_warm_and_stats_snapshots_are_consistent_copies(rng):
    """The ServeSession cross-thread readers added for the /healthz
    path: warm_snapshot/stats_snapshot return consistent COPIES (a
    reader mutating one cannot corrupt session state), and the posture
    matches the session's own window."""
    import numpy as np

    from mpi_knn_tpu.config import KNNConfig
    from mpi_knn_tpu.serve import ServeSession, build_index

    X = rng.standard_normal((192, 16)).astype(np.float32)
    cfg = KNNConfig(k=3, backend="serial", query_bucket=16,
                    corpus_tile=64, query_tile=32)
    sess = ServeSession(build_index(X, cfg))
    sess.warm([16])
    ws = sess.warm_snapshot()
    assert ws["done"] is True and ws["total"] >= 1
    ws["ready"] = -99
    assert sess.warm_snapshot()["ready"] != -99
    list(sess.stream([X[:8], X[:12]]))
    st = sess.stats_snapshot()
    assert st["batches_retired"] == 2
    assert st["queries_served"] == 20
    assert st["rung"] == sess.rung
    st["tenants"].append("ghost")
    assert sess.stats_snapshot()["tenants"] == []


def test_atomic_write_publishes_whole_content(tmp_path):
    """utils.atomicio: concurrent writers + a polling reader — the
    reader only ever sees a COMPLETE document (the H4 property the
    ready-file/heartbeat/aotcache writers now share)."""
    from mpi_knn_tpu.utils.atomicio import atomic_write_text

    path = tmp_path / "ready"
    docs = [f"url-{i}" * 200 + "\n" for i in range(50)]
    seen = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                seen.append(path.read_text())
            except OSError:
                pass

    t = threading.Thread(target=reader)
    t.start()
    try:
        for d in docs:
            atomic_write_text(path, d)
    finally:
        stop.set()
        t.join()
    assert path.read_text() == docs[-1]
    assert all(s in docs for s in seen if s)
    # no temp-file litter
    assert [p.name for p in tmp_path.iterdir()] == ["ready"]


def test_heartbeat_still_atomic_via_shared_helper(tmp_path):
    """The heartbeat writer refactored onto utils.atomicio keeps its
    protocol: strictly-increasing seq, readable mid-overwrite."""
    from mpi_knn_tpu.resilience.heartbeat import HeartbeatWriter, read_beat

    w = HeartbeatWriter(str(tmp_path / "beat.json"))
    assert w.beat("a") == 1
    assert w.beat("b") == 2
    doc = read_beat(str(tmp_path / "beat.json"))
    assert doc is not None and doc["seq"] == 2 and doc["label"] == "b"


def test_report_shape_and_save(tmp_path):
    """host_report.json carries schema, rules, roots, lock graph,
    waivers — the fields the check.sh gate pins."""
    rep = run_host_lint()
    path = rep.save(tmp_path)
    doc = json.loads(path.read_text())
    assert doc["schema_version"] == 1
    assert doc["source"] == "mpi_knn_tpu.analysis.host"
    assert set(doc["rules"]) == {
        "H1-lock-discipline", "H2-lock-order", "H3-confinement",
        "H4-atomic-publish",
    }
    assert doc["summary"]["targets"] == 7
    assert doc["summary"]["classes_checked"] >= 15
    s = doc["summary"]
    assert s["lock_graph_acyclic"] and s["findings"] == 0


@pytest.mark.parametrize("rule", [
    "H1-lock-discipline", "H2-lock-order", "H3-confinement",
    "H4-atomic-publish",
])
def test_each_rule_runs_clean_alone_on_production(rule):
    rep = run_host_lint(rule_names=[rule])
    assert rep.ok, [f.to_json() for f in rep.findings]
