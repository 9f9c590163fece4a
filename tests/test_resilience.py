"""Resilient-execution gate (ISSUE 6): the fault-injection matrix, the
isolated worker runner, retry/backoff, the serving degradation ladder,
the doctor preflight, and the bench partial-round banking regression.

Every resilience path is EXERCISED here on CPU, never trusted: an
injected hang must die by heartbeat starvation with a structured
``timeout`` result; an injected transient fault must succeed after N
retries with the exact backoff sequence asserted; an injected NaN must
trip the sentinel loudly with batch provenance; injected deadline
breaches must walk the degradation ladder with each rung's knob change
visible in the batch record and recall still meeting that rung's own
bar. The bench regression pins the BENCH_r05 shape: one wedged series
banks a structured ``"failed": true`` line while every sibling banks its
real measurement and the process exits 0.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from mpi_knn_tpu import KNNConfig, build_index
from mpi_knn_tpu.data.synthetic import make_blobs
from mpi_knn_tpu.ivf import build_ivf_index
from mpi_knn_tpu.resilience import (
    HEARTBEAT_ENV,
    HeartbeatWriter,
    PoisonedResultError,
    ResiliencePolicy,
    RetryExhausted,
    TransientFault,
    backoff_schedule,
    build_ladder,
    fault_point,
    install_faults,
    maybe_beat,
    read_beat,
    retry_with_backoff,
    run_supervised,
)
from mpi_knn_tpu.resilience.faults import parse_fault_env, poison_topk
from mpi_knn_tpu.resilience.ladder import FULL_RUNG
from mpi_knn_tpu.resilience.worker import python_worker_argv
from mpi_knn_tpu.serve import ServeSession

from tests.oracle import oracle_all_knn, recall_against_oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# heartbeat protocol


def test_heartbeat_write_read_roundtrip(tmp_path):
    p = str(tmp_path / "beat.json")
    w = HeartbeatWriter(p)
    assert w.beat("first") == 1
    assert w.beat("second") == 2
    doc = read_beat(p)
    assert doc["seq"] == 2 and doc["label"] == "second"
    assert doc["pid"] == os.getpid()


def test_read_beat_missing_and_torn(tmp_path):
    assert read_beat(str(tmp_path / "never-written.json")) is None
    torn = tmp_path / "torn.json"
    torn.write_text('{"seq": 1, "lab')  # mid-write garbage
    assert read_beat(str(torn)) is None
    notdict = tmp_path / "notdict.json"
    notdict.write_text("[1, 2]")
    assert read_beat(str(notdict)) is None


def test_maybe_beat_noop_without_supervisor(monkeypatch):
    monkeypatch.delenv(HEARTBEAT_ENV, raising=False)
    assert maybe_beat("anything") is None


def test_maybe_beat_under_supervisor_env(tmp_path, monkeypatch):
    p = str(tmp_path / "beat.json")
    monkeypatch.setenv(HEARTBEAT_ENV, p)
    a = maybe_beat("a")
    b = maybe_beat("b")
    assert b == a + 1  # strictly increasing within one process
    assert read_beat(p)["label"] == "b"


# ---------------------------------------------------------------------------
# fault injection


def test_parse_fault_env_specs():
    specs = parse_fault_env(
        "bench-series=hang, serve-batch=transient:2,serve-nan=nan"
    )
    assert specs["bench-series"].kind == "hang"
    assert specs["serve-batch"].kind == "transient"
    assert specs["serve-batch"].arg == 2.0
    assert specs["serve-nan"].kind == "nan"


@pytest.mark.parametrize(
    "bad", ["serve-batch", "serve-batch=explode", "=hang", "x=slow:y"]
)
def test_parse_fault_env_malformed_is_loud(bad):
    # a typo'd fault silently not firing would make a resilience test
    # vacuously green
    with pytest.raises(ValueError):
        parse_fault_env(bad)


def test_transient_fault_fires_n_times_then_clears():
    with install_faults({"site-a": ("transient", 2)}):
        with pytest.raises(TransientFault):
            fault_point("site-a")
        with pytest.raises(TransientFault):
            fault_point("site-a")
        fault_point("site-a")  # third hit succeeds
        fault_point("other-site")  # unarmed sites never fire
    fault_point("site-a")  # disarmed on exit


def test_slow_fault_sleeps():
    with install_faults({"s": ("slow", 0.05)}):
        t0 = time.perf_counter()
        fault_point("s")
        assert time.perf_counter() - t0 >= 0.05


def test_env_driven_fault(monkeypatch):
    monkeypatch.setenv("TKNN_FAULTS", "env-site=transient:1")
    from mpi_knn_tpu.resilience.faults import reset_fault_state

    reset_fault_state()
    with pytest.raises(TransientFault):
        fault_point("env-site")
    fault_point("env-site")
    reset_fault_state()


def test_poison_topk_injects_nan_only_when_armed():
    import jax.numpy as jnp

    d = jnp.ones((4, 3), dtype=jnp.float32)
    assert poison_topk(d) is d  # unarmed: same object, no device work
    with install_faults({"serve-nan": "nan"}):
        out = np.asarray(poison_topk(d))
    assert np.isnan(out[0, 0]) and not np.isnan(out[1:]).any()


# ---------------------------------------------------------------------------
# retry / backoff


def test_backoff_schedule_doubles_and_caps():
    assert backoff_schedule(5, 0.05, 0.2) == (0.05, 0.1, 0.2, 0.2, 0.2)
    assert backoff_schedule(0, 0.05, 0.2) == ()


def test_retry_succeeds_after_n_with_exact_backoff_sequence():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise TransientFault("injected")
        return "payload"

    out = retry_with_backoff(
        flaky, retries=3, base_s=0.05, max_s=2.0, sleep=slept.append
    )
    assert out.value == "payload"
    assert out.attempts == 3
    # the deterministic backoff story, asserted exactly
    assert out.backoffs == (0.05, 0.1)
    assert tuple(slept) == (0.05, 0.1)
    assert out.backoffs == backoff_schedule(3, 0.05, 2.0)[:2]


def test_retry_nonretryable_propagates_immediately():
    def boom():
        raise KeyError("a bug, not a transport blip")

    with pytest.raises(KeyError):
        retry_with_backoff(boom, retries=5, sleep=lambda s: None)


def test_retry_exhausted_carries_cause_and_attempts():
    def always():
        raise TransientFault("never recovers")

    with pytest.raises(RetryExhausted) as e:
        retry_with_backoff(always, retries=1, sleep=lambda s: None)
    assert e.value.attempts == 2  # first try + 1 retry
    assert isinstance(e.value.__cause__, TransientFault)


# ---------------------------------------------------------------------------
# isolated worker runner

_CHILD_OK = textwrap.dedent("""
    from mpi_knn_tpu.resilience.heartbeat import maybe_beat
    maybe_beat("working")
    print("payload-line")
""")

_CHILD_HANG = textwrap.dedent("""
    from mpi_knn_tpu.resilience.faults import fault_point
    from mpi_knn_tpu.resilience.heartbeat import maybe_beat
    maybe_beat("pre-hang")
    fault_point("test-hang")   # armed: blocks forever
""")

_CHILD_SPIN = textwrap.dedent("""
    import time
    from mpi_knn_tpu.resilience.heartbeat import maybe_beat
    while True:
        maybe_beat("spin")
        time.sleep(0.05)
""")


def test_worker_ok_result():
    res = run_supervised(
        python_worker_argv("-c", _CHILD_OK), cwd=REPO, beat_timeout_s=60
    )
    assert res.ok and res.status == "ok" and res.returncode == 0
    assert "payload-line" in res.stdout
    assert res.beats >= 1 and res.last_beat_label == "working"
    assert res.reason is None


def test_worker_injected_hang_killed_by_beat_starvation():
    """ISSUE 6 fault matrix: injected hang → heartbeat kill + structured
    ``timeout`` result (never an exception, never a supervisor hang)."""
    env = dict(os.environ, TKNN_FAULTS="test-hang=hang")
    t0 = time.monotonic()
    res = run_supervised(
        python_worker_argv("-c", _CHILD_HANG),
        env=env, cwd=REPO, beat_timeout_s=1.0, wall_timeout_s=120,
    )
    assert res.status == "timeout" and not res.ok
    assert "beat starvation" in res.reason
    # the kill names the last progress the worker made before wedging
    assert res.beats == 1 and res.last_beat_label == "pre-hang"
    assert time.monotonic() - t0 < 60  # starved, not wall-clocked


def test_worker_wall_timeout_despite_live_beats():
    res = run_supervised(
        python_worker_argv("-c", _CHILD_SPIN),
        cwd=REPO, beat_timeout_s=30, wall_timeout_s=1.0,
    )
    assert res.status == "timeout"
    assert "wall timeout" in res.reason
    assert res.beats >= 1  # it WAS alive; the outer bound fired


def test_worker_crash_is_structured_with_stderr_tail():
    code = "import sys; sys.stderr.write('boom-detail\\n'); sys.exit(3)"
    res = run_supervised(python_worker_argv("-c", code), cwd=REPO)
    assert res.status == "crashed" and res.returncode == 3
    assert "boom-detail" in res.stderr_tail


# ---------------------------------------------------------------------------
# degradation ladder construction


def _serve_cfg(**kw):
    kw.setdefault("k", 4)
    kw.setdefault("query_tile", 16)
    kw.setdefault("corpus_tile", 32)
    kw.setdefault("query_bucket", 32)
    kw.setdefault("dispatch_depth", 1)
    return KNNConfig(backend="serial", **kw)


def test_resilience_policy_validation():
    with pytest.raises(ValueError):
        ResiliencePolicy(max_retries=-1)
    with pytest.raises(ValueError):
        ResiliencePolicy(degrade_after=0)
    with pytest.raises(ValueError):
        ResiliencePolicy(batch_deadline_s=-1.0)
    with pytest.raises(ValueError):
        ResiliencePolicy(min_bucket=0)


def test_build_ladder_dense_serial(rng):
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    rungs = build_ladder(idx, idx.cfg, ResiliencePolicy(min_bucket=16))
    assert [label for label, _ in rungs] == [FULL_RUNG, "mixed", "bucket/16"]
    # cumulative: the bottom rung keeps the mixed policy
    assert rungs[-1][1].precision_policy == "mixed"
    assert rungs[-1][1].query_bucket == 16


def test_build_ladder_skips_unhonorable_rungs(rng):
    X = rng.standard_normal((128, 16)).astype(np.float32)
    # mixed over a bf16-at-rest index is refused by the index's own
    # contract → the rung must not exist; bucket already at the floor →
    # no bucket rung either: the ladder degenerates to [full]
    idx = build_index(X, _serve_cfg(dtype="bfloat16", query_bucket=16))
    rungs = build_ladder(idx, idx.cfg, ResiliencePolicy(min_bucket=16))
    assert [label for label, _ in rungs] == [FULL_RUNG]


def test_build_ladder_ivf_has_nprobe_rung(rng):
    X, _ = make_blobs(256, 16, num_classes=4, seed=3)
    idx = build_ivf_index(
        X, _serve_cfg(partitions=4, nprobe=4, query_bucket=16)
    )
    cfg = idx.compatible_cfg(idx.cfg)
    rungs = build_ladder(idx, cfg, ResiliencePolicy(min_bucket=16))
    labels = [label for label, _ in rungs]
    assert labels[:2] == [FULL_RUNG, "nprobe/2"]  # nprobe sheds FIRST
    assert rungs[1][1].nprobe == 2


# ---------------------------------------------------------------------------
# ServeSession resilience: retry, sentinel, ladder walk


def test_serve_transient_retry_stamps_record_and_keeps_parity(rng):
    X = rng.standard_normal((128, 16)).astype(np.float32)
    Q = rng.standard_normal((8, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    clean = ServeSession(idx).submit(Q)[0]

    pol = ResiliencePolicy(max_retries=3, backoff_base_s=0.01)
    sess = ServeSession(idx, resilience=pol)
    with install_faults({"serve-batch": ("transient", 2)}):
        res = sess.submit(Q)[0]
    # the retry story is stamped on the batch record, exactly
    assert res.retries == 2
    assert res.backoffs == (0.01, 0.02)
    assert sess.retries_total == 2
    # and a retried batch serves the same answer bits as a clean one
    np.testing.assert_array_equal(res.ids, clean.ids)
    np.testing.assert_array_equal(res.dists, clean.dists)


def test_serve_retry_exhausted_raises_loudly(rng):
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    sess = ServeSession(
        idx, resilience=ResiliencePolicy(max_retries=1, backoff_base_s=0.01)
    )
    with install_faults({"serve-batch": ("transient", 5)}):
        with pytest.raises(RetryExhausted):
            sess.submit(np.zeros((4, 16), dtype=np.float32))


def test_serve_nan_sentinel_trips_with_batch_provenance(rng):
    """ISSUE 6 fault matrix: NaN poison in a distance tile → the sentinel
    trips loudly, carrying the provenance an operator needs (batch seq,
    bucket, rung, rows) — never a silently-returned poisoned answer."""
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    sess = ServeSession(idx, resilience=ResiliencePolicy(max_retries=0))
    with install_faults({"serve-nan": "nan"}):
        with pytest.raises(PoisonedResultError) as e:
            sess.submit(np.ones((8, 16), dtype=np.float32))
    # seq is 0-indexed — the SAME number the serve CLI prints on the
    # batch's latency line, so the provenance points at the right line
    assert e.value.batch_seq == 0
    assert e.value.bucket == 32
    assert e.value.rows == 8
    assert e.value.rung == FULL_RUNG


def test_serve_without_policy_is_legacy_shape(rng):
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    sess = ServeSession(idx)
    assert sess.rung == FULL_RUNG and len(sess.ladder) == 1
    res = sess.submit(np.ones((4, 16), dtype=np.float32))[0]
    assert res.degraded is None and res.retries == 0
    assert not res.deadline_breached


def test_degradation_ladder_walk_recall_gated_per_rung(rng):
    """ISSUE 6 acceptance: injected per-batch deadline breaches walk the
    ladder; every degraded batch is stamped; measured recall at each rung
    meets that rung's bar (full: 1.0 exact; mixed: the 0.999 recall@10
    gate of DESIGN.md §6; bucket: bit-identity to the mixed rung — bucket
    size never changes answers)."""
    X = rng.standard_normal((192, 16)).astype(np.float32)
    Q = rng.standard_normal((16, 16)).astype(np.float32)
    k = 4
    odists, oids = oracle_all_knn(X, k, queries=Q)

    idx = build_index(X, _serve_cfg(k=k))
    pol = ResiliencePolicy(
        batch_deadline_s=0.01, degrade_after=1, max_retries=0, min_bucket=16
    )
    sess = ServeSession(idx, resilience=pol)
    assert [label for label, _ in sess.ladder] == [
        FULL_RUNG, "mixed", "bucket/16",
    ]
    # the injected slow batch (20 ms > the 10 ms deadline) is the breach
    # driver — fault-injected, not wall-clock luck
    with install_faults({"serve-batch": ("slow", 0.02)}):
        b1 = sess.submit(Q)[0]  # dispatched at full; breaches
        b2 = sess.submit(Q)[0]  # dispatched at mixed; breaches
        b3 = sess.submit(Q)[0]  # dispatched at bucket/16; breaches
        b4 = sess.submit(Q)[0]  # ladder exhausted: stays at the floor

    # every knob change is visible in the batch records
    assert (b1.degraded, b2.degraded) == (None, "mixed")
    assert b3.degraded == b4.degraded == "bucket/16"
    assert b1.deadline_breached and b3.deadline_breached
    assert (b1.bucket, b2.bucket, b3.bucket) == (32, 32, 16)
    assert sess.deadline_breaches == 4
    assert [d["rung"] for d in sess.degradations] == ["mixed", "bucket/16"]
    assert sess.degradations[0]["after_batch"] == 0  # b1 prints as batch 0
    assert sess.rung == "bucket/16"

    # recall gates, per rung's own bar
    assert recall_against_oracle(b1.ids, odists, oids, k) == 1.0
    assert recall_against_oracle(b2.ids, odists, oids, k) >= 0.999
    assert recall_against_oracle(b3.ids, odists, oids, k) >= 0.999
    # the bucket rung sheds latency by shrinking the unit of work, never
    # by approximating it: bit-identical to the mixed rung's answers
    np.testing.assert_array_equal(b3.ids, b2.ids)
    np.testing.assert_array_equal(b3.dists, b2.dists)


def test_degradation_ladder_ivf_nprobe_rung_recall(rng):
    """The clustered rung: deadline breach first sheds nprobe (the
    cheapest recall spend — its bar is the index's own recall_target)."""
    X, _ = make_blobs(256, 16, num_classes=4, seed=7)
    Q = X[:16] + rng.normal(scale=0.01, size=(16, 16)).astype(np.float32)
    Q = Q.astype(np.float32)
    k = 4
    odists, oids = oracle_all_knn(X, k, queries=Q)

    idx = build_ivf_index(X, _serve_cfg(k=k, partitions=4, nprobe=4))
    cfg = idx.compatible_cfg(idx.cfg)
    pol = ResiliencePolicy(
        batch_deadline_s=0.01, degrade_after=1, max_retries=0
    )
    sess = ServeSession(idx, resilience=pol)
    assert sess.ladder[1][0] == "nprobe/2"
    with install_faults({"serve-batch": ("slow", 0.02)}):
        b1 = sess.submit(Q)[0]  # full: nprobe=4 == partitions, exact
        b2 = sess.submit(Q)[0]  # degraded: nprobe=2

    assert b1.degraded is None and b2.degraded == "nprobe/2"
    assert recall_against_oracle(b1.ids, odists, oids, k) == 1.0
    # the rung's bar is the configured recall_target, the same bar the
    # IVF tuner gates on
    assert recall_against_oracle(b2.ids, odists, oids, k) >= cfg.recall_target


def test_warm_precompiles_every_ladder_rung(rng):
    """The first batch after a degradation lands at the moment of
    overload — warm() must pre-compile every rung's cell so a cold
    compile cannot itself breach the deadline and cascade the ladder."""
    from mpi_knn_tpu.obs.metrics import watch_compiles

    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    pol = ResiliencePolicy(
        batch_deadline_s=0.01, degrade_after=1, max_retries=0, min_bucket=16
    )
    sess = ServeSession(idx, resilience=pol)
    sess.warm([16])

    with watch_compiles() as compiles:
        with install_faults({"serve-batch": ("slow", 0.02)}):
            for _ in range(len(sess.ladder) + 1):
                sess.submit(np.ones((16, 16), dtype=np.float32))
    assert sess.rung == sess.ladder[-1][0]  # the ladder WAS walked
    assert compiles == []  # ...with zero compiles after warm()


def test_cli_inert_resilience_knobs_refused(rng, capsys):
    """--degrade-after / --no-nan-sentinel without a policy-activating
    flag are refused with exit 2, never silently inert (the serve CLI's
    convention for knobs that would not apply)."""
    from mpi_knn_tpu.serve.cli import main as query_main

    for extra in (
        ["--degrade-after", "5"],
        ["--no-nan-sentinel"],
        # degradation is deadline-driven: --retries alone activates a
        # policy, but --degrade-after still can never trigger
        ["--retries", "2", "--degrade-after", "3"],
    ):
        rc = query_main(
            ["--data", "synthetic:64x8c4", "--synthetic", "8", *extra]
        )
        assert rc == 2
        assert "silently inert" in capsys.readouterr().err


def test_retry_backoff_excluded_from_deadline(rng):
    """Backoff sleeps are self-inflicted waiting on a transient fault,
    not load: a retried batch whose compute fits the deadline must not
    count as a breach (two transport blips would otherwise walk the
    one-way ladder and spend recall on a problem smaller programs cannot
    fix). latency_s itself stays the honest dispatch→sync total."""
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    pol = ResiliencePolicy(
        batch_deadline_s=0.15, degrade_after=1, max_retries=2,
        backoff_base_s=0.3,
    )
    sess = ServeSession(idx, resilience=pol)
    Q = np.ones((8, 16), dtype=np.float32)
    sess.submit(Q)  # warm: the compile must not be the measured batch
    with install_faults({"serve-batch": ("transient", 1)}):
        res = sess.submit(Q)[0]
    assert res.retries == 1 and res.backoffs == (0.3,)
    assert res.latency_s > 0.3  # the honest total includes the backoff
    assert not res.deadline_breached
    assert sess.degradations == [] and res.degraded is None


def test_no_degradation_without_breach(rng):
    X = rng.standard_normal((128, 16)).astype(np.float32)
    idx = build_index(X, _serve_cfg())
    pol = ResiliencePolicy(batch_deadline_s=1e6, degrade_after=1)
    sess = ServeSession(idx, resilience=pol)
    for _ in range(3):
        res = sess.submit(np.ones((8, 16), dtype=np.float32))[0]
        assert res.degraded is None and not res.deadline_breached
    assert sess.deadline_breaches == 0 and sess.degradations == []


# ---------------------------------------------------------------------------
# doctor preflight


def test_doctor_probe_healthy_cpu():
    from mpi_knn_tpu.resilience.doctor import run_probe

    env = {k: v for k, v in os.environ.items() if k != "TKNN_FAULTS"}
    verdict = run_probe(platform="cpu", env=env)
    assert verdict["ok"] is True and verdict["status"] == "ok"
    assert verdict["probe"]["device_count"] >= 1
    assert verdict["probe"]["platform"] == "cpu"
    assert verdict["probe"]["jit_probe_s"] > 0
    assert verdict["beats"] >= 4  # start/platform/jax-import/devices/jit


def test_doctor_probe_injected_hang_times_out():
    """ISSUE 6 satellite: a wedged device wedges the probe CHILD, never
    the caller — the verdict is a structured timeout, exit path 1."""
    from mpi_knn_tpu.resilience.doctor import run_probe

    env = dict(os.environ, TKNN_FAULTS="doctor-probe=hang")
    verdict = run_probe(
        platform="cpu", beat_timeout_s=1.0, wall_timeout_s=60, env=env
    )
    assert verdict["ok"] is False and verdict["status"] == "timeout"
    assert "beat starvation" in verdict["reason"]
    assert verdict["probe"] is None


def test_doctor_cli_exit_codes():
    env = {k: v for k, v in os.environ.items() if k != "TKNN_FAULTS"}
    r = subprocess.run(
        [sys.executable, "-m", "mpi_knn_tpu", "doctor", "--platform", "cpu"],
        capture_output=True, text=True, cwd=REPO, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    verdict = json.loads(r.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True

    env_wedged = dict(env, TKNN_FAULTS="doctor-probe=hang")
    r = subprocess.run(
        [sys.executable, "-m", "mpi_knn_tpu", "doctor", "--platform", "cpu",
         "--timeout", "1"],
        capture_output=True, text=True, cwd=REPO, timeout=300, env=env_wedged,
    )
    assert r.returncode == 1
    verdict = json.loads(r.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False and verdict["status"] == "timeout"


# ---------------------------------------------------------------------------
# bench supervisor: partial-round banking (the BENCH_r05 regression)


def test_bench_partial_round_banks_siblings_of_a_wedged_series():
    """ISSUE 6 acceptance: with an injected hang in ONE bench series,
    `python bench.py` exits 0, banks every other series' real measurement
    line, and emits a structured `"failed": true` line (not a bare
    watchdog error) for the wedged one. A third series with conflicting
    knobs exercises the usage-error path: exit-2 children are a config
    bug and are never banked."""
    series = [
        {"name": "good"},
        # its own short leash: the overlay overrides the beat bound so
        # the healthy sibling keeps the full first-compile allowance
        {"name": "wedged", "BENCH_K": "5",
         "TKNN_FAULTS": "bench-series=hang",
         "BENCH_BEAT_TIMEOUT_S": "2"},
        {"name": "badknobs", "BENCH_RING_SCHEDULE": "bidir"},
    ]
    env = dict(
        os.environ,
        BENCH_PLATFORM="cpu", BENCH_M="800", BENCH_REPS="1",
        BENCH_SERIES=json.dumps(series),
    )
    env.pop("TKNN_FAULTS", None)
    r = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        cwd=REPO, timeout=420, env=env,
    )
    assert r.returncode == 0, (r.stdout, r.stderr[-3000:])
    lines = [json.loads(ln) for ln in r.stdout.strip().splitlines() if ln]
    assert len(lines) == 2, r.stdout  # good + wedged; badknobs NOT banked

    good, wedged = lines
    # the completed sibling banks its REAL measurement line, untouched
    assert set(good) == {"metric", "value", "unit", "vs_baseline"}
    assert good["metric"] == "mnist0k_allknn_k10_seconds"
    assert good["value"] > 0 and "failed" not in good

    # the wedged series banks a structured failed line under its own
    # series name — never a bare rc-2 watchdog error. ISSUE 7 shape: a
    # kill is NOT a measurement — value is null, the kill time lives in
    # the explicit time_until_kill_s field, and no vs_baseline can ever
    # be read off the line (BENCH_r05 banked value:480/vs_baseline:0.0)
    assert wedged["failed"] is True
    assert wedged["metric"] == "mnist0k_allknn_k5_seconds"
    assert wedged["series"] == "wedged" and wedged["status"] == "timeout"
    assert wedged["value"] is None
    assert "vs_baseline" not in wedged
    assert 0 < wedged["time_until_kill_s"] < 60  # starvation, not wall
    # the child's span flight record survives the SIGKILL and is banked
    # alongside (the 'start' beat fired before the injected hang)
    assert wedged["flight"]["records"] >= 1

    # supervisor notes: the kill reason and the usage-error refusal are
    # on stderr for the operator, non-JSON (harness tooling reads the
    # last '{'-line as the context object)
    assert "beat starvation" in r.stderr
    assert "usage error" in r.stderr


def test_bench_malformed_series_is_loud():
    env = dict(os.environ, BENCH_SERIES="not json at all")
    r = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        cwd=REPO, timeout=60, env=env,
    )
    assert r.returncode == 2
    assert r.stdout.strip() == ""  # no measurement lines from a typo
    assert "bad BENCH_SERIES" in r.stderr
