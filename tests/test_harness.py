"""Harness: timing, report, checkpoint/resume, CLI."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from scripts import trace_ops

from mpi_knn_tpu import KNNConfig, all_knn
from mpi_knn_tpu.backends.resumable import all_knn_resumable
from mpi_knn_tpu.cli import main as cli_main
from mpi_knn_tpu.data.matfile import write_mat
from mpi_knn_tpu.data.synthetic import make_blobs
from mpi_knn_tpu.utils.checkpoint import load_checkpoint, fingerprint
from mpi_knn_tpu.utils.report import RunReport, recall_at_k
from mpi_knn_tpu.utils.timing import PhaseTimer


# ------------------------------------------------------------------ timing


def test_phase_timer_accumulates():
    t = PhaseTimer()
    with t.phase("a"):
        pass
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass
    assert set(t.seconds) == {"a", "b"}
    assert t.seconds["a"] >= 0


# ------------------------------------------------------------------ report


def test_recall_at_k_exact_and_partial():
    got = np.array([[1, 2, 3], [4, 5, 6]])
    want = np.array([[3, 2, 1], [4, 5, 9]])
    assert recall_at_k(got, got) == 1.0
    assert recall_at_k(got, want) == pytest.approx(5 / 6)


def test_recall_ignores_invalid_baseline_slots():
    got = np.array([[1, 2, -1]])
    want = np.array([[1, 2, -1]])
    assert recall_at_k(got, want) == 1.0


def test_report_json_roundtrip(tmp_path):
    r = RunReport(config={"k": 5}, data_source="synthetic", shape=(10, 4))
    r.matches = 9
    p = tmp_path / "r.json"
    r.save(p)
    back = json.loads(p.read_text())
    assert back["matches"] == 9
    assert back["environment"]["platform"] == "cpu"


# ------------------------------------------------------------------ checkpoint


def _resume_case(tmp_path, save_every=2):
    X, _ = make_blobs(120, 8, seed=5)
    cfg = KNNConfig(k=6, query_tile=16, corpus_tile=16, backend="serial")
    qids = np.arange(len(X), dtype=np.int32)
    return X, cfg, qids


def test_resumable_matches_serial(tmp_path, rng):
    X, cfg, qids = _resume_case(tmp_path)
    d, i = all_knn_resumable(X, X, qids, cfg, checkpoint_dir=None)
    base = all_knn(X, config=cfg)
    # chunked execution may reassociate fp ops; ids must match exactly
    np.testing.assert_allclose(
        np.asarray(d), np.asarray(base.dists), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(i), np.asarray(base.ids))


def test_resumable_never_resumes_a_carry_made_under_the_other_offset(
        tmp_path, rng):
    """PR 29 centres a whole-number corpus by its ROUNDED mean: a carry a
    run saved under the plain mean (every run before it) differs by fp
    noise and must not be merged into. The whole-number offset is part of
    the run's identity; its own checkpoints resume."""
    from mpi_knn_tpu.utils.checkpoint import KNNCheckpoint, save_checkpoint

    X = rng.integers(0, 256, (96, 8)).astype(np.float32)
    cfg = KNNConfig(k=6, query_tile=16, corpus_tile=16, backend="serial")
    qids = np.arange(len(X), dtype=np.int32)
    ck = tmp_path / "ck"
    old_fp = fingerprint(X, X, cfg)  # what the parent wrote for this run
    save_checkpoint(ck, KNNCheckpoint(
        carry_d=np.zeros((6, 16, 6), np.float32),  # poison: all "found"
        carry_i=np.zeros((6, 16, 6), np.int32),
        tiles_done=6, fingerprint=old_fp))
    seen = []
    d, i = all_knn_resumable(
        X, X, qids, cfg, checkpoint_dir=ck, save_every=3,
        progress_cb=lambda done, total: seen.append(done))
    assert seen == [3, 6]  # restarted from tile 0, not resumed at 6
    base = all_knn(X, config=cfg)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(base.dists))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(base.ids))
    assert load_checkpoint(ck, old_fp) is None
    state = load_checkpoint(ck, old_fp + ":ctr-whole")
    assert state is not None and state.tiles_done == 6
    # fractional data keeps the identity it always had
    Xf = X + 0.25
    all_knn_resumable(Xf, Xf, qids, cfg, checkpoint_dir=tmp_path / "ckf")
    assert load_checkpoint(tmp_path / "ckf", fingerprint(Xf, Xf, cfg))


def test_checkpoint_resume_continues_not_restarts(tmp_path):
    """Kill after round 1, resume: result identical, and the resumed run must
    start from the saved tile cursor."""
    X, cfg, qids = _resume_case(tmp_path)
    ck = tmp_path / "ck"

    rounds = []
    # run only the first chunk by raising out of the progress callback
    class Stop(Exception):
        pass

    def bail(done, total):
        rounds.append(done)
        raise Stop

    with pytest.raises(Stop):
        all_knn_resumable(
            X, X, qids, cfg, checkpoint_dir=ck, save_every=3, progress_cb=bail
        )
    state = load_checkpoint(ck, fingerprint(X, X, cfg))
    assert state is not None and state.tiles_done == 3

    resumed_rounds = []
    d, i = all_knn_resumable(
        X, X, qids, cfg, checkpoint_dir=ck, save_every=3,
        progress_cb=lambda done, total: resumed_rounds.append(done),
    )
    assert resumed_rounds[0] > 3  # continued, not restarted
    base = all_knn(X, config=cfg)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(base.ids))


def test_checkpoint_rejects_wrong_fingerprint(tmp_path):
    X, cfg, qids = _resume_case(tmp_path)
    ck = tmp_path / "ck"
    all_knn_resumable(X, X, qids, cfg, checkpoint_dir=ck, save_every=2)
    # different data -> stale checkpoint must be ignored
    Y = X + 1.0
    assert load_checkpoint(ck, fingerprint(Y, Y, cfg)) is None


# ------------------------------------------------------------------ CLI


def test_cli_synthetic_loo(tmp_path, capsys):
    rc = cli_main(
        [
            "--data", "synthetic:256x16c4", "--k", "5", "--num-classes", "4",
            "--backend", "serial", "--query-tile", "64", "--corpus-tile", "64",
            "--report", str(tmp_path / "rep.json"),
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "Matches:" in out and "Clock time" in out
    rep = json.loads((tmp_path / "rep.json").read_text())
    assert rep["accuracy"] > 0.9
    assert rep["backend"] == "serial"
    assert "knn" in rep["phase_seconds"]


def test_cli_mat_file_input(tmp_path, capsys, rng):
    X, y = make_blobs(100, 8, num_classes=3, seed=1)
    p = tmp_path / "corpus.mat"
    write_mat(p, {"train_X": X.astype(np.float64),
                  "train_labels": (y + 1)[:, None].astype(np.float64)})
    rc = cli_main(
        ["--data", str(p), "--k", "3", "--num-classes", "3",
         "--backend", "serial", "--query-tile", "32", "--corpus-tile", "32"]
    )
    assert rc == 0
    assert "Matches:" in capsys.readouterr().out


def test_cli_svd_path(capsys):
    rc = cli_main(
        ["--data", "synthetic:128x32c4", "--svd", "8", "--k", "3",
         "--num-classes", "4", "--backend", "serial",
         "--query-tile", "32", "--corpus-tile", "32"]
    )
    assert rc == 0


def test_cli_checkpoint_flag(tmp_path, capsys):
    rc = cli_main(
        ["--data", "synthetic:96x8c4", "--k", "3", "--num-classes", "4",
         "--backend", "serial", "--query-tile", "16", "--corpus-tile", "16",
         "--checkpoint-dir", str(tmp_path / "ck"), "--save-every", "2"]
    )
    assert rc == 0
    assert (tmp_path / "ck" / "knn_state.npz").exists()


def test_cli_save_every_zero_rejected(capsys):
    """--save-every 0 must be an argparse error, not silently replaced by
    the default cadence (ADVICE r1)."""
    import pytest

    with pytest.raises(SystemExit) as e:
        cli_main(
            ["--data", "synthetic:96x8c4", "--k", "3", "--num-classes", "4",
             "--backend", "serial", "--checkpoint-dir", "/tmp/never-used",
             "--save-every", "0"]
        )
    assert e.value.code == 2
    assert "--save-every" in capsys.readouterr().err


def test_cli_svd_with_queries_projects_both(tmp_path, capsys):
    """Regression: --svd must project the queries into the same subspace as
    the corpus, not leave them at full dimensionality."""
    X, y = make_blobs(128, 32, num_classes=4, seed=2)
    qp = tmp_path / "q.npy"
    np.save(qp, X[:7] + 0.01)
    rc = cli_main(
        ["--data", "synthetic:128x32c4", "--svd", "8", "--k", "3",
         "--num-classes", "4", "--backend", "serial", "--loo",
         "--queries", str(qp), "--query-tile", "32", "--corpus-tile", "32"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "predictions (7 queries):" in out


def test_cli_ring_backend(capsys):
    rc = cli_main(
        ["--data", "synthetic:64x8c4", "--k", "3", "--num-classes", "4",
         "--backend", "ring-overlap"]
    )
    assert rc == 0
    assert "backend=ring-overlap" in capsys.readouterr().out


def test_cli_recall_vs_serial(capsys):
    rc = cli_main(
        ["--data", "synthetic:96x8c4", "--k", "4", "--num-classes", "4",
         "--backend", "ring-overlap", "--recall-vs-serial"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "recall-vs-serial=1.0000" in out


def test_cli_recall_gate_sampled(tmp_path):
    """The sampled gate must agree with serial ground truth: the sampled
    queries keep their corpus identity (self-exclusion parity), so recall
    is exactly 1.0 for an exact distributed backend."""
    rep = tmp_path / "r.json"
    rc = cli_main(
        ["--data", "synthetic:300x8c4", "--k", "4", "--num-classes", "4",
         "--backend", "ring-overlap", "--recall-vs-serial",
         "--recall-sample", "32", "--report", str(rep), "-q"]
    )
    assert rc == 0
    body = json.loads(rep.read_text())
    assert body["recall_vs_baseline"] == 1.0
    assert body["notes"]["recall_sample"] == 32


def test_cli_sift_spec(capsys):
    rc = cli_main(
        ["--data", "sift:512", "--k", "3", "--backend", "serial",
         "--query-tile", "128", "--corpus-tile", "128", "-q"]
    )
    assert rc == 0


def test_multihost_init_single_host_noop():
    from mpi_knn_tpu.parallel.distributed import init_multihost

    info = init_multihost()
    assert info["num_processes"] == 1
    assert info["devices"] == 8  # the virtual CPU mesh


def test_sift_generator_chunked_deterministic():
    from mpi_knn_tpu.data.synthetic import make_sift_like

    a = make_sift_like(m=300, d=16, chunk=128)
    b = make_sift_like(m=300, d=16, chunk=128)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (300, 16) and a.min() >= 0 and a.max() <= 255


def test_cli_entrypoint_subprocess():
    """python -m mpi_knn_tpu works as a real process (CPU via --platform)."""
    r = subprocess.run(
        [sys.executable, "-m", "mpi_knn_tpu", "--data", "synthetic:64x8c4",
         "--k", "3", "--num-classes", "4", "--backend", "serial",
         "--platform", "cpu", "-q"],
        capture_output=True, text=True, cwd="/root/repo", timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]


def test_cli_svd_path(tmp_path):
    """--svd reduces the corpus on device before the kNN (the
    mnist_train_svd configuration); the report must carry the svd phase and
    a sane accuracy (exactly what scripts/r3_measure.sh's svd step
    extracts)."""
    rep = tmp_path / "svd.json"
    rc = cli_main(
        ["--data", "synthetic:200x32c4", "--k", "5", "--num-classes", "4",
         "--svd", "8", "--loo", "--platform", "cpu", "-q",
         "--report", str(rep)]
    )
    assert rc == 0
    body = json.loads(rep.read_text())
    assert "svd" in body["phase_seconds"] and "knn" in body["phase_seconds"]
    assert body["accuracy"] is not None and body["accuracy"] > 0.5
    assert body["shape"] == [200, 8]  # reduced dim reaches the kNN


def test_bench_driver_contract():
    """`python bench.py` is THE driver interface: stdout must be exactly one
    JSON line with metric/value/unit/vs_baseline, stderr must carry the
    context object, and the default knobs must be the measured-best config
    (twolevel schedule, exact top-k — BASELINE.md r3 A/B)."""
    env = dict(os.environ, BENCH_PLATFORM="cpu", BENCH_M="1500",
               BENCH_REPS="1", BENCH_WATCHDOG_S="0")
    r = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        cwd="/root/repo", timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l]
    assert len(lines) == 1, r.stdout
    head = json.loads(lines[0])
    assert set(head) == {"metric", "value", "unit", "vs_baseline"}
    assert head["unit"] == "s" and head["value"] > 0
    ctx = json.loads(
        [l for l in r.stderr.splitlines() if l.startswith("{")][-1]
    )
    assert ctx["merge_schedule"] == "twolevel"
    assert ctx["topk_method"] == "exact"
    assert ctx["recall_at_k_vs_oracle"] >= 0.999


def test_bench_all_failed_round_prints_no_measurement():
    """A round in which no series ran to the end exits non-zero and prints
    no measurement line: there is no re-run on another platform, so a
    number is from the device the series asked for or it does not exist.
    The primary run here is a 60k CPU all-kNN that cannot finish before
    the 3 s watchdog, standing in for a device that stopped answering."""
    env = dict(os.environ, BENCH_PLATFORM="cpu", BENCH_M="60000",
               BENCH_REPS="1", BENCH_WATCHDOG_S="3")
    r = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        cwd="/root/repo", timeout=120, env=env,
    )
    assert r.returncode == 2, (r.stdout, r.stderr[-2000:])
    assert r.stdout.strip() == "", r.stdout
    # the structured failed line is still written, for the operator
    failed = [json.loads(ln) for ln in r.stderr.splitlines()
              if ln.startswith("{") and '"failed"' in ln]
    assert len(failed) == 1 and failed[0]["value"] is None
    assert failed[0]["metric"] == "mnist60k_allknn_k10_seconds"
    assert "cpu" not in failed[0]["metric"]


def test_bench_platform_tpu_without_a_tpu_fails():
    """BENCH_PLATFORM=tpu on a host with no TPU: the series crashes at
    backend start-up, the round exits non-zero and prints no measurement
    line (this sandbox has libtpu but no chip — the case where an
    unforced process would carry on on the CPU)."""
    env = dict(os.environ, BENCH_PLATFORM="tpu", BENCH_M="256",
               BENCH_REPS="1")
    r = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        cwd="/root/repo", timeout=120, env=env,
    )
    assert r.returncode != 0, (r.stdout, r.stderr[-2000:])
    assert r.stdout.strip() == "", r.stdout


def test_bench_supervisor_never_imports_jax():
    """One process per chip: the supervisor spawns the series children, so
    it must never hold the device itself. Run a real (tiny) round with the
    supervisor in-process and look at what it imported."""
    code = (
        "import sys, bench; rc = bench.supervise(); "
        "assert 'jax' not in sys.modules, 'supervisor imported jax'; "
        "sys.exit(rc)"
    )
    env = dict(os.environ, BENCH_PLATFORM="cpu", BENCH_M="256",
               BENCH_REPS="1")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd="/root/repo", timeout=300, env=env,
    )
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    assert json.loads(r.stdout.strip().splitlines()[-1])["value"] > 0


def test_cli_platform_tpu_without_a_tpu_fails():
    """`--platform tpu` is a hard requirement: with no TPU the run exits
    non-zero instead of carrying on on the CPU."""
    r = subprocess.run(
        [sys.executable, "-m", "mpi_knn_tpu", "--data", "synthetic:64x8c4",
         "--k", "3", "--num-classes", "4", "--backend", "serial", "-q",
         "--platform", "tpu"],
        capture_output=True, text=True, cwd="/root/repo", timeout=120,
        env=os.environ,
    )
    assert r.returncode != 0
    assert "Unable to initialize backend 'tpu'" in r.stderr


def test_chip_smoke_needs_a_tpu_unless_allow_cpu():
    """chip_smoke.py fails on the CPU (device phase, no result line) and
    runs to `ok` there only under the explicit --allow-cpu debugging flag,
    which stamps platform=cpu on every line."""
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny"], capture_output=True,
        text=True, cwd="/root/repo", timeout=120, env=os.environ,
    )
    assert r.returncode != 0
    assert "device: FAILED" in r.stdout
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())

    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny", "--allow-cpu"],
        capture_output=True, text=True, cwd="/root/repo", timeout=600,
        env=os.environ,
    )
    assert r.returncode == 0, (r.stdout, r.stderr[-3000:])
    lines = r.stdout.strip().splitlines()
    assert all("platform=cpu" in ln for ln in lines[:-1]), r.stdout
    # the last line holds exactly the keys the driver reads
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["count"], int)
    head, _, body = lines[-2].partition(" {")
    assert head == "summary: platform=cpu"
    doc = json.loads("{" + body)
    for phase in ("device", "allknn", "serve"):
        assert doc["phases"][phase]["ok"] is True, doc
    # conftest forces 8 virtual devices into XLA_FLAGS, so the ring ran
    assert doc["ring_devices"] == 4
    assert doc["phases"]["ring"]["ok"] and doc["phases"]["ring-overlap"]["ok"]


def test_compile_cache_dir_is_placed_from_outside_or_in_the_checkout():
    """use_compile_cache leaves jax alone when JAX_COMPILATION_CACHE_DIR is
    set (jax reads it itself) and otherwise points inside the checkout —
    never at a temp dir, a pid or a timestamp (the path is part of every
    cache key, so a directory that moves never hits)."""
    code = (
        "import jax; from mpi_knn_tpu.utils.platform import "
        "use_compile_cache; print(use_compile_cache()); "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR="/some/outside/dir")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd="/root/repo", timeout=120, env=env)
    assert r.stdout.split() == ["/some/outside/dir"] * 2, r.stderr[-2000:]
    env.pop("JAX_COMPILATION_CACHE_DIR")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd="/root/repo", timeout=120, env=env)
    assert r.stdout.split() == ["/root/repo/.jax_cache"] * 2, r.stderr[-2000:]


def test_bench_failed_line_shape_is_not_a_measurement():
    """ISSUE 7 regression (BENCH_r05): a watchdog kill must NEVER bank as
    a measurement. BENCH_r05 stamped `value: 480.0, vs_baseline: 0.0` on
    a timeout — a kill posing as a zero-regression data point. Failed
    lines carry `value: null`, the kill time in an explicit
    `time_until_kill_s` field, and no `vs_baseline` key at all (the
    subprocess-level version of this pin lives in test_resilience.py)."""
    import bench

    doc = bench._failed_line(
        "mnist60k_allknn_k10_seconds", "wedged", "timeout",
        time_until_kill_s=12.3,
        flight={"records": 4, "spans_complete": 1, "events": 2,
                "open_spans": [{"name": "warm", "cat": "bench",
                                "attrs": {}}], "last": []},
    )
    assert doc["value"] is None
    assert "vs_baseline" not in doc
    assert doc["time_until_kill_s"] == 12.3
    assert doc["failed"] is True and doc["status"] == "timeout"
    assert doc["series"] == "wedged"
    assert doc["flight"]["open_spans"][0]["name"] == "warm"
    # a line that never ran (preflight refusal) has no flight record and
    # 0 s until the kill — still value: null, still no vs_baseline
    pre = bench._failed_line("m", "s0", "preflight", time_until_kill_s=0.0)
    assert pre["value"] is None and "vs_baseline" not in pre
    assert "flight" not in pre


def test_ring_ab_script():
    """scripts/ring_ab.py runs the full 2×2 A/B matrix (uni/bidir ×
    blocking/overlap) and reports per-cell timings + four-way agreement."""
    r = subprocess.run(
        [sys.executable, "scripts/ring_ab.py", "--m", "256", "--d", "16",
         "--k", "3", "--platform", "cpu", "--reps", "1"],
        capture_output=True, text=True, cwd="/root/repo", timeout=300,
        env=os.environ,  # conftest already appended the 8-device XLA flag
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["results_agree"] == 1.0
    cells = {f"{s}-{v}" for s in ("uni", "bidir")
             for v in ("blocking", "overlap")}
    assert set(out["cells_s"]) == cells
    assert all(t > 0 for t in out["cells_s"].values())
    assert out["speedup_overlap_uni"] > 0
    assert out["speedup_bidir_overlap"] > 0


def test_save_neighbors_and_corrupt_checkpoint(tmp_path):
    """--save-neighbors writes NPZ; a corrupt checkpoint file degrades to a
    clean restart instead of crashing the resumable run."""
    out = tmp_path / "nn.npz"
    rc = cli_main(
        ["--data", "synthetic:96x8c4", "--k", "3", "--num-classes", "4",
         "--backend", "serial", "--platform", "cpu", "-q",
         "--save-neighbors", str(out)]
    )
    assert rc == 0
    z = np.load(out)
    assert z["ids"].shape == (96, 3) and z["predictions"].shape == (96,)

    # corrupt checkpoint -> load returns None (restart), no exception
    from mpi_knn_tpu.utils.checkpoint import load_checkpoint

    ck = tmp_path / "ck"
    ck.mkdir()
    (ck / "knn_state.npz").write_bytes(b"not a zip at all")
    assert load_checkpoint(ck, "whatever") is None


def test_cli_profile_writes_trace(tmp_path):
    """--profile writes a jax.profiler trace directory (SURVEY.md §6
    tracing row — the XProf-compatible replacement for gettimeofday)."""
    prof = tmp_path / "trace"
    rc = cli_main(
        ["--data", "synthetic:64x8c4", "--k", "3", "--num-classes", "4",
         "--backend", "serial", "--platform", "cpu", "-q",
         "--profile", str(prof)]
    )
    assert rc == 0
    # the profiler lays out plugins/profile/<run>/; existence of any file
    # under the dir is the contract
    assert any(p.is_file() for p in prof.rglob("*")), "no trace files written"

    # the wire-format trace parser must read what jax.profiler wrote:
    # at least one plane with busy categories, and a clean per-file error
    # (not an abort) on a truncated trace
    files = trace_ops.find_xplanes(str(prof))
    assert files, "no .xplane.pb written"
    report = trace_ops.analyze(trace_ops.parse_xplane(files[0]))
    assert report, "parser produced no planes"
    plane = next(iter(report.values()))
    assert plane["busy_ms_by_category"], plane
    bad = tmp_path / "bad.xplane.pb"
    bad.write_bytes(b"\xff\xff\xff")
    with pytest.raises((ValueError, IndexError)):
        trace_ops.parse_xplane(str(bad))


def test_trace_ops_parses_real_ring_trace(tmp_path):
    """End-to-end on REAL trace bytes (VERDICT r4 weak #4): capture an
    actual ring-overlap run under ``jax.profiler.trace`` on the 8-device
    CPU mesh and push it through the whole trace pipeline — wire-format
    parse, ppermute→collective categorization, overlap metric. On CPU the
    events land on the ``/host:CPU`` plane and the overlap numbers mean
    nothing (memcpy collectives; only a TPU plane tells the device
    story) — what this pins is that the pipeline consumes
    real profiler output, so the first chip-side capture only changes the
    plane name and the async start/done pairing, not the parsing."""
    import jax

    rng = np.random.default_rng(0)
    X = rng.standard_normal((256, 32)).astype(np.float32)
    cfg = dict(k=3, backend="ring-overlap", query_tile=32, corpus_tile=32)
    all_knn(X, **cfg).dists.block_until_ready()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        all_knn(X, **cfg).dists.block_until_ready()

    files = trace_ops.find_xplanes(str(tmp_path))
    assert files, "profiler wrote no .xplane.pb"
    events = trace_ops.parse_xplane(files[0])
    # the wire-format claim is unconditional: real bytes parsed to real
    # events. The EVENT-NAMING claim is environmental — some jaxlibs label
    # host-plane collective events by HLO op name (collective-permute.N) or
    # omit them from the host plane entirely, instead of the jaxpr-level
    # 'ppermute' label this pipeline categorizes by. Skip precisely on that
    # naming gap; a capture with no events at all is still a hard failure.
    assert events, "real capture parsed to zero events"
    if not any(e["name"].startswith("ppermute") for e in events):
        pytest.skip(
            "environmental: this jaxlib's profiler does not emit "
            "'ppermute*'-named events on the CPU host plane "
            f"({len(events)} events parsed fine, so the xplane wire-format "
            "path is exercised; only the collective event-naming "
            "convention differs from the one trace_ops categorizes)"
        )
    report = trace_ops.analyze(events)
    # pick the plane that carries the collectives explicitly — a future
    # jax may emit extra planes (python tracer etc.) in arbitrary order
    plane = max(report.values(), key=lambda p: p["collective_total_ms"])
    assert plane["collective_total_ms"] > 0, plane
    assert "matmul" in plane["busy_ms_by_category"], plane


def test_trace_ops_async_collective_span_overlap():
    """TPU async collectives trace as '-start'/'-done' pairs whose in-flight
    DMA time belongs to neither event; the span metric (start of start-op to
    end of done-op, paired by name stem and occurrence order) must credit a
    matmul that runs inside that gap as hidden transfer, while the plain
    busy-interval overlap reads ~0."""
    ms = 1_000_000_000  # ps per ms
    events = [
        # round 1: transfer in flight 0..10ms (start op busy 0-1, done 9-10)
        dict(plane="/device:TPU:0", line="XLA Ops",
             name="collective-permute-start.1", start_ps=0, dur_ps=1 * ms),
        dict(plane="/device:TPU:0", line="XLA Ops",
             name="collective-permute-done.1", start_ps=9 * ms, dur_ps=1 * ms),
        # the distance matmul runs 2..8ms — fully inside the DMA gap
        dict(plane="/device:TPU:0", line="XLA Ops",
             name="fusion.42", start_ps=2 * ms, dur_ps=6 * ms),
        # round 2 of the same instruction: 20..24ms span, matmul elsewhere
        dict(plane="/device:TPU:0", line="XLA Ops",
             name="collective-permute-start.1", start_ps=20 * ms, dur_ps=1 * ms),
        dict(plane="/device:TPU:0", line="XLA Ops",
             name="collective-permute-done.1", start_ps=23 * ms, dur_ps=1 * ms),
    ]
    rep = trace_ops.analyze(events)["/device:TPU:0"]
    # busy-interval overlap: start/done events never intersect the matmul
    assert rep["collective_overlapped_with_matmul_ms"] == 0.0, rep
    # spans: 0..10 and 20..24 -> 14 ms total, 6 ms under the matmul
    assert rep["collective_span_ms"] == 14.0, rep
    assert rep["collective_span_overlapped_with_matmul_ms"] == 6.0, rep
    # sanity: categories aggregated as expected
    assert rep["busy_ms_by_category"]["matmul"] == 6.0, rep
