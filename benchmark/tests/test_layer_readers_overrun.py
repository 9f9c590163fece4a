"""The per-layer readers of the overrun record and the host's own evidence
(``overrun_ms.lat`` / ``.tput``, ``gc_pause_ms.lat`` / ``.tput`` on a
``window_metrics_delta`` made the way a run makes it; ``call_overrun_ms``
on the registry of this process): 0.0 on a
clean window, the sum over ``where``, and ``None`` — never an exception — on
the parent commit's ``/metrics``, which has none of the samples."""

import pytest

from benchmark import loadgen
from benchmark.harness import load_by_path

WINDOW = ("overrun_ms.lat", "overrun_ms.tput", "gc_pause_ms.lat",
          "gc_pause_ms.tput")
NAMES = WINDOW + ("call_overrun_ms",)


def read(name, delta):
    return load_by_path("layer_metrics", name).read(
        {"window_metrics_delta": delta})


def exposition(overruns=()):
    """The program's own exposition after some retires: the pump's CPU
    counter, the collector's (settled by the snapshot), and one sample a
    ``where`` that overran."""
    from mpi_knn_tpu.obs import host
    from mpi_knn_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("serve_batches_total").inc(270)
    reg.counter("serve_pump_cpu_seconds_total").inc(3.2)
    host._GCWatch().settle(reg)  # a hook that saw no collection: zeros
    report = host.OverrunReport("serve_batch", "serve")
    for seq, (where, seconds) in enumerate(overruns):
        report(reg, where, seconds, seq=seq)
    return loadgen.parse_metrics(reg.to_prometheus())


def test_a_clean_window_reads_zero_not_none():
    delta = loadgen.metrics_delta(exposition(), exposition())
    for name in WINDOW:
        assert read(name, delta) == 0.0


@pytest.mark.parametrize("name", ["overrun_ms.lat", "overrun_ms.tput"])
def test_overrun_ms_sums_over_where(name):
    before = exposition([("wait-host", 0.25)])
    after = exposition([("wait-host", 0.25), ("wait-host", 2.6),
                        ("wait-device", 6.7), ("reply", 0.075)])
    assert read(name, loadgen.metrics_delta(before, after)) == pytest.approx(
        1e3 * (2.6 + 6.7 + 0.075))
    # the counts are another family and no part of the seconds
    assert read(name, loadgen.metrics_delta({}, after)) == pytest.approx(
        1e3 * (0.25 + 2.6 + 6.7 + 0.075))


@pytest.mark.parametrize("name", ["gc_pause_ms.lat", "gc_pause_ms.tput"])
def test_gc_pause_ms_adds_generations_one_and_two(name):
    delta = {'python_gc_seconds_total{generation="1"}': 0.004,
             'python_gc_seconds_total{generation="2"}': 0.310,
             'python_gc_collections_total{generation="2"}': 3.0}
    assert read(name, delta) == pytest.approx(314.0)


@pytest.mark.parametrize("name", WINDOW)
def test_a_program_without_the_samples_reads_none_and_does_not_raise(name):
    """The parent commit's ``/metrics``: batches and pump phases, none of
    the new samples. Also a cell with no window record at all."""
    parent = {"serve_batches_total": 270.0,
              "serve_batch_latency_seconds_count": 270.0,
              'serve_batch_phase_seconds_total{phase="wait"}': 40.0}
    assert read(name, parent) is None
    assert read(name, None) is None
    assert load_by_path("layer_metrics", name).read({}) is None


def test_call_overrun_ms_reads_this_process(monkeypatch):
    """None before the program timed a call (the parent never does), 0.0
    once it has and none overran, then the sum over ``where``."""
    from mpi_knn_tpu.obs import host
    from mpi_knn_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(obs_metrics, "get_registry", lambda: reg)
    reader = load_by_path("layer_metrics", "call_overrun_ms")
    reg.counter("knn_corpus_prepare_total", labels={"result": "hit"}).inc()
    assert reader.read({}) is None
    reg.histogram("knn_call_host_seconds").observe(0.004)
    assert reader.read({}) == 0.0
    report = host.OverrunReport("knn_call", "api")
    report(reg, "outside", 12.9, seq=5)
    report(reg, "dispatch", 0.1, seq=9)
    assert reader.read({"window_metrics_delta": None}) == pytest.approx(
        13000.0)


@pytest.mark.parametrize("name", NAMES)
def test_benchmark_json_lists_the_reader_in_cells_that_report_what_it_moves(
        name):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == metric["moves"]]
    assert metric["source"] == "program_counter"
    assert (metric["unit"], metric["better"]) == ("ms", "lower")
    assert set(metric["workloads"]) <= set(moved["workloads"])
    if name == "call_overrun_ms":
        assert metric["layer"] == "one-shot API"
        assert metric["workloads"] == ["allknn-mnist8m", "ring4-mnist8m"]
        return
    assert metric["layer"] == "serving engine"
    (empty,) = [m for m in bench["per_layer"]
                if m["name"] == "server_empty_pct"]
    assert metric["workloads"] == (
        ["serve-bigann10m-small"] if name.endswith(".lat")
        else empty["workloads"])
