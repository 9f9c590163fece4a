"""The per-layer readers of the request's life, the dispatch lag and the
server's occupancy (``server_empty_pct``, ``dispatch_lag_ms.lat`` /
``.tput``, ``request_edge_ms.lat`` / ``.tput``) on a ``window_metrics_delta``
made the way a run makes it: the value, the sample names exactly as the
program's exposition prints them, and ``None`` where there is nothing to
read — a zero count, no window, or the parent commit's ``/metrics``, which
has none of the samples."""

import pytest

from benchmark import loadgen
from benchmark.harness import load_by_path

NAMES = ("server_empty_pct", "dispatch_lag_ms.lat", "dispatch_lag_ms.tput",
         "request_edge_ms.lat", "request_edge_ms.tput")
PHASE_S = {"read": 0.004, "admit": 0.001, "await": 0.5, "wake": 0.002,
           "encode": 0.03, "write": 0.003}


def read(name, delta):
    return load_by_path("layer_metrics", name).read(
        {"window_metrics_delta": delta})


def exposition_delta():
    """Four /query requests in two batches and one /upsert over a 51 s
    window, printed by the program's registry and parsed by the
    generator's reader."""
    from mpi_knn_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for state, seconds in (("empty", 5.1), ("occupied", 45.9)):
        reg.counter("frontend_occupancy_seconds_total",
                    labels={"state": state}).inc(seconds)
    for lag in (0.002, 0.006):
        reg.histogram("frontend_dispatch_lag_seconds").observe(lag)
    for phase, seconds in PHASE_S.items():
        reg.counter("frontend_request_phase_seconds_total",
                    labels={"phase": phase, "route": "query"}).inc(seconds)
    # a write's phases carry their own route and are no part of the edge
    reg.counter("frontend_request_phase_seconds_total",
                labels={"phase": "read", "route": "upsert"}).inc(9.0)
    for _ in range(4):
        reg.histogram("frontend_request_seconds").observe(
            sum(PHASE_S.values()) / 4)
    return loadgen.metrics_delta(
        {}, loadgen.parse_metrics(reg.to_prometheus()))


EDGE_MS = 1e3 * (0.004 + 0.001 + 0.002 + 0.03 + 0.003) / 4


@pytest.mark.parametrize("name, value", [
    ("server_empty_pct", 10.0),
    ("dispatch_lag_ms.lat", 4.0),
    ("dispatch_lag_ms.tput", 4.0),
    ("request_edge_ms.lat", EDGE_MS),
    ("request_edge_ms.tput", EDGE_MS),
])
def test_reader_on_the_programs_own_exposition(name, value):
    assert read(name, exposition_delta()) == pytest.approx(value)


def test_edge_and_await_partition_the_request():
    """The five edge phases and ``await`` are the request's span: the edge
    is what ``request_server_ms`` holds beyond queue and batch."""
    delta = exposition_delta()
    await_ms = 1e3 * PHASE_S["await"] / 4
    assert read("request_edge_ms.lat", delta) + await_ms == pytest.approx(
        read("request_server_ms", delta))


@pytest.mark.parametrize("name, delta", [
    ("server_empty_pct",
     {'frontend_occupancy_seconds_total{state="empty"}': 0.0,
      'frontend_occupancy_seconds_total{state="occupied"}': 0.0}),
    ("dispatch_lag_ms.lat", {"frontend_dispatch_lag_seconds_count": 0.0,
                             "frontend_dispatch_lag_seconds_sum": 0.0}),
    ("dispatch_lag_ms.tput", {"frontend_dispatch_lag_seconds_count": 0.0,
                              "frontend_dispatch_lag_seconds_sum": 0.0}),
    ("request_edge_ms.lat",
     {'frontend_request_phase_seconds_total{phase="read",route="query"}':
      0.0, "frontend_request_seconds_count": 0.0}),
    ("request_edge_ms.tput",
     {'frontend_request_phase_seconds_total{phase="read",route="query"}':
      0.0, "frontend_request_seconds_count": 0.0}),
])
def test_zero_count_reads_none(name, delta):
    assert read(name, delta) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_samples_reads_none_and_does_not_raise(name):
    """The parent commit's ``/metrics``: requests, batches and pump phases,
    none of the new samples. Also a cell with no window record at all."""
    parent = {"serve_batches_total": 54.0,
              "frontend_request_seconds_count": 54.0,
              "frontend_request_seconds_sum": 1.8,
              "frontend_queue_wait_seconds_count": 54.0,
              'serve_batch_phase_seconds_total{phase="idle"}': 30.0}
    assert read(name, parent) is None
    assert read(name, None) is None
    assert load_by_path("layer_metrics", name).read({}) is None


def test_a_server_that_was_never_empty_reads_zero():
    delta = {'frontend_occupancy_seconds_total{state="occupied"}': 51.0}
    assert read("server_empty_pct", delta) == 0.0


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
    assert metric["layer"] == "front end"
    assert set(metric["workloads"]) <= set(moved["workloads"])
    serving = "serve-" if name.endswith(".lat") else ("serve-", "stream-")
    assert all(w.startswith(serving) for w in metric["workloads"])
