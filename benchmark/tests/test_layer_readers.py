"""The per-layer readers of the serving path's spans and counters
(``queue_wait_ms``, ``request_server_ms``, ``bucket_fill_pct``,
``batch_host_ms``, ``batch_d2h_ms``, ``window_compiles``) on hand-made
``window_metrics_delta`` records: the value, ``None`` where there is nothing
to read (no window, a zero count, a program without the sample), and the
sample names exactly as the program's exposition prints them."""

import pytest

from benchmark import loadgen
from benchmark.harness import load_by_path

PHASE = 'serve_batch_phase_seconds_total{phase="%s"}'


def read(name, delta):
    return load_by_path("layer_metrics", name).read(
        {"window_metrics_delta": delta})


def exposition_delta():
    """A window's delta made the way a run makes it: the program's registry
    printed by ``to_prometheus``, parsed by the generator's reader."""
    from mpi_knn_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for phase, seconds in (("idle", 3.0), ("coalesce", 0.01), ("prep", 0.02),
                           ("enqueue", 0.03), ("wait", 0.9), ("d2h", 0.1),
                           ("reply", 0.04)):
        reg.counter("serve_batch_phase_seconds_total",
                    labels={"phase": phase}).inc(seconds)
    reg.counter("serve_batches_total").inc(10)
    reg.counter("serve_queries_total").inc(290)
    reg.counter("serve_padded_rows_total").inc(640)
    reg.counter("jax_compiles_total").inc(0)
    for v in (0.03, 0.05):
        reg.histogram("frontend_queue_wait_seconds").observe(v)
        reg.histogram("frontend_request_seconds").observe(4 * v)
    return loadgen.metrics_delta(
        {}, loadgen.parse_metrics(reg.to_prometheus()))


@pytest.mark.parametrize("name, value", [
    ("queue_wait_ms", 40.0),
    ("request_server_ms", 160.0),
    ("bucket_fill_pct", 100.0 * 290 / 640),
    ("batch_host_ms", 1e3 * (0.01 + 0.02 + 0.03 + 0.1 + 0.04) / 10),
    ("batch_d2h_ms", 10.0),
    ("window_compiles", 0.0),
])
def test_reader_on_the_programs_own_exposition(name, value):
    assert read(name, exposition_delta()) == pytest.approx(value)


@pytest.mark.parametrize("name, delta", [
    ("queue_wait_ms", {"frontend_queue_wait_seconds_count": 0.0,
                       "frontend_queue_wait_seconds_sum": 0.0}),
    ("request_server_ms", {"frontend_request_seconds_count": 0.0,
                           "frontend_request_seconds_sum": 0.0}),
    ("bucket_fill_pct", {"serve_queries_total": 0.0,
                         "serve_padded_rows_total": 0.0}),
    ("batch_host_ms", {PHASE % "prep": 0.0, "serve_batches_total": 0.0}),
    ("batch_d2h_ms", {PHASE % "d2h": 0.0, "serve_batches_total": 0.0}),
])
def test_zero_count_reads_none(name, delta):
    assert read(name, delta) is None


@pytest.mark.parametrize("name", [
    "queue_wait_ms", "request_server_ms", "bucket_fill_pct",
    "batch_host_ms", "batch_d2h_ms", "window_compiles"])
def test_a_program_without_the_samples_reads_none_and_does_not_raise(name):
    """The parent commit's ``/metrics``: batches and rows, none of the new
    samples. Also a cell with no window record at all (all-kNN)."""
    parent = {"serve_batches_total": 54.0, "serve_queries_total": 1600.0,
              "serve_batch_latency_seconds_count": 54.0}
    assert read(name, parent) is None
    assert read(name, None) is None
    assert load_by_path("layer_metrics", name).read({}) is None


def test_window_compiles_counts_compiles_and_cache_loads():
    assert read("window_compiles", {"jax_compiles_total": 2.0}) == 2.0
    assert read("window_compiles", {"jax_compiles_total": 1.0,
                                    "jax_cache_loads_total": 1.0}) == 2.0


def test_cycle_stall_pct_is_the_share_of_the_window_in_long_cycles():
    """The streaming cell's walker's own clock: cycles longer than 1.5
    medians, over the window; nothing to read without three cycles."""
    stall = load_by_path("layer_metrics", "cycle_stall_pct").read
    typical = load_by_path("layer_metrics", "cycle_median_ms").read

    def run(*seconds):
        return {"stream": {"cycles": [{"s": s} for s in seconds]}}

    even = [0.4] * 9
    assert stall(run(*even)) == 0.0
    assert stall(run(*even, 0.6)) == 0.0  # not over 1.5 medians
    assert stall(run(*even, 1.4)) == pytest.approx(100 * 1.4 / 5.0)
    assert typical(run(*even, 1.4)) == pytest.approx(400)
    # a shift of the typical cycle is no stall
    assert stall(run(*[0.5] * 10)) == 0.0
    assert typical(run(*[0.5] * 10)) == pytest.approx(500)
    for nothing in ({}, {"stream": None}, {"stream": {"window_s": 5.0}},
                    run(0.4, 9.0)):
        assert stall(nothing) is None and typical(nothing) is None
