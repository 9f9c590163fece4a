"""Time the serving process's interpreter spent in garbage collections of
generation 1 and 2 during the window, in the cell whose end-to-end metrics
are latencies: 1e3 x ``python_gc_seconds_total`` (both generations), as the
difference of the two ``/metrics`` reads around the window. The program
times each such collection start to stop in a ``gc.callbacks`` hook
(``mpi_knn_tpu/obs/host.py``); the collector holds the interpreter lock, so
every thread of the server — the pump among them — waits that long.
Generation 0 is not timed. Source: program counter."""

SAMPLE = 'python_gc_seconds_total{generation="%d"}'


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta or SAMPLE % 2 not in delta:
        return None
    return 1e3 * (delta.get(SAMPLE % 1, 0.0) + delta[SAMPLE % 2])
