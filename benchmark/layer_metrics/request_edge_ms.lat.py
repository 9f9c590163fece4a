"""A ``/query`` request's mean server time outside its queue and its batch,
in the cell whose end-to-end metrics are latencies: the phases ``read``
(socket read and decode), ``admit``, ``wake`` (ticket fulfilled to the
handler running again), ``encode`` (the answer's body) and ``write`` of
``frontend_request_phase_seconds_total{phase,route="query"}`` over
``frontend_request_seconds_count``, as the difference of the two
``/metrics`` reads around the window. Each phase is the duration of its
``knn:http.<phase>`` span; with ``await`` they partition ``knn:http.request``
from consecutive reads of one clock. Source: program counter."""

SAMPLE = 'frontend_request_phase_seconds_total{phase="%s",route="query"}'
EDGE = ("read", "admit", "wake", "encode", "write")


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    names = [SAMPLE % p for p in EDGE]
    count = delta.get("frontend_request_seconds_count", 0.0)
    if count <= 0 or not any(n in delta for n in names):
        return None
    return 1e3 * sum(delta.get(n, 0.0) for n in names) / count
