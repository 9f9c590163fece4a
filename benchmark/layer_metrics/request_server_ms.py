"""Mean time of a ``/query`` request of the window inside the server, from
the read of its body to its response written: ``frontend_request_seconds``
sum over count (the duration of the handler's ``knn:http.request`` span), as
the difference of the two ``/metrics`` reads around the window. What the
generator's latency holds beyond it is the network and the generator.
Source: program span."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    count = delta.get("frontend_request_seconds_count", 0.0)
    if count <= 0:
        return None
    return 1e3 * delta.get("frontend_request_seconds_sum", 0.0) / count
