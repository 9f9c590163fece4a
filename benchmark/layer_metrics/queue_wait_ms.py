"""Mean time a request of the window sat in the front end before its batch
was handed to the engine: ``frontend_queue_wait_seconds`` sum over count,
as the difference of the two ``/metrics`` reads around the window. The
program observes it per request, from admission (``Frontend.submit``) to the
hand-over in the pump, both on the pump's clock. Source: program span."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    count = delta.get("frontend_queue_wait_seconds_count", 0.0)
    if count <= 0:
        return None
    return 1e3 * delta.get("frontend_queue_wait_seconds_sum", 0.0) / count
