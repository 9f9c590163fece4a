"""Mean time a batch of the window had been dispatchable when the pump
handed it to the engine, in the cell whose end-to-end metrics are latencies:
``frontend_dispatch_lag_seconds`` sum over count, as the difference of the
two ``/metrics`` reads around the window. The coalescer stamps a batch ripe
at the arrival of the request that filled it, or at its oldest request's
deadline; ``Frontend._dispatch`` observes its own clock less that, once a
batch (``lag_ms`` on the ``knn:pump.coalesce`` span). What of
``queue_wait_ms`` is not the policy's hold is this. Source: program counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    count = delta.get("frontend_dispatch_lag_seconds_count", 0.0)
    if count <= 0:
        return None
    return 1e3 * delta.get("frontend_dispatch_lag_seconds_sum", 0.0) / count
