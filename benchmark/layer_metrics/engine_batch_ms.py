"""Mean dispatch-to-sync time of a batch in the serving engine over the
window: ``serve_batch_latency_seconds`` sum over count, as the difference of
the two ``/metrics`` reads around the window. Source: program span."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    count = delta.get("serve_batch_latency_seconds_count", 0.0)
    if count <= 0:
        return None
    return 1e3 * delta.get("serve_batch_latency_seconds_sum", 0.0) / count
