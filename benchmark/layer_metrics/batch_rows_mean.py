"""Mean coalesced rows per dispatched batch over the window:
``frontend_batch_fill_rows`` sum over count, as the difference of the two
``/metrics`` reads around the window. Source: program counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    count = delta.get("frontend_batch_fill_rows_count", 0.0)
    if count <= 0:
        return None
    return delta.get("frontend_batch_fill_rows_sum", 0.0) / count
