"""Share of the rows the device computed for the window's batches that were
rows of a request: 100 x ``serve_queries_total`` over
``serve_padded_rows_total`` (the padded height of every retired batch), as
the difference of the two ``/metrics`` reads around the window. Source:
program counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    padded = delta.get("serve_padded_rows_total", 0.0)
    if padded <= 0:
        return None
    return 100.0 * delta.get("serve_queries_total", 0.0) / padded
