"""Results answered a query row in the window: the movement of the
program's ``knn_range_results_total`` over that of
``knn_range_rows_total`` between the two ``/metrics`` reads around the
window. It guards the traffic — the mix's law gives every batch the same
strata, so this reads the same on every seed to within the law's own
spread — and a change that drops results moves it. None where the program
has no such counters (the parent commit). Source: program counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    rows = delta.get("knn_range_rows_total", 0.0)
    if rows <= 0 or "knn_range_results_total" not in delta:
        return None
    return delta["knn_range_results_total"] / rows
