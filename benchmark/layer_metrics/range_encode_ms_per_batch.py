"""The handler's ``encode`` phase for one answer in range format: the
seconds of ``frontend_request_phase_seconds_total{phase="encode",
route="query"}`` over ``frontend_request_seconds_count``, as the difference
of the two ``/metrics`` reads around the window. In the cell every request
is one full 1024-row batch, so this is the encode of a batch's answer:
``lims`` of rows + 1 offsets and two flat lists through ``tolist`` and
``json.dumps``, a cost that follows the answer's length (the k-NN answer's
follows rows x k). None where the program counts no range rows (the parent
commit: there is no such answer). Source: program span."""

ENCODE = 'frontend_request_phase_seconds_total{phase="encode",route="query"}'


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    count = delta.get("frontend_request_seconds_count", 0.0)
    if (count <= 0 or ENCODE not in delta
            or delta.get("knn_range_rows_total", 0.0) <= 0):
        return None
    return 1e3 * delta[ENCODE] / count
