"""Least time the chip could take for the traced span's range batches
(``benchmark/opcount_range.py``: 2*Q*C*d operations over the v5e's 8-bit
peak, 393e12 a second, or the corpus's bytes at rest ONCE a batch plus the
queries and the answers over the HBM peak, whichever is larger) over the
device seconds under the program's ``knn.scan_range`` AND
``knn.range_overflow`` scopes together in that span: the share of its
roofline that finding every row within the radius reaches. The second
path's walk is in the time and not in the work, so the share cannot pass
100 %. Rows, batches and results are the movements of
``serve_queries_total``, ``serve_batches_total`` and
``knn_range_results_total`` between the trace's start and stop. None where
the program has no such scopes (the parent commit). Source: device trace."""

from benchmark import opcount_range


def read(run: dict):
    ranged, peaks = run.get("range"), run.get("peaks")
    delta, about = run.get("traced_metrics_delta"), run.get("about")
    if not ranged or not peaks or not delta or not about:
        return None
    seconds = (ranged.get("scan_s") or 0.0) + (ranged.get("overflow_s")
                                               or 0.0)
    rows = delta.get("serve_queries_total", 0.0)
    batches = delta.get("serve_batches_total", 0.0)
    if seconds <= 0 or rows <= 0 or batches <= 0:
        return None
    least, _ = opcount_range.least_seconds(
        rows, batches, delta.get("knn_range_results_total", 0.0),
        about["rows"], about["dim"], peaks, run["device"]["kind"])
    return 100.0 * least / seconds
