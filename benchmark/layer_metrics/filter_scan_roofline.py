"""Least time the chip could take for the masked scan's rows of the traced
span (``benchmark/opcount_filter.py scan_least_seconds``: the rows of
``filter_rows_total{regime="scan"|"none"}`` against the whole corpus in
one bf16 pass, the corpus read once a scan dispatch of
``filter_dispatches_total{regime="scan"}``) over the device's busy time in
that span less the gather regime's own (scope ``knn.filter_gather``): the
same work whatever implements it. Source: device trace and program
counter."""

from benchmark import opcount_filter

GATHER_SCOPE = "knn.filter_gather"


def read(run: dict):
    trace, peaks, scopes = run.get("trace"), run.get("peaks"), run.get("scopes")
    delta, about = run.get("traced_metrics_delta"), run.get("filter")
    if not trace or not peaks or not delta or not about or not scopes:
        return None
    rows = sum(delta.get('filter_rows_total{regime="%s"}' % r, 0.0)
               for r in ("scan", "none"))
    dispatches = delta.get('filter_dispatches_total{regime="scan"}', 0.0)
    busy = trace["busy_s"] - scopes.get(GATHER_SCOPE, 0.0)
    if rows <= 0 or dispatches <= 0 or busy <= 0:
        return None
    return 100.0 * opcount_filter.scan_least_seconds(
        rows, dispatches, about["rows"], about["dim"], 10, peaks) / busy
