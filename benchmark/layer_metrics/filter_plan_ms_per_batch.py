"""Host time the predicates' plans take, a batch of the window:
``filter_plan_seconds_total`` (the summed duration of the ``knn:filter.plan``
spans: look-ups, intersections, the split by regime, padding — a request's
plan is made at its admission on the HTTP handler's thread, beside other
requests', and the pump only joins the plans of a batch) over
``serve_batches_total``, as the difference of the two ``/metrics`` reads
around the window. Source: program span."""

PLAN = "filter_plan_seconds_total"


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    batches = delta.get("serve_batches_total", 0.0)
    if PLAN not in delta or batches <= 0:
        return None
    return 1e3 * delta[PLAN] / batches
