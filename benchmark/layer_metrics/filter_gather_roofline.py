"""Least time the chip could take to read the candidate slots the gather
regime's programs were given in the traced span (``benchmark/
opcount_filter.py gather_least_seconds``: ``filter_gather_slots_total``,
padding included, x (4 d + 8) B over the HBM peak) over those programs' own
device time: everything under the scope ``knn.filter_gather``
(``run["scopes"]``). Source: device trace and program counter."""

from benchmark import opcount_filter

SCOPE = "knn.filter_gather"


def read(run: dict):
    scopes, peaks = run.get("scopes"), run.get("peaks")
    delta, about = run.get("traced_metrics_delta"), run.get("filter")
    if not scopes or not peaks or not delta or not about:
        return None
    busy = scopes.get(SCOPE, 0.0)
    slots = delta.get("filter_gather_slots_total", 0.0)
    if busy <= 0 or slots <= 0:
        return None
    return 100.0 * opcount_filter.gather_least_seconds(
        slots, about["dim"], peaks) / busy
