"""Median wall time of an ``all_knn`` call in the traced span less the
device's busy time per call there: what the one-shot API's host work adds
to each call. Source: host clock and device trace."""

import statistics


def read(run: dict):
    walls, trace = run.get("traced_call_walls_s"), run.get("trace")
    if not walls or not trace:
        return None
    return 1e3 * (statistics.median(walls) - trace["busy_s"] / len(walls))
