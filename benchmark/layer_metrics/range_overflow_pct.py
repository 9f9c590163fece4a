"""Share of the device's busy time in the traced span that went to the
SECOND path of range search: the device seconds under the program's
``knn.range_overflow`` scope (the rows whose results the lane lists could
not hold: their tiles fetched again, ranked and written out) over the
span's busy seconds. What completeness costs beyond the scan; it goes with
the number of such rows a batch and the tiles their results lie in. None
where the program has no such scope (the parent commit). Source: device
trace."""


def read(run: dict):
    ranged, trace = run.get("range"), run.get("trace")
    if not ranged or not trace or ranged.get("overflow_s") is None:
        return None
    busy = trace.get("busy_s") or 0.0
    if busy <= 0:
        return None
    return 100.0 * ranged["overflow_s"] / busy
