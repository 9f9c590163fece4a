"""Host time of the write path for a thousand rows written: the sum of
``mutation_latency_seconds`` (one observation a mutation call: parse, plan,
copy, donated dispatch, commit) over the rows of ``mutation_upserts_total``
+ ``mutation_deletes_total``, both over the window. Source: program span
(``knn:mutate.upsert`` / ``knn:mutate.delete``)."""


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    rows = (delta.get("mutation_upserts_total", 0.0)
            + delta.get("mutation_deletes_total", 0.0))
    seconds = delta.get("mutation_latency_seconds_sum")
    if seconds is None or rows <= 0:
        return None
    return 1e3 * seconds / (rows / 1e3)
