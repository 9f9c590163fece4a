"""Device time of the clustered probe's first stage, a query row: own
device seconds under the program's ``knn.ivf/score`` scope in the traced
span (the query tile's dot against the centroid table at ``highest`` and
the top-``nprobe`` of its row) over the query rows the engine retired in it
(``serve_queries_total``, read when the trace starts and stops). Source:
device trace (``run["scopes"]``) and program counter."""

SCOPE = "knn.ivf/score"


def per_row_us(run: dict, scope: str):
    """Own seconds under ``scope`` over the traced query rows, in us; None
    where the trace names no such scope or no row was retired."""
    scopes = run.get("scopes")
    rows = (run.get("traced_metrics_delta") or {}).get(
        "serve_queries_total", 0.0)
    if not scopes or scope not in scopes or rows <= 0:
        return None
    return 1e6 * scopes[scope] / rows


def read(run: dict):
    return per_row_us(run, SCOPE)
