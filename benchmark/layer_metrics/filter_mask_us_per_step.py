"""Device time the predicate adds to one tile step of the masked scan: own
device seconds under the program's ``knn.filter_mask`` scope in the traced
span (the gather of a query tile's words from the index's bitsets ahead of
its scan, and in every step the words' expansion to the (rows, corpus
tile) plane) over the tile steps the engine retired in it
(``knn_dist_tile_steps_total``, every path, read when the trace starts and
stops). Source: device trace (``run["scopes"]``) and program counter."""

SCOPE = "knn.filter_mask"
STEPS = "knn_dist_tile_steps_total{"


def read(run: dict):
    scopes = run.get("scopes")
    delta = run.get("traced_metrics_delta") or {}
    steps = sum(v for name, v in delta.items() if name.startswith(STEPS))
    if not scopes or SCOPE not in scopes or steps <= 0:
        return None
    return 1e6 * scopes[SCOPE] / steps
