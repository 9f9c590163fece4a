"""Device time of one cosine tile step's distance half: own device seconds
under the program's ``knn.dist_cosine`` scope in the traced span (the dot
at the configured precision, its scaling by the corpus rows' inverse norms,
the masks) over the tile steps the engine retired in it
(``knn_dist_tile_steps_total{path="cosine"}``, read when the trace starts
and stops). On a v5e a 1024 x 8192 x 1536 step's dot cannot take less than
131 us in one bf16 pass and 785 us in the six of float32 at ``highest``.
Source: device trace (``run["scopes"]``) and program counter."""

STEPS = 'knn_dist_tile_steps_total{path="cosine"}'
SCOPE = "knn.dist_cosine"


def traced_steps(run: dict):
    """Cosine tile steps retired in the traced span, or None (no counter of
    that name in the program, or none moved)."""
    steps = (run.get("traced_metrics_delta") or {}).get(STEPS, 0.0)
    return steps if steps > 0 else None


def read(run: dict):
    scopes, steps = run.get("scopes"), traced_steps(run)
    if not scopes or steps is None or SCOPE not in scopes:
        return None
    return 1e6 * scopes[SCOPE] / steps
