"""Device time of one inner-product tile step's distance half: own device
seconds under the program's ``knn.dist_ip`` scope in the traced span (the
dot at the configured precision, its negation, the masks) over the tile
steps the engine retired in it (``knn_dist_tile_steps_total{path="ip"}``,
read when the trace starts and stops). On a v5e a 1024 x 8192 x 200 step's
dot cannot take less than 17 us in one bf16 pass and 102 us in the six of
float32 at ``highest``; reading the tile (6.6 MB) takes 8 us. Source: device
trace (``run["scopes"]``) and program counter."""

STEPS = 'knn_dist_tile_steps_total{path="ip"}'
SCOPE = "knn.dist_ip"


def traced_steps(run: dict):
    """Inner-product tile steps retired in the traced span, or None (no
    counter of that name in the program, or none moved)."""
    steps = (run.get("traced_metrics_delta") or {}).get(STEPS, 0.0)
    return steps if steps > 0 else None


def read(run: dict):
    scopes, steps = run.get("scopes"), traced_steps(run)
    if not scopes or steps is None or SCOPE not in scopes:
        return None
    return 1e6 * scopes[SCOPE] / steps
