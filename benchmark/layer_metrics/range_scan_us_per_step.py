"""Device time of one tile step of the range scan: the device seconds under
the program's ``knn.scan_range`` scope in the traced span (a byte tile
fetched, widened and centred, the one-pass dot, the radius's test, the
count and the insertion of what passed: the fused kernel in its ranged
form) over the tile steps the engine retired in it
(``knn_dist_tile_steps_total{path="range"}``, read when the trace starts
and stops). The byte cell's k-NN step took 20.06 us at d = 128 (PERF.md
§5); the operations of a step double at d = 256. None where the program has
no such counter or scope (the parent commit). Source: device trace and
program counter."""

STEPS = 'knn_dist_tile_steps_total{path="range"}'


def read(run: dict):
    ranged = run.get("range")
    steps = (run.get("traced_metrics_delta") or {}).get(STEPS, 0.0)
    if not ranged or steps <= 0 or not ranged.get("scan_s"):
        return None
    return 1e6 * ranged["scan_s"] / steps
