"""Device time of one tile step of the scan over a byte stack: the device
seconds under the program's ``knn.scan_u8`` scope in the traced span (the
tile fetched as bytes, widened and centred, the one-pass dot, the masks,
the bound's test and the insertion into the lists: the kernel, or the
scan's one-pass tile steps) over the tile steps the engine retired in it
(``knn_dist_tile_steps_total{path="u8"}``, read when the trace starts and
stops). A float32 stack's kernel took 29.7 us a 1024 x 8192 x 128 step
(PERF.md §5). None where the program has no such counter or scope (the
parent commit). Source: device trace and program counter."""

STEPS = 'knn_dist_tile_steps_total{path="u8"}'


def read(run: dict):
    u8 = run.get("u8")
    steps = (run.get("traced_metrics_delta") or {}).get(STEPS, 0.0)
    if not u8 or steps <= 0 or not u8.get("scan_s"):
        return None
    return 1e6 * u8["scan_s"] / steps
