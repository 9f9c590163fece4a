"""Device time of one inner-product tile step outside its distance half:
the device's busy seconds in the traced span less the own time under
``knn.dist_ip`` (what is left: the tile's slice, *bins* into the carried
lists, the bound's refreshes, the finish and the merge once a batch, loop
control), over the same count of tile steps as ``ip_dist_us_per_step``;
the two add up to the step. Source: device trace (``run["trace"]``,
``run["scopes"]``) and program counter."""

from benchmark.harness import load_by_path


def read(run: dict):
    dist = load_by_path("layer_metrics", "ip_dist_us_per_step")
    scopes, trace = run.get("scopes"), run.get("trace")
    steps = dist.traced_steps(run)
    if not scopes or not trace or steps is None or dist.SCOPE not in scopes:
        return None
    return 1e6 * (trace["busy_s"] - scopes[dist.SCOPE]) / steps
