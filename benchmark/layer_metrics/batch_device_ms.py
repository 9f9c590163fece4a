"""Device busy time in the traced span over the batches the engine retired
in it (``serve_batches_total``, read when the trace starts and stops).
Source: device trace and program counter."""


def read(run: dict):
    trace, delta = run.get("trace"), run.get("traced_metrics_delta")
    if not trace or not delta:
        return None
    batches = delta.get("serve_batches_total", 0.0)
    if batches <= 0:
        return None
    return 1e3 * trace["busy_s"] / batches
