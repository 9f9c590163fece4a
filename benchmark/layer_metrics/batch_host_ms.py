"""Host time of the pump thread per batch of the window, outside its waits:
the ``coalesce``, ``prep``, ``enqueue``, ``d2h`` and ``reply`` phases of
``serve_batch_phase_seconds_total`` (each the summed duration of the
``knn:`` span of that name) over ``serve_batches_total``, as the difference
of the two ``/metrics`` reads around the window. Left out: ``idle`` (nothing
to do) and ``wait`` (blocked on the device). Source: program span."""

PHASES = ("coalesce", "prep", "enqueue", "d2h", "reply")


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta:
        return None
    names = ['serve_batch_phase_seconds_total{phase="%s"}' % p
             for p in PHASES]
    batches = delta.get("serve_batches_total", 0.0)
    if batches <= 0 or not any(n in delta for n in names):
        return None
    return 1e3 * sum(delta.get(n, 0.0) for n in names) / batches
