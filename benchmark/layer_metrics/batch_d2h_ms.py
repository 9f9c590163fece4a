"""Time the pump thread spent per batch of the window fetching the batch's
answers from the device: the ``d2h`` phase of
``serve_batch_phase_seconds_total`` (the summed duration of the
``knn:batch.d2h`` spans) over ``serve_batches_total``, as the difference of
the two ``/metrics`` reads around the window. Source: program span."""

SAMPLE = 'serve_batch_phase_seconds_total{phase="d2h"}'


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta or SAMPLE not in delta:
        return None
    batches = delta.get("serve_batches_total", 0.0)
    if batches <= 0:
        return None
    return 1e3 * delta[SAMPLE] / batches
