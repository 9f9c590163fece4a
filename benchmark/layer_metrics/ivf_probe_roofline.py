"""Least time the chip could take for what the traced batches probed
(``benchmark/opcount_ivf.py least_seconds``: the larger of 2 d operations a
(query row, live row) pair of ``ivf_probe_live_rows_total`` in one bf16
pass, and the live rows of the DISTINCT partitions each batch touched,
``ivf_probe_distinct_live_rows_total``, read once a batch at 4 d + 8 B)
over the device's busy time in the traced span. It reads the semantics'
work, not the implementation's: padding slots and a copy of a bucket for
every query row that probes it are in the time and not in the count.
Source: device trace and program counter."""

from benchmark import opcount_ivf


def read(run: dict):
    trace, peaks, about = run.get("trace"), run.get("peaks"), run.get("ivf")
    delta = run.get("traced_metrics_delta") or {}
    pairs = delta.get("ivf_probe_live_rows_total", 0.0)
    once = delta.get("ivf_probe_distinct_live_rows_total", 0.0)
    if (not trace or not peaks or not about or pairs <= 0 or once <= 0
            or trace["busy_s"] <= 0):
        return None
    least, _ = opcount_ivf.least_seconds(pairs, once, about["dim"], peaks)
    return 100.0 * least / trace["busy_s"]
