"""Least time the chip could take for the useful work of the traced span
(``benchmark/opcount.py``: the larger of 2*Q*C*d over the bf16 peak and the
bytes that must be read over the HBM peak), over the device's busy time in
that span. One number for the whole step: the program has no named scopes
yet. Source: device trace."""


def read(run: dict):
    work, trace = run.get("traced_work"), run.get("trace")
    if not work or not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * work["least_s"] / trace["busy_s"]
