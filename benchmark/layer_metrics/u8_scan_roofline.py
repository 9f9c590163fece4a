"""Least time the chip could take for the traced span's query rows against
the byte corpus (``benchmark/opcount_u8.py``: 2*Q*C*d operations over the
v5e's 8-bit peak, 393e12 a second, or the corpus's bytes at rest once a
batch over the HBM peak, whichever is larger) over the device seconds
under the program's ``knn.scan_u8`` scope in that span: the share of its
roofline the scan over a byte stack reaches, kernel or tile steps. The
rows and batches are the movement of ``serve_queries_total`` and
``serve_batches_total`` between the trace's start and stop. None where the
program has no such scope (the parent commit). Source: device trace."""

from benchmark import opcount_u8


def read(run: dict):
    u8, peaks = run.get("u8"), run.get("peaks")
    delta, about = run.get("traced_metrics_delta"), run.get("about")
    if not u8 or not peaks or not delta or not about:
        return None
    scan_s = u8.get("scan_s") or 0.0
    rows = delta.get("serve_queries_total", 0.0)
    batches = delta.get("serve_batches_total", 0.0)
    if scan_s <= 0 or rows <= 0 or batches <= 0:
        return None
    least, _ = opcount_u8.least_seconds(
        rows, batches, about["rows"], about["dim"], about["k"], peaks,
        run["device"]["kind"])
    return 100.0 * least / scan_s
