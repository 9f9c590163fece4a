"""Least time the chip could take for the bytes the traced writes must
move (``benchmark/opcount_stream.py``: rows, ids and norms of each chunk,
read and written once, over the HBM peak) over the scatter programs' own
device time: everything under the scopes ``knn.mutate/upsert`` and
``knn.mutate/delete`` in the traced span (``run["scopes"]``). The rows are
``mutation_upserts_total`` / ``mutation_deletes_total``, read when the
trace starts and stops. Source: device trace and program counter."""

from benchmark import opcount_stream

SCOPES = ("knn.mutate/upsert", "knn.mutate/delete")


def read(run: dict):
    scopes, peaks = run.get("scopes"), run.get("peaks")
    delta, stream = run.get("traced_metrics_delta"), run.get("stream")
    if not scopes or not peaks or not delta or not stream:
        return None
    busy = sum(scopes.get(s, 0.0) for s in SCOPES)
    up = delta.get("mutation_upserts_total", 0.0)
    down = delta.get("mutation_deletes_total", 0.0)
    if busy <= 0 or up + down <= 0:
        return None
    return 100.0 * opcount_stream.least_seconds(
        up, down, stream["dim"], peaks) / busy
