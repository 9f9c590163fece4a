"""Device time of the clustered probe's gather, a query row: own device
seconds under the program's ``knn.ivf/gather`` scope in the traced span
(the takes of the probed partitions' padded buckets — rows, ids, norms —
a copy for every query row that probes one, and whatever the compiler makes
of them) over the query rows retired in it, as ``ivf_score_us_per_row``
counts them. Source: device trace and program counter."""

from benchmark.harness import load_by_path

SCOPE = "knn.ivf/gather"


def read(run: dict):
    return load_by_path("layer_metrics", "ivf_score_us_per_row").per_row_us(
        run, SCOPE)
