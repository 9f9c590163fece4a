"""Device time of the clustered probe's exact finish, a query row: own
device seconds under the program's ``knn.rerank`` scope in the traced span
(``ops/rerank.py rerank_exact_topk``: the row's dot against its own
gathered candidates at ``highest``, the masks, the k smallest of nprobe x
bucket_cap columns) over the query rows retired in it, as
``ivf_score_us_per_row`` counts them. With the score, the gather and loop
control it adds up to the step. Source: device trace and program
counter."""

from benchmark.harness import load_by_path

SCOPE = "knn.rerank"


def read(run: dict):
    return load_by_path("layer_metrics", "ivf_score_us_per_row").per_row_us(
        run, SCOPE)
