"""Operations and bytes the algorithm needs, from shapes alone.

Exact k-NN of Q query rows against C corpus rows of width d needs the Q x C
inner products, 2*Q*C*d floating-point operations (the closed form of
``analysis/cost.py analytical_mxu_flops``), and has to read the corpus once
for each batch that is answered apart (a batch is a query tile: the corpus
streams past it once), plus the queries, and write Q*k distances and ids.
Extra passes for precision (3 at ``high``, 6 at ``highest``), centring,
norms and selection are the program's cost, not the algorithm's need, so
they are not counted: a share of the roofline says how far the whole step
is from what the chip could do for the useful work.
"""

from __future__ import annotations


def knn_flops(q_rows: int, corpus_rows: int, dim: int) -> float:
    return 2.0 * q_rows * corpus_rows * dim


def knn_bytes(q_rows: int, batches: int, corpus_rows: int, dim: int,
              k: int, itemsize: int = 4) -> float:
    corpus = float(batches) * corpus_rows * dim * itemsize
    queries = float(q_rows) * dim * itemsize
    answers = float(q_rows) * k * (4 + 4)
    return corpus + queries + answers


def least_seconds(q_rows: int, batches: int, corpus_rows: int, dim: int,
                  k: int, peaks: dict) -> tuple[float, str]:
    """(least time the chip could take, which bound applied)."""
    t_flops = knn_flops(q_rows, corpus_rows, dim) / peaks["bf16_flops_per_s"]
    t_bytes = knn_bytes(q_rows, batches, corpus_rows, dim, k) / peaks[
        "hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
