"""Operations and bytes RANGE search needs over a byte corpus, from shapes
alone, and the least time a v5e could take for them.

Every corpus row within a radius of each of Q query rows, against C corpus
rows of width d, needs the Q x C squared distances — 2*Q*C*d operations, as
exact k-NN does (``opcount_u8.knn_ops``) — and has to read the corpus ONCE
for each batch answered apart, at the one byte an element that rests, plus
the query rows and the answers it returns (a distance and an id a result).
The count is the same whatever implements it: a program that walks the
stack a second time for the rows whose results its lists could not hold
does overhead, not work, so a share of this roofline cannot pass 100 %.
The operations are held against the most the chip can do with 8-bit
operands (``opcount_u8.INT8_OPS_PER_S``: 393e12 a second on the v5e; Google
Cloud documentation, 'TPU v5e' system architecture: 197 TFLOP/s bf16, 393
TOP/s int8, 16 GB HBM2e at 819 GB/s per chip), the bytes against the HBM
rate of ``peaks.json``.
"""

from __future__ import annotations

from benchmark.opcount_u8 import INT8_OPS_PER_S, knn_ops


def range_bytes(q_rows: float, batches: float, results: float,
                corpus_rows: int, dim: int) -> float:
    corpus = float(batches) * corpus_rows * dim * 1  # bytes at rest, once
    queries = float(q_rows) * (dim * 4 + 4)  # the rows and their radii
    answers = float(results) * (4 + 4) + float(q_rows) * 4  # and offsets
    return corpus + queries + answers


def least_seconds(q_rows: float, batches: float, results: float,
                  corpus_rows: int, dim: int, peaks: dict,
                  kind: str = "TPU v5 lite") -> tuple[float, str]:
    """(least time the chip could take, which bound applied)."""
    t_ops = knn_ops(q_rows, corpus_rows, dim) / INT8_OPS_PER_S[kind]
    t_bytes = range_bytes(q_rows, batches, results, corpus_rows,
                          dim) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
