"""Operations and bytes the algorithm needs over a BYTE corpus, from shapes
alone, and the least time a v5e could take for them.

Exact k-NN of Q query rows against C corpus rows of width d needs the Q x C
inner products, 2*Q*C*d operations (``opcount.knn_flops``), and has to read
the corpus once for each batch answered apart — at ONE byte an element,
which is what rests. The operations are held against the most the chip can
do with 8-bit operands, whatever the program feeds its matrix unit (a
program that widens its bytes to bf16, as this one does, can reach half of
it): 393e12 int8 operations a second. Source, as ``peaks.json`` has it:
Google Cloud documentation, 'TPU v5e' system architecture: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip. So a share of this
roofline cannot pass 100 % under either implementation.
"""

from __future__ import annotations

INT8_OPS_PER_S = {"TPU v5 lite": 393e12}


def knn_ops(q_rows: float, corpus_rows: int, dim: int) -> float:
    return 2.0 * q_rows * corpus_rows * dim


def knn_bytes(q_rows: float, batches: float, corpus_rows: int, dim: int,
              k: int) -> float:
    corpus = float(batches) * corpus_rows * dim * 1  # bytes at rest
    queries = float(q_rows) * dim * 4
    answers = float(q_rows) * k * (4 + 4)
    return corpus + queries + answers


def least_seconds(q_rows: float, batches: float, corpus_rows: int, dim: int,
                  k: int, peaks: dict, kind: str = "TPU v5 lite"
                  ) -> tuple[float, str]:
    """(least time the chip could take, which bound applied). ``peaks`` is
    the device's entry of ``peaks.json`` (the HBM rate); the 8-bit peak is
    this file's."""
    t_ops = knn_ops(q_rows, corpus_rows, dim) / INT8_OPS_PER_S[kind]
    t_bytes = knn_bytes(q_rows, batches, corpus_rows, dim, k) / peaks[
        "hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
