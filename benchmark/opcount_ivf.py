"""Operations and bytes a clustered (IVF-Flat) batch needs, from what it
probed: the semantics' work, not the implementation's.

A batch's query rows score ``partitions`` centroids (nothing beside what
follows) and are compared with every LIVE row of the ``nprobe`` partitions
each probes: 2 * d operations a (query row, live row) pair, in one bf16
pass where the data allows it — ``ivf_probe_live_rows_total`` counts the
pairs. The rows themselves have to cross HBM once a batch, however many of
the batch's query rows probe a partition: the live rows of the DISTINCT
partitions the batch touched (``ivf_probe_distinct_live_rows_total``), d
float32 each plus the id and the norm (8 B). Padding slots, a copy of a
bucket for each query row that probes it, a gather written out and read
again are the program's cost: a bucket-major probe, a paged store or a
fused kernel is judged by the same count.
"""

from __future__ import annotations


def probe_flops(live_pairs: float, dim: int) -> float:
    return 2.0 * float(live_pairs) * dim


def probe_bytes(distinct_live_rows: float, dim: int,
                itemsize: int = 4) -> float:
    return float(distinct_live_rows) * (dim * itemsize + 8)


def least_seconds(live_pairs: float, distinct_live_rows: float, dim: int,
                  peaks: dict) -> tuple[float, str]:
    """(least seconds the chip could take, which bound applies)."""
    by_flops = probe_flops(live_pairs, dim) / peaks["bf16_flops_per_s"]
    by_bytes = probe_bytes(distinct_live_rows, dim) / peaks[
        "hbm_bytes_per_s"]
    return max(by_flops, by_bytes), (
        "compute" if by_flops >= by_bytes else "memory")
