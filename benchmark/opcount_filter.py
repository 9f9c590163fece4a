"""Operations and bytes a filtered batch needs, from shapes alone, by the
regime its rows take (``mpi_knn_tpu/serve/tags.py``).

*Masked scan*: what ``opcount.py`` counts for an unfiltered batch of the
same rows — the Q x C inner products, 2*Q*C*d operations in ONE bf16 pass,
and the corpus read once for each dispatch that is answered apart — the
same work whatever implements the predicate: the bitsets' words, the
mask's expansion and extra passes are the program's cost.

*Gather and finish*: a candidate slot is d float32 of a row plus its id
and its norm (8 B), read once; the inner products of a row's few thousand
candidates are nothing beside that, so the bytes bound. The slots are the
ones the gather programs were given, padding included
(``filter_gather_slots_total``): a padded slot is read like any other, and
what padding costs is ``filter_slots_per_candidate``'s to say.
"""

from __future__ import annotations

from benchmark import opcount


def scan_least_seconds(scan_rows: float, dispatches: float,
                       corpus_rows: int, dim: int, k: int,
                       peaks: dict) -> float:
    """The least time the chip could take for the scan regime's rows."""
    return opcount.least_seconds(
        scan_rows, dispatches, corpus_rows, dim, k, peaks)[0]


def gather_bytes(slots: float, dim: int, itemsize: int = 4) -> float:
    return float(slots) * (dim * itemsize + 8)


def gather_least_seconds(slots: float, dim: int, peaks: dict) -> float:
    """The least time the chip could take to read the gathered slots."""
    return gather_bytes(slots, dim) / peaks["hbm_bytes_per_s"]
