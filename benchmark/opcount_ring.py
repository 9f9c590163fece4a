"""One chip's share of a corpus ring's work, from shapes alone.

A call answers ``q_rows`` query rows over ``chips`` chips: each chip keeps
``q_rows / chips`` of them and computes them against every block as it
comes by, so against all ``corpus_rows``. The corpus streams past a chip's
query tile once a call (its own block from its memory, the others as they
arrive and are read again from memory). What goes over the wire is the
ring's cost, not the algorithm's need, and is not counted here
(``opcount.py`` says the same of the passes for precision and selection).
"""

from __future__ import annotations

from benchmark import opcount


def chip_rows(q_rows: float, chips: int) -> float:
    return float(q_rows) / chips


def chip_flops(q_rows: float, chips: int, corpus_rows: int, dim: int) -> float:
    return opcount.knn_flops(chip_rows(q_rows, chips), corpus_rows, dim)


def chip_bytes(q_rows: float, calls: float, chips: int, corpus_rows: int,
               dim: int, k: int) -> float:
    return opcount.knn_bytes(chip_rows(q_rows, chips), calls, corpus_rows,
                             dim, k)


def chip_least_seconds(q_rows: float, calls: float, chips: int,
                       corpus_rows: int, dim: int, k: int,
                       peaks: dict) -> tuple[float, str]:
    """(least time one chip could take for its share of ``q_rows`` rows
    answered in ``calls`` calls, which bound applied)."""
    return opcount.least_seconds(chip_rows(q_rows, chips), calls,
                                 corpus_rows, dim, k, peaks)
