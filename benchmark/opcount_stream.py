"""Bytes a live mutation has to move, from shapes alone.

An upsert of n rows of width d into a float32 store must read the chunk
(rows, ids) and write the touched slots once: n rows of d float32, n int32
ids and n float32 norms, each read once (the chunk; the norms from the
rows) and written once. A delete of n ids must read and write n int32 ids.
The slot indices, the old slots an update clears and any pass over the
rest of the store are the program's cost, not the write's need: a scatter
that copies the stack moves gigabytes for these kilobytes and reads under
0.01 % of its roofline.
"""

from __future__ import annotations


def mutate_bytes(upserted_rows: float, deleted_rows: float, dim: int,
                 itemsize: int = 4) -> float:
    upsert = float(upserted_rows) * (dim * itemsize + 4 + 4)
    delete = float(deleted_rows) * 4
    return 2.0 * (upsert + delete)  # read once, written once


def least_seconds(upserted_rows: float, deleted_rows: float, dim: int,
                  peaks: dict) -> float:
    """The least time the chip could take for the traced writes: their
    bytes over the HBM peak (a scatter computes nothing)."""
    return mutate_bytes(upserted_rows, deleted_rows, dim) / peaks[
        "hbm_bytes_per_s"]
