"""The plain reference over a corpus that lies sharded by rows on several
chips: ``reference.exact_knn`` (direct form, float32, no matmul) on each
shard where it lies, ids offset by the shard's first row, then the k
smallest of the shards' survivors on the host, equal distances by the lower
id. Nothing of corpus size is gathered, and it imports nothing of the
program.

Every true neighbour is among its own shard's k smallest, so the merge
loses nothing: the answer is ``reference.exact_knn``'s on the gathered
array (``tests/test_reference_sharded.py`` holds it to that, ties
included).
"""

from __future__ import annotations

import numpy as np

from benchmark import reference


def row_shards(corpus) -> list:
    """``[(first row, the shard's single-device array)]`` in row order, one
    entry for each distinct block of rows (a replica is left out)."""
    seen, out = set(), []
    for s in corpus.addressable_shards:
        lo = s.index[0].start or 0
        if lo not in seen:
            seen.add(lo)
            out.append((int(lo), s.data))
    return sorted(out, key=lambda pair: pair[0])


def merge_smallest(dists: np.ndarray, ids: np.ndarray, k: int):
    """The k smallest of each row of (nq, n) candidates, ascending, equal
    distances by the lower id."""
    order = np.lexsort((ids, dists), axis=-1)[:, :k]
    return (np.take_along_axis(dists, order, axis=-1),
            np.take_along_axis(ids, order, axis=-1))


def take_rows(corpus, row_ids) -> np.ndarray:
    """Rows of the sharded corpus by global id, on the host, each read from
    the shard that holds it."""
    row_ids = np.asarray(row_ids)
    out = None
    for lo, data in row_shards(corpus):
        here = (row_ids >= lo) & (row_ids < lo + data.shape[0])
        if not here.any():
            continue
        got = np.asarray(data[row_ids[here] - lo])
        if out is None:
            out = np.zeros((row_ids.shape[0], got.shape[1]), got.dtype)
        out[here] = got
    return out


def exact_knn(corpus, queries, k: int, self_ids=None,
              exclude_zero: bool = True, **block):
    """((nq, k) squared distances ascending, (nq, k) int32 global ids),
    numpy. ``corpus`` is a (C, d) float32 array sharded by rows over
    devices; ``queries`` a host (nq, d) array; ``self_ids`` global."""
    queries = np.asarray(queries, dtype=np.float32)
    dd, ii = [], []
    for lo, data in row_shards(corpus):
        local = None
        if self_ids is not None:
            # a row of another shard has no identity here: below zero
            sid = np.asarray(self_ids, np.int64) - lo
            local = np.where((sid >= 0) & (sid < data.shape[0]), sid,
                             -1).astype(np.int32)
        d, i = reference.exact_knn(data, queries, k, self_ids=local,
                                   exclude_zero=exclude_zero, **block)
        dd.append(d)
        ii.append(i.astype(np.int32) + np.int32(lo))
    return merge_smallest(np.concatenate(dd, axis=1),
                          np.concatenate(ii, axis=1), k)
