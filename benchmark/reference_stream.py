"""The plain reference of a corpus that changes: a model of the index —
every row that will ever exist, by id, and a ``live`` mask — with the exact
k nearest LIVE rows by the direct form sum((q - c)**2) in float32 over
blocks, no matrix multiplication, ties by the lower id, dead rows at +inf.
``reference.py``'s form with a mask; it imports nothing of the program.

The rows come in segments (the base corpus on the device as the launcher
made it, the rows the runbook inserts as a second, small array), so that
no second corpus-sized array is made: each segment is searched on its own
and the survivors are merged on the host, the lower id first on a tie.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _knn_fn(k: int, block_rows: int, q_chunk: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def knn(rows, live, q):
        # rows (C, d), live (C,) bool, C % block_rows == 0; q (nq, d),
        # nq % q_chunk == 0
        n_rows, d = rows.shape
        nq = q.shape[0]

        def per_block(b):
            lo = b * block_rows
            blk = jax.lax.dynamic_slice_in_dim(rows, lo, block_rows)
            alive = jax.lax.dynamic_slice_in_dim(live, lo, block_rows)
            pos = lo + jnp.arange(block_rows, dtype=jnp.int32)

            def per_chunk(qc):  # q_chunk rows at a time bound the diffs
                diff = blk[None, :, :] - qc[:, None, :]
                d2 = jnp.where(alive[None, :], jnp.sum(diff * diff, axis=-1),
                               jnp.inf)
                neg, at = jax.lax.top_k(-d2, k)
                return -neg, pos[at]

            dd, ii = jax.lax.map(per_chunk,
                                 q.reshape(nq // q_chunk, q_chunk, d))
            return dd.reshape(nq, k), ii.reshape(nq, k)

        dd, ii = jax.lax.map(
            per_block, jnp.arange(n_rows // block_rows, dtype=jnp.int32))
        d_all = jnp.moveaxis(dd, 0, 1).reshape(nq, -1)
        i_all = jnp.moveaxis(ii, 0, 1).reshape(nq, -1)
        neg, at = jax.lax.top_k(-d_all, k)
        return -neg, jnp.take_along_axis(i_all, at, axis=-1)

    return knn


class StreamModel:
    """Rows by id in ``segments`` — ``[(first id, (n, d) float32 array)]``,
    ascending and gap-free from id 0 — and which ids are live."""

    def __init__(self, segments: list, live_rows: int):
        self.segments = segments
        self.ids = sum(int(a.shape[0]) for _, a in segments)
        self.live = np.zeros(self.ids, dtype=bool)
        self.live[:live_rows] = True

    def apply(self, op: dict) -> None:
        """One runbook step: an insert makes its range live, a delete
        dead; a search changes nothing."""
        if op["operation"] == "search":
            return
        self.live[op["start"]:op["end"]] = op["operation"] == "insert"

    def exact_knn_live(self, queries, k: int, also_live=None,
                       block_rows: int = 16384, q_chunk: int = 8):
        """((nq, k) squared distances ascending, (nq, k) int32 ids), numpy:
        the k nearest live rows (``also_live``: a bool mask of ids counted
        as live besides, for the question "had they stayed")."""
        import jax.numpy as jnp

        live = self.live if also_live is None else self.live | also_live
        q = np.asarray(queries, dtype=np.float32)
        nq = q.shape[0]
        pad = (-nq) % q_chunk
        if pad:
            q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
        found_d, found_i = [], []
        for first, rows in self.segments:
            n = int(rows.shape[0])
            blk = block_rows if n % block_rows == 0 else int(
                np.gcd(n, block_rows))
            knn = _knn_fn(int(min(k, blk)), blk, q_chunk)
            d, i = knn(jnp.asarray(rows, dtype=jnp.float32),
                       jnp.asarray(live[first:first + n]), jnp.asarray(q))
            found_d.append(np.asarray(d)[:nq])
            found_i.append(np.asarray(i)[:nq].astype(np.int64) + first)
        d_all = np.concatenate(found_d, axis=1)
        i_all = np.concatenate(found_i, axis=1)
        # the lower id first on a tie: segments are in id order and each
        # segment's survivors are, so a stable sort by distance keeps it
        order = np.argsort(d_all, axis=1, kind="stable")[:, :k]
        return (np.take_along_axis(d_all, order, axis=1),
                np.take_along_axis(i_all, order, axis=1).astype(np.int32))
