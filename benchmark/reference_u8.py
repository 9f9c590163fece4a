"""The plain reference of the byte-stack cells: exact k nearest neighbours
by the direct form ``sum((q - c)**2)`` in float32 over corpus BLOCKS that a
callable hands over one at a time — ``reference.py``'s semantics with the
corpus streamed, because no (N, 128) float32 array of a hundred million
rows can exist on the chip (51.2e9 B). No matrix multiplication, so no
matmul precision mode can touch it; it imports nothing of the program and
never reads the index.

On whole-number data in [0, 255] every term and every partial sum is a
whole number below 2**24 (128 x 255**2 = 8.3e6), so the float32 sums are
exact and the comparison with the program is EQUALITY of distances.
Semantics as the configuration states them: squared L2, the k smallest in
ascending order, ties by the lower id, exact-zero distances left out where
``exclude_zero`` is set.

The form is chosen for the device it runs on, not for the program: a block
is widened and turned once so that its rows lie along the lanes, the
differences are squared and summed down the other axis (elementwise adds,
no reduction across lanes), and the k smallest of a block are taken by k
rounds of minimum-and-knock-out over the (queries, block) distances — k
passes over an array, where one sort of it would be hundreds.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _block_fn(k: int, exclude_zero: bool, q_chunk: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def merge(best_d, best_i, blk, q, lo):
        # blk (B, d) any whole-number type; q (nq, d) float32
        rows = blk.shape[0]
        nq, d = q.shape
        turned = blk.astype(jnp.float32).T  # (d, B)

        def per_chunk(qc):  # (q_chunk, d): bounds the differences' size
            diff = turned[None, :, :] - qc[:, :, None]
            return jnp.sum(diff * diff, axis=1)

        d2 = jax.lax.map(
            per_chunk, q.reshape(nq // q_chunk, q_chunk, d)).reshape(nq, rows)
        if exclude_zero:
            d2 = jnp.where(d2 <= 0.0, jnp.inf, d2)
        at = jnp.arange(rows, dtype=jnp.int32)

        def knock_out(_, state):
            d2, vals, ids, j = state
            m = jnp.min(d2, axis=1)
            # the lowest column that holds the minimum
            pos = jnp.min(jnp.where(d2 == m[:, None], at[None, :], rows),
                          axis=1)
            d2 = jnp.where(at[None, :] == pos[:, None], jnp.inf, d2)
            return (d2, vals.at[:, j].set(m),
                    ids.at[:, j].set(jnp.where(m < jnp.inf, lo + pos, -1)),
                    j + 1)

        _, vals, ids, _ = jax.lax.fori_loop(
            0, k, knock_out,
            (d2, jnp.full((nq, k), jnp.inf, jnp.float32),
             jnp.full((nq, k), -1, jnp.int32), 0))
        # the carried k and the block's k: earlier blocks first, so a tie
        # keeps the lower id
        all_d = jnp.concatenate([best_d, vals], axis=1)
        all_i = jnp.concatenate([best_i, ids], axis=1)
        neg, pos = jax.lax.top_k(-all_d, k)
        return -neg, jnp.take_along_axis(all_i, pos, axis=1)

    return merge


def exact_knn_blocks(block_of, block_rows, queries, k: int,
                     exclude_zero: bool = True, q_chunk: int = 8):
    """((nq, k) squared distances ascending, (nq, k) int32 ids), numpy.
    ``block_of(b)`` hands over block ``b`` of the corpus, a (block_rows[b],
    d) array of whole numbers (on the device or the host), whose first row
    is corpus row ``sum(block_rows[:b])``; ``queries`` is a host (nq, d)
    array."""
    import jax.numpy as jnp

    q = np.asarray(queries, dtype=np.float32)
    nq = q.shape[0]
    pad = (-nq) % q_chunk
    if pad:
        q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
    merge = _block_fn(int(k), bool(exclude_zero), int(q_chunk))
    qd = jnp.asarray(q)
    best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
    best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
    lo = 0
    for b, rows in enumerate(block_rows):
        blk = block_of(b)
        if blk.shape[0] != rows:
            raise ValueError(f"block {b} holds {blk.shape[0]} rows, not "
                             f"{rows}")
        best_d, best_i = merge(best_d, best_i, blk, qd, np.int32(lo))
        lo += rows
    return np.asarray(best_d)[:nq], np.asarray(best_i)[:nq]
