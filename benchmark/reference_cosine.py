"""The plain reference of the cosine cells: exact k nearest neighbours by
cosine distance ``1 - q.c / (|q| |c|)`` in float32, by the direct form over
corpus blocks — every product written out and summed, each side's norm by
the same direct sum — with no matrix multiplication, so no matmul precision
mode can touch it. It imports nothing of the program.

Unlike ``reference.py`` on whole-number data this one rounds: a dot here is
a 1536-term float32 sum of fractional products, off from the real number by
a few 1e-7 relative to a similarity near 0.8, which a distance of 0.2
sees four times larger. That band is part of what the configuration's
``dist_rel_err_max`` was measured from (PERF.md §4): the limit parts the
program as configured from the same program one precision lower, and the
reference's own rounding lies well inside it.

Semantics as the configuration states them: cosine distance, never below 0
(the true distance is not), the k smallest in ascending order, ties by the
lower id, no row left out (``exclude_self`` and ``exclude_zero`` are false:
a retrieval index hides no exact match). A row whose squared norm is at or
under ``NORM_EPS`` is at distance 1 from everything, as a zero row is.
"""

from __future__ import annotations

import functools

import numpy as np

NORM_EPS = 1e-30  # squared-norm clamp: a zero row has no direction


@functools.lru_cache(maxsize=None)
def _knn_fn(k: int, block_rows: int, q_chunk: int):
    """The jitted search, by the direct form."""
    import jax
    import jax.numpy as jnp

    def norms(x):  # (r, d) -> (r,), the direct sum of squares
        return jnp.sqrt(jnp.maximum(jnp.sum(x * x, axis=-1), NORM_EPS))

    @jax.jit
    def knn(corpus, q):
        # corpus (C, d), C % block_rows == 0; q (nq, d), nq % q_chunk == 0
        rows, d = corpus.shape
        nq = q.shape[0]

        def per_block(b):
            lo = b * block_rows
            blk = jax.lax.dynamic_slice_in_dim(corpus, lo, block_rows)
            ids = lo + jnp.arange(block_rows, dtype=jnp.int32)
            blk_n = norms(blk)

            def per_chunk(qc):  # q_chunk rows at a time bound the products
                dot = jnp.sum(qc[:, None, :] * blk[None, :, :], axis=-1)
                dist = 1.0 - dot / (norms(qc)[:, None] * blk_n[None, :])
                # top_k keeps the lower position among equals: the lower id
                neg, pos = jax.lax.top_k(-jnp.maximum(dist, 0.0), k)
                return -neg, ids[pos]

            dd, ii = jax.lax.map(per_chunk, q.reshape(-1, q_chunk, d))
            return dd.reshape(nq, k), ii.reshape(nq, k)

        dd, ii = jax.lax.map(
            per_block, jnp.arange(rows // block_rows, dtype=jnp.int32))
        # blocks in id order, each block's survivors ascending: position
        # order among equal distances is id order again
        d_all = jnp.moveaxis(dd, 0, 1).reshape(nq, -1)
        i_all = jnp.moveaxis(ii, 0, 1).reshape(nq, -1)
        neg, pos = jax.lax.top_k(-d_all, k)
        return -neg, jnp.take_along_axis(i_all, pos, axis=-1)

    return knn


def exact_knn_cosine(corpus, queries, k: int, block_rows: int = 8192,
                     q_chunk: int = 8):
    """((nq, k) cosine distances ascending, (nq, k) int32 ids), numpy: the
    plain reference. ``corpus`` is a (C, d) float32 device array (or
    anything ``jnp.asarray`` takes), ``queries`` a host (nq, d) array."""
    import jax.numpy as jnp

    corpus = jnp.asarray(corpus, dtype=jnp.float32)
    q = np.asarray(queries, dtype=np.float32)
    nq = q.shape[0]
    pad = (-nq) % q_chunk
    if pad:
        q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
    rows = corpus.shape[0]
    if rows % block_rows:
        block_rows = int(np.gcd(rows, block_rows))
    d, i = _knn_fn(int(k), int(block_rows), int(q_chunk))(
        corpus, jnp.asarray(q))
    return np.asarray(d)[:nq], np.asarray(i)[:nq]
