"""The plain reference of the filtered cells: exact k nearest neighbours
AMONG THE ROWS WHOSE BAG HOLDS EVERY TAG OF THE QUERY ROW, in float32 by the
direct form sum((q - c)**2) over corpus blocks (no matrix multiplication, so
no matmul precision mode can touch it; ``jax.default_matmul_precision`` is
set to ``highest`` around it all the same). It imports nothing of the
program and knows no bitsets, no posting lists and no regimes: the
predicate is read straight off the bags, given as a matrix (a row's tag ids
first, any id past the vocabulary after them) — a block's rows against a
query's tags, element by element.

Semantics as the configuration states them: squared L2, the k smallest in
ascending order with ties by the lower id, exact-zero distances left out
where ``exclude_zero`` is set, a query row with no tag answered against
all rows, and a query that fewer than k rows match answered with those
rows and then empty slots (distance +inf, id -1).
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _knn_fn(k: int, exclude_zero: bool, block_rows: int, q_chunk: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def knn(corpus, bags, q, q_tags):
        # corpus (C, d), bags (C, B), C % block_rows == 0; q (nq, d),
        # q_tags (nq, W), nq % q_chunk == 0
        rows, d = corpus.shape
        nq = q.shape[0]

        def per_block(b):
            lo = b * block_rows
            blk = jax.lax.dynamic_slice_in_dim(corpus, lo, block_rows)
            bag = jax.lax.dynamic_slice_in_dim(bags, lo, block_rows)
            ids = lo + jnp.arange(block_rows, dtype=jnp.int32)

            def per_chunk(args):  # q_chunk rows at a time bound the diffs
                qc, tc = args
                diff = blk[None, :, :] - qc[:, None, :]
                d2 = jnp.sum(diff * diff, axis=-1)
                # every tag of the query row is in the corpus row's bag
                has = (bag[None, :, None, :] == tc[:, None, :, None]).any(-1)
                keep = (has | (tc < 0)[:, None, :]).all(-1)
                drop = ~keep
                if exclude_zero:
                    drop = drop | (d2 <= 0.0)
                neg, pos = jax.lax.top_k(-jnp.where(drop, jnp.inf, d2), k)
                return -neg, ids[pos]

            dd, ii = jax.lax.map(
                per_chunk, (q.reshape(nq // q_chunk, q_chunk, d),
                            q_tags.reshape(nq // q_chunk, q_chunk, -1)))
            return dd.reshape(nq, k), ii.reshape(nq, k)

        dd, ii = jax.lax.map(
            per_block, jnp.arange(rows // block_rows, dtype=jnp.int32))
        d_all = jnp.moveaxis(dd, 0, 1).reshape(nq, -1)
        i_all = jnp.moveaxis(ii, 0, 1).reshape(nq, -1)
        neg, pos = jax.lax.top_k(-d_all, k)
        best = -neg
        return best, jnp.where(jnp.isinf(best), -1,
                               jnp.take_along_axis(i_all, pos, axis=-1))

    return knn


def exact_knn_filtered(corpus, bags, queries, q_tags, k: int,
                       exclude_zero: bool = True, block_rows: int = 16384,
                       q_chunk: int = 8):
    """((nq, k) squared distances ascending, (nq, k) int32 ids), numpy.
    ``corpus`` (C, d) float32 on the device (or anything ``jnp.asarray``
    takes), ``bags`` (C, B) int32 the rows' tag ids (any id >= 0 a tag,
    the filler past the vocabulary), ``queries`` (nq, d), ``q_tags``
    (nq, W) int32 with -1 for none."""
    import jax
    import jax.numpy as jnp

    corpus = jnp.asarray(corpus, dtype=jnp.float32)
    q = np.asarray(queries, dtype=np.float32)
    t = np.asarray(q_tags, dtype=np.int32)
    nq = q.shape[0]
    pad = (-nq) % q_chunk
    if pad:
        q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
        t = np.concatenate([t, np.full((pad, t.shape[1]), -1, np.int32)])
    rows = corpus.shape[0]
    if rows % block_rows:
        block_rows = int(np.gcd(rows, block_rows))
    knn = _knn_fn(int(k), bool(exclude_zero), int(block_rows), q_chunk)
    with jax.default_matmul_precision("highest"):
        d, i = knn(corpus, jnp.asarray(bags, dtype=jnp.int32),
                   jnp.asarray(q), jnp.asarray(t))
    return np.asarray(d)[:nq], np.asarray(i)[:nq]
