"""The plain reference: exact k nearest neighbours in float32 by the direct
form sum((q - c)**2) over corpus blocks, with no matrix multiplication, so
no matmul precision mode can touch it. It imports nothing of the program.

On whole-number data in [0, 255] (both configurations) every term and every
partial sum is a whole number below 2**24, so the float32 sums are exact.
Semantics as the configurations state them: squared L2, the k smallest in
ascending order, a query's own corpus row left out where ``self_ids`` names
it, and exact-zero distances left out where ``exclude_zero`` is set.
"""

from __future__ import annotations

import functools

import numpy as np


def _smallest(d2, ids, self_ids, k: int, exclude_zero: bool):
    """The k smallest of each row of ``d2`` (q, B) with their ids, a row's
    own corpus row and (where asked) zero distances left out."""
    import jax
    import jax.numpy as jnp

    drop = ids[None, :] == self_ids[:, None]
    if exclude_zero:
        drop = drop | (d2 <= 0.0)
    neg, pos = jax.lax.top_k(-jnp.where(drop, jnp.inf, d2), k)
    return -neg, ids[pos]


def _over_blocks(per_block, n_blocks: int, nq: int, k: int):
    """Run ``per_block(b) -> ((nq, k) d, (nq, k) ids)`` over the corpus
    blocks and keep the k smallest of all their survivors."""
    import jax
    import jax.numpy as jnp

    dd, ii = jax.lax.map(per_block, jnp.arange(n_blocks, dtype=jnp.int32))
    d_all = jnp.moveaxis(dd, 0, 1).reshape(nq, -1)
    i_all = jnp.moveaxis(ii, 0, 1).reshape(nq, -1)
    neg, pos = jax.lax.top_k(-d_all, k)
    return -neg, jnp.take_along_axis(i_all, pos, axis=-1)


@functools.lru_cache(maxsize=None)
def _knn_fn(k: int, exclude_zero: bool, block_rows: int, q_chunk: int):
    """The jitted search, by the direct form."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def knn(corpus, q, self_ids):
        # corpus (C, d), C % block_rows == 0; q (nq, d), nq % q_chunk == 0
        rows, d = corpus.shape
        nq = q.shape[0]

        def block(b):
            lo = b * block_rows
            return (jax.lax.dynamic_slice_in_dim(corpus, lo, block_rows),
                    lo + jnp.arange(block_rows, dtype=jnp.int32))

        def direct(b):
            blk, ids = block(b)

            def per_chunk(args):  # q_chunk rows at a time bound the diffs
                qc, sc = args
                diff = blk[None, :, :] - qc[:, None, :]
                return _smallest(jnp.sum(diff * diff, axis=-1), ids, sc, k,
                                 exclude_zero)

            dd, ii = jax.lax.map(
                per_chunk, (q.reshape(nq // q_chunk, q_chunk, d),
                            self_ids.reshape(nq // q_chunk, q_chunk)))
            return dd.reshape(nq, k), ii.reshape(nq, k)

        return _over_blocks(direct, rows // block_rows, nq, k)

    return knn


def exact_knn(corpus, queries, k: int, self_ids=None,
              exclude_zero: bool = True, block_rows: int = 16384,
              q_chunk: int = 8):
    """((nq, k) squared distances ascending, (nq, k) int32 ids), numpy:
    the plain reference. ``corpus`` is a (C, d) float32 device array (or
    anything ``jnp.asarray`` takes), ``queries`` a host (nq, d) array."""
    import jax.numpy as jnp

    corpus = jnp.asarray(corpus, dtype=jnp.float32)
    q = np.asarray(queries, dtype=np.float32)
    nq = q.shape[0]
    pad = (-nq) % q_chunk
    sid = (np.full(nq, -1, np.int32) if self_ids is None
           else np.asarray(self_ids, np.int32))
    if pad:
        q = np.concatenate([q, np.zeros((pad, q.shape[1]), np.float32)])
        sid = np.concatenate([sid, np.full(pad, -1, np.int32)])
    rows = corpus.shape[0]
    if rows % block_rows:
        block_rows = int(np.gcd(rows, block_rows))
    knn = _knn_fn(int(k), bool(exclude_zero), int(block_rows), q_chunk)
    d, i = knn(corpus, jnp.asarray(q), jnp.asarray(sid))
    return np.asarray(d)[:nq], np.asarray(i)[:nq]
