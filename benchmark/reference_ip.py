"""The plain reference of the inner-product cells: exact maximum inner
product search, ``s = sum_i q_i c_i`` in float32 by the direct form over
corpus blocks — every product written out and summed — with no matrix
multiplication, so no matmul precision mode can touch it. It imports
nothing of the program.

Like ``reference_cosine.py`` and unlike ``reference.py`` on whole-number
data this one rounds: a score here is a 200-term float32 sum of fractional
products of either sign, off from the real number by up to a few 1e-7 of
``sum_i |q_i c_i|`` (measured against float64 in
``benchmark/tests/test_ip_cell.py``: under 4e-7 of the score at the cell's
law, whose largest scores are a third or more of ``|q| |c|``). That band
is part of what the configuration's ``dist_rel_err_max`` was measured from
(PERF.md §4): the limit parts the program as configured from the same
program one precision lower, and the reference's own rounding lies well
inside it.

Semantics as the configuration states them: the k rows of LARGEST inner
product, returned in the engine's one ordering — the NEGATED score
``-<q, c>``, ascending (pgvector's ``<#>`` returns the same, for the same
reason) — ties by the lower id, no row left out (a zero score is
orthogonality, not identity: there is no ``exclude_zero`` under a score),
nothing centred, nothing normalised, nothing clamped.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _knn_fn(k: int, block_rows: int, q_chunk: int):
    """The jitted search, by the direct form."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def knn(corpus, q):
        # corpus (C, d), C % block_rows == 0; q (nq, d), nq % q_chunk == 0
        rows, d = corpus.shape
        nq = q.shape[0]

        def per_block(b):
            lo = b * block_rows
            blk = jax.lax.dynamic_slice_in_dim(corpus, lo, block_rows)
            ids = lo + jnp.arange(block_rows, dtype=jnp.int32)

            def per_chunk(qc):  # q_chunk rows at a time bound the products
                score = jnp.sum(qc[:, None, :] * blk[None, :, :], axis=-1)
                # top_k keeps the lower position among equals: the lower id
                top, pos = jax.lax.top_k(score, k)
                return top, ids[pos]

            ss, ii = jax.lax.map(per_chunk, q.reshape(-1, q_chunk, d))
            return ss.reshape(nq, k), ii.reshape(nq, k)

        ss, ii = jax.lax.map(
            per_block, jnp.arange(rows // block_rows, dtype=jnp.int32))
        # blocks in id order, each block's survivors descending: position
        # order among equal scores is id order again
        s_all = jnp.moveaxis(ss, 0, 1).reshape(nq, -1)
        i_all = jnp.moveaxis(ii, 0, 1).reshape(nq, -1)
        top, pos = jax.lax.top_k(s_all, k)
        return -top, jnp.take_along_axis(i_all, pos, axis=-1)

    return knn


def exact_knn_ip(corpus, queries, k: int, block_rows: int = 8192,
                 q_chunk: int = 8):
    """((nq, k) negated inner products ascending, (nq, k) int32 ids),
    numpy: the plain reference. ``corpus`` is a (C, d) float32 device array
    (or anything ``jnp.asarray`` takes), ``queries`` a host (nq, d) array."""
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
