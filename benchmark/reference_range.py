"""The plain reference of the range-search cells: EVERY corpus row at a
squared L2 distance strictly under the radius (``<``, as FAISS
``range_search`` under L2 has it), for each query row, by the direct form
``sum((q - c)**2)`` in float32 over corpus BLOCKS that a callable hands
over one at a time — ``reference_u8.py``'s streaming with lists of no
fixed length where that file keeps k. No matrix multiplication, so no
matmul precision mode can touch it; it imports nothing of the program and
never reads the index.

Exactness, stated and checked. On whole-number rows in [0, 255] every term
``(q - c)**2`` is a whole number up to 255**2 and every partial sum of a
row's d terms a whole number up to d x 255**2: under 2**24 — where float32
holds every whole number — for d <= 258 (256 x 255**2 = 16 646 400 <
16 777 216). :func:`check_exact` says so before anything runs and REFUSES
a wider row (``ValueError``): a sum that could pass 2**24 would be rounded
to even, a distance AT the radius could read as under it, and this file
would no longer be a reference — it would want an integer accumulator.
Beyond the argument, every returned pair's distance is made again on the
host in int64 from the rows themselves and must EQUAL the float32 value
(``AssertionError`` otherwise).

Semantics as the configuration states them: squared L2, strictly under the
radius, each row's results ascending by distance, ties by the lower id,
exact-zero distances left out where ``exclude_zero`` is set. Results in
the suite's range format: ``lims`` (nq + 1 offsets), flat ``dists``, flat
``ids``.

The form is chosen for the device it runs on: a block is widened and
turned once so that its rows lie along the lanes, the differences are
squared and summed down the other axis. Of a block only the columns that
ANY query row holds within its radius leave the device (their distances
and their rows), a number the host learns first; every hit of a block is
kept, in host lists.
"""

from __future__ import annotations

import functools

import numpy as np

BYTE_MAX = 255


def check_exact(dim: int) -> None:
    """Refuse a width at which a float32 sum of ``dim`` squared byte
    differences could pass 2**24."""
    if dim * BYTE_MAX ** 2 >= 2 ** 24:
        raise ValueError(
            f"a row of {dim} bytes can lie {dim * BYTE_MAX ** 2} from "
            "another: past 2**24 a float32 sum rounds and this reference "
            "would not be exact (it would want an integer accumulator)")


@functools.lru_cache(maxsize=None)
def _distances(q_chunk: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def distances(blk, q, radius):
        # blk (B, d) whole numbers; q (nq, d) float32; radius (nq,)
        nq, d = q.shape
        turned = blk.astype(jnp.float32).T  # (d, B)

        def per_chunk(qc):  # bounds the differences' size
            diff = turned[None, :, :] - qc[:, :, None]
            return jnp.sum(diff * diff, axis=1)

        d2 = jax.lax.map(
            per_chunk, q.reshape(nq // q_chunk, q_chunk, d)).reshape(nq, -1)
        held = jnp.any(d2 < radius[:, None], axis=0)
        return d2, held, jnp.sum(held)

    return distances


@functools.lru_cache(maxsize=None)
def _columns(size: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def columns(d2, held, blk):
        at = jnp.nonzero(held, size=size, fill_value=held.shape[0])[0]
        safe = jnp.minimum(at, held.shape[0] - 1)
        return at, d2[:, safe], blk[safe]

    return columns


def range_search_blocks(block_of, block_rows, queries, radius,
                        exclude_zero: bool = True, q_chunk: int = 4):
    """``(lims (nq + 1,) int64, dists (n,) float32, ids (n,) int64)``,
    numpy. ``block_of(b)`` hands over block ``b`` of the corpus, a
    (block_rows[b], d) array of whole numbers in [0, 255] (on the device or
    the host), whose first row is corpus row ``sum(block_rows[:b])``;
    ``queries`` is a host (nq, d) array of such rows; ``radius`` one number
    or one a query row."""
    import jax.numpy as jnp

    q = np.asarray(queries, dtype=np.float32)
    nq, dim = q.shape
    check_exact(dim)
    radius = np.broadcast_to(np.asarray(radius, np.float32), (nq,))
    pad = (-nq) % q_chunk
    qp = np.concatenate([q, np.zeros((pad, dim), np.float32)]) if pad else q
    rp = np.concatenate([radius, np.zeros(pad, np.float32)])
    qd, rd = jnp.asarray(qp), jnp.asarray(rp)
    q_int = q.astype(np.int64)
    hits: list = [[] for _ in range(nq)]  # (dists, ids) a block, a row
    lo = 0
    for b, rows in enumerate(block_rows):
        blk = block_of(b)
        if blk.shape[0] != rows:
            raise ValueError(f"block {b} holds {blk.shape[0]} rows, not "
                             f"{rows}")
        d2, held, n_held = _distances(q_chunk)(blk, qd, rd)
        n_held = int(n_held)
        if n_held:
            size = 1 << max(6, (n_held - 1).bit_length())
            at, d_cols, x_cols = map(
                np.asarray, _columns(size)(d2, held, blk))
            at, d_cols, x_cols = (
                at[:n_held], d_cols[:, :n_held], x_cols[:n_held])
            within = d_cols[:nq] < radius[:, None]
            if exclude_zero:
                within &= d_cols[:nq] > 0
            for r in np.nonzero(within.any(axis=1))[0]:
                cols = np.nonzero(within[r])[0]
                d = d_cols[r, cols]
                exact = ((x_cols[cols].astype(np.int64) - q_int[r]) ** 2
                         ).sum(axis=1)
                assert (exact == d.astype(np.int64)).all() and (
                    d == np.rint(d)).all(), "a float32 sum was not exact"
                hits[r].append((d, lo + at[cols].astype(np.int64)))
        lo += rows
    lims = np.zeros(nq + 1, np.int64)
    all_d, all_i = [], []
    for r, parts in enumerate(hits):
        if parts:
            d = np.concatenate([p[0] for p in parts])
            i = np.concatenate([p[1] for p in parts])
            order = np.lexsort((i, d))  # by distance, ties by the lower id
            all_d.append(d[order])
            all_i.append(i[order])
        lims[r + 1] = lims[r] + sum(len(p[0]) for p in parts)
    return (lims,
            np.concatenate(all_d) if all_d else np.zeros(0, np.float32),
            np.concatenate(all_i) if all_i else np.zeros(0, np.int64))
