"""``clustered_u8``'s law made a BLOCK at a time, as bytes: rows are a class
centre plus Gaussian noise, rounded to whole numbers and clipped to
[0, 255], held as ``uint8`` — the published element type of the byte-valued
sources. ``device_block(seed, block, rows, dim, spec)`` is a function of
(seed, block) alone, so a corpus that no host and no second device buffer
can hold is made twice from the same numbers — once for the streamed
reference (``reference_u8.py``), once for the build in blocks — and never
exists whole outside the index's stack. The centres are
``clustered_u8.centres`` (the jax-free load generator needs the same ones
for its query rows), the query rows ``clustered_u8.host_rows``: whole
numbers, so the one-pass branch is the one timed.

The law, not the bits, is ``clustered_u8.device_corpus``'s: that function
folds a chunk index of its own chunking into the key; here the block index
is folded in, and a block's rows are one draw.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.datagen.clustered_u8 import centres, host_rows  # noqa: F401


@functools.lru_cache(maxsize=4)
def _device_centres(seed: int, spec_items: tuple, dim: int):
    import jax.numpy as jnp

    return jnp.asarray(centres(seed, dict(spec_items), dim))


@functools.lru_cache(maxsize=None)
def _make(rows: int, dim: int, sigma: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, block, cen):
        k1, k2 = jax.random.split(jax.random.fold_in(key, block))
        which = jax.random.randint(k1, (rows,), 0, cen.shape[0])
        x = cen[which] + jax.random.normal(
            k2, (rows, dim), jnp.float32) * sigma
        return jnp.clip(jnp.rint(x), 0.0, 255.0).astype(jnp.uint8)

    return make


def device_block(seed: int, block: int, rows: int, dim: int, spec: dict):
    """(rows, dim) uint8 on the default device: block ``block`` of the
    corpus the seed stands for."""
    import jax

    law = tuple(sorted((k, v) for k, v in spec.items()
                       if k in ("centres", "centre_scale")))
    cen = _device_centres(int(seed), law, int(dim))
    # --seed may pass 2**31: fold it in as two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)
    return _make(int(rows), int(dim), float(spec["sigma"]))(
        key, np.int32(block), cen)


def block_rows_of(rows: int, spec: dict) -> list:
    """The rows of each block of a ``rows``-row corpus: ``block_rows`` of
    the specification, and what is left in the last."""
    step = int(spec["block_rows"])
    return [min(step, rows - lo) for lo in range(0, rows, step)]
