"""Embedding-like data: a row is ``s * (c + sigma * g)``, ``c`` one of the
unit-length class centres, ``g`` standard normal noise and ``s`` a scale of
the row's own, drawn log-uniformly from ``[scale_min, scale_max]``. Held as
float32; every element is fractional.

With ``sigma**2 * dim = 0.25`` two rows of one class lie at cosine distance
~0.2 and rows of two classes at ~1, as neighbouring and unrelated text
embeddings do. Real embedding rows are unit length; these are not, on
purpose: with unit rows cosine distance is ``1 - q.c`` and a program that
skipped a normalisation would still answer right. The scale changes no
cosine distance and every dot product.

The centres are made on the host with numpy (they are small, and the
jax-free load generator needs the same ones for its query rows); the rows
are made on the device in chunks, so that no transient passes a few times
``chunk_rows * dim * 4`` bytes and nothing crosses the host.
"""

from __future__ import annotations

import numpy as np


def centres(seed: int, spec: dict, dim: int) -> np.ndarray:
    """(centres, dim) float32 unit-length class centres from the seed, on
    the host."""
    rng = np.random.default_rng([int(seed), 0xC0])
    cen = rng.standard_normal((int(spec["centres"]), dim))
    cen /= np.linalg.norm(cen, axis=1, keepdims=True)
    return cen.astype(np.float32)


def host_rows(rng: np.random.Generator, n: int, cen: np.ndarray,
              spec: dict) -> np.ndarray:
    """``n`` fresh rows of the same law as the corpus's, on the host: the
    query rows of a serving mix."""
    which = rng.integers(0, cen.shape[0], size=n)
    x = cen[which] + rng.standard_normal((n, cen.shape[1])) * float(
        spec["sigma"])
    scale = np.exp(rng.uniform(np.log(float(spec["scale_min"])),
                               np.log(float(spec["scale_max"])), size=n))
    return (x * scale[:, None]).astype(np.float32)


def device_corpus(seed: int, rows: int, dim: int, spec: dict,
                  chunk_rows: int = 8192):
    """(rows, dim) float32 corpus on the default device, one jitted call."""
    import jax
    import jax.numpy as jnp

    if rows % chunk_rows:
        chunk_rows = int(np.gcd(rows, chunk_rows))
    cen = jnp.asarray(centres(seed, spec, dim))
    sigma = float(spec["sigma"])
    log_lo = float(np.log(float(spec["scale_min"])))
    log_hi = float(np.log(float(spec["scale_max"])))
    # --seed may pass 2**31: fold it in as two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31
    )

    @jax.jit
    def make(key, cen):
        def body(i, buf):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
            which = jax.random.randint(k1, (chunk_rows,), 0, cen.shape[0])
            x = cen[which] + jax.random.normal(
                k2, (chunk_rows, dim), jnp.float32) * sigma
            scale = jnp.exp(jax.random.uniform(
                k3, (chunk_rows, 1), jnp.float32, log_lo, log_hi))
            return jax.lax.dynamic_update_slice(
                buf, x * scale, (i * chunk_rows, 0))

        return jax.lax.fori_loop(
            0, rows // chunk_rows, body,
            jnp.zeros((rows, dim), jnp.float32),
        )

    return make(key, cen)
