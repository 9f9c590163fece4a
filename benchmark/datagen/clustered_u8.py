"""Pixel- or descriptor-like data: rows are a class centre plus Gaussian
noise, rounded to whole numbers and clipped to [0, 255], held as float32.

With ``centres=10, centre_scale=255, sigma=25`` this is
``data/synthetic.make_mnist_like``'s shape, with ``256 / 140 / 30``
``make_sift_like``'s. The centres are made on the host with numpy (they are
tiny, and the jax-free load generator needs the same ones for its query
rows); the rows are made on the device in chunks, so that no transient
passes ``chunk_rows * dim * 4`` bytes and nothing crosses the host.
"""

from __future__ import annotations

import numpy as np


def centres(seed: int, spec: dict, dim: int) -> np.ndarray:
    """(centres, dim) float32 class centres, on the host: from the seed, or
    from the configuration's ``law_seed`` where it names one — the LAW is
    then the configuration's and only the ROWS the run's, for a cell whose
    work goes with the geometry (the clustered cell's work items)."""
    rng = np.random.default_rng([int(spec.get("law_seed", seed)), 0xC0])
    return (rng.random((int(spec["centres"]), dim))
            * float(spec["centre_scale"])).astype(np.float32)


def host_rows(rng: np.random.Generator, n: int, cen: np.ndarray,
              spec: dict) -> np.ndarray:
    """``n`` fresh rows of the same shape as the corpus's, on the host:
    the query rows of a serving mix. Under a ``law_seed`` the CLASS of each
    row is the configuration's too (which classes a batch holds decides how
    many lists it touches) and the noise about its centre the run's."""
    law = spec.get("law_seed")
    pick = rng if law is None else np.random.default_rng([int(law), 0x71])
    which = pick.integers(0, cen.shape[0], size=n)
    x = cen[which] + rng.standard_normal((n, cen.shape[1])) * float(
        spec["sigma"])
    return np.clip(np.rint(x), 0.0, 255.0).astype(np.float32)


def device_corpus(seed: int, rows: int, dim: int, spec: dict,
                  chunk_rows: int = 65536):
    """(rows, dim) float32 corpus on the default device, one jitted call."""
    import jax
    import jax.numpy as jnp

    if rows % chunk_rows:
        chunk_rows = int(np.gcd(rows, chunk_rows))
    cen = jnp.asarray(centres(seed, spec, dim))
    sigma = float(spec["sigma"])
    # --seed may pass 2**31: fold it in as two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31
    )

    @jax.jit
    def make(key, cen):
        def body(i, buf):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            which = jax.random.randint(k1, (chunk_rows,), 0, cen.shape[0])
            x = cen[which] + jax.random.normal(
                k2, (chunk_rows, dim), jnp.float32) * sigma
            x = jnp.clip(jnp.rint(x), 0.0, 255.0)
            return jax.lax.dynamic_update_slice(buf, x, (i * chunk_rows, 0))

        return jax.lax.fori_loop(
            0, rows // chunk_rows, body,
            jnp.zeros((rows, dim), jnp.float32),
        )

    return make(key, cen)
