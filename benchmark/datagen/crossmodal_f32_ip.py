"""Cross-modal retrieval data for a score that is not a distance: a corpus
of "image" rows and queries of "text" rows drawn from ANOTHER law, ranked
by inner product. Fractional float32 throughout; nothing is centred or
unit length, on purpose.

The corpus law. ``centres`` class directions ``a_j`` (unit length), one
common offset ``o`` (length ``offset``: the corpus mean is about
``E[s] * o``, visibly not zero, so a program that centres the queries by it
ranks differently) and a scale of the row's own::

    x = s * (a_j + o + sigma * g)         g standard normal
    s log-uniform in [scale_min, scale_max], independent of j

With ``sigma**2 * dim = 0.25`` rows of one class lie at cosine ~0.85 of
each other. The scale (a factor ``scale_max / scale_min`` of 5 in the row
norms, independent of everything a query knows) changes no cosine and
every inner product: the largest inner products of a query are the
LONGEST well-aligned rows, its cosine neighbours the best aligned of any
length, its L2 neighbours the ones whose length is near ``0.4 |q|`` — three
different answers for nearly every query (``benchmark/IP.md`` has the
measured shares), which is what lets a program that normalises, measures
L2, centres or clamps be seen.

The query law is another one::

    q = t * (w1 a_j1 + w2 a_j2 + w3 a_j3 + topic * b_l + shift * p
             + q_sigma * g)

a mix of THREE corpus class directions (weights ``mix``: its centre is no
class's), one of ``topics`` text-only directions ``b_l`` and a common
text offset ``p`` — unit directions the corpus has no component along but
noise — a wider spread (``q_sigma**2 * dim = 0.5``) and another norm law,
``t`` log-normal (``ln t`` normal with deviation ``norm_sigma``) where the
corpus's is log-uniform. The first class carries ``mix[0] = 0.6`` of a
query of length about 1.2, so the largest inner products of every query
stay above 0.3 |q| |c| (measured: ``benchmark/tests/test_ip_cell.py``), and
an error relative to a score means something.

``centres`` are made on the host with numpy (they are small, and the
jax-free load generator needs the same ones for its query rows); the
corpus rows are made on the device in chunks, so that no transient passes a
few times ``chunk_rows * dim * 4`` bytes and nothing crosses the host.
"""

from __future__ import annotations

import numpy as np


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def centres(seed: int, spec: dict, dim: int) -> np.ndarray:
    """(centres + 1, dim) float32 from the seed, on the host: the unit
    class directions, then the corpus offset ``o`` as the last row."""
    rng = np.random.default_rng([int(seed), 0xC0])
    cen = _unit(rng.standard_normal((int(spec["centres"]), dim)))
    off = _unit(rng.standard_normal((1, dim))) * float(spec["offset"])
    return np.concatenate([cen, off]).astype(np.float32)


def host_corpus_rows(rng: np.random.Generator, n: int, cen: np.ndarray,
                     spec: dict) -> np.ndarray:
    """``n`` rows of the CORPUS's law on the host (tests; the cells' corpus
    is :func:`device_corpus`'s)."""
    classes, off = cen[:-1], cen[-1]
    which = rng.integers(0, classes.shape[0], size=n)
    x = classes[which] + off + rng.standard_normal(
        (n, cen.shape[1])) * float(spec["sigma"])
    scale = np.exp(rng.uniform(np.log(float(spec["scale_min"])),
                               np.log(float(spec["scale_max"])), size=n))
    return (x * scale[:, None]).astype(np.float32)


def host_rows(rng: np.random.Generator, n: int, cen: np.ndarray,
              spec: dict) -> np.ndarray:
    """``n`` fresh rows of the QUERY law, on the host: the query rows of a
    serving mix. Not the corpus's law: see the module's account."""
    q = spec["queries"]
    classes, dim = cen[:-1], cen.shape[1]
    # the text side's own directions come first from the stream, so that
    # they are the same for any ``n``
    topics = _unit(rng.standard_normal((int(q["topics"]), dim)))
    shift = _unit(rng.standard_normal(dim))
    mix = np.asarray(q["mix"], dtype=np.float64)
    which = rng.integers(0, classes.shape[0], size=(n, len(mix)))
    x = (classes[which] * mix[None, :, None]).sum(axis=1)
    x = x + float(q["topic"]) * topics[rng.integers(0, len(topics), size=n)]
    x = x + float(q["shift"]) * shift
    x = x + rng.standard_normal((n, dim)) * float(q["q_sigma"])
    t = np.exp(rng.standard_normal(n) * float(q["norm_sigma"]))
    return (x * t[:, None]).astype(np.float32)


def device_corpus(seed: int, rows: int, dim: int, spec: dict,
                  chunk_rows: int = 8192):
    """(rows, dim) float32 corpus on the default device, one jitted call."""
    import jax
    import jax.numpy as jnp

    if rows % chunk_rows:
        chunk_rows = int(np.gcd(rows, chunk_rows))
    cen = centres(seed, spec, dim)
    classes, off = jnp.asarray(cen[:-1]), jnp.asarray(cen[-1])
    sigma = float(spec["sigma"])
    log_lo = float(np.log(float(spec["scale_min"])))
    log_hi = float(np.log(float(spec["scale_max"])))
    # --seed may pass 2**31: fold it in as two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31
    )

    @jax.jit
    def make(key, classes, off):
        def body(i, buf):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
            which = jax.random.randint(k1, (chunk_rows,), 0, classes.shape[0])
            x = classes[which] + off + jax.random.normal(
                k2, (chunk_rows, dim), jnp.float32) * sigma
            scale = jnp.exp(jax.random.uniform(
                k3, (chunk_rows, 1), jnp.float32, log_lo, log_hi))
            return jax.lax.dynamic_update_slice(
                buf, x * scale, (i * chunk_rows, 0))

        return jax.lax.fori_loop(
            0, rows // chunk_rows, body,
            jnp.zeros((rows, dim), jnp.float32),
        )

    return make(key, classes, off)
