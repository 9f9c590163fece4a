"""Descriptor-like rows that each carry a bag of tags, and query rows that
each carry one or two: the filtered track's shape (``configs/
yfcc10m-192-l2-filter.json``).

Rows are ``clustered_u8``'s: a class centre plus Gaussian noise, rounded
and clipped to [0, 255], float32. A row's centre is drawn on the host (the
bags and the jax-free driver need it), its noise on the device.

The bags' law (``spec``, every number under the configuration's
``assumed``): a row makes ``bag_draws`` draws, each kept with probability
``draw_keep`` (the first always), duplicates dropped. A draw comes with
probability ``own_share`` from the row's centre's own ``own_tags`` tags
(a camera model or a country is not spread evenly over descriptors), else
from the whole vocabulary by the shifted log-uniform law ``rank =
floor(shift * ((V + shift) / shift) ** u) - shift`` for uniform ``u``
(Zipf-Mandelbrot with exponent 1: P(rank r) ~ 1 / (r + shift)), rank 0 the
most frequent; an own tag is the lower of two uniform picks among the
centre's. A tag's id IS its rank in the vocabulary's law. Everything
is drawn block by block (``block_rows``) from a generator of the block's
own, so that any number of threads makes the same bags.

**What ``--seed`` makes and what it does not.** The vectors follow the
seed: the centres, every row's noise, every query's noise. The bags, each
row's centre and the queries' tags follow ``law_seed``, a number of the
configuration: which tags lie on which rows is the deployment's, as a
catalogue's attributes are, and with it the WORK of a run — how many rows
each query's tags match, so which regime it takes and how many candidates
it gathers — is the same whatever the seed (``loadgen.py``'s own rule: the
seed must not change the work), while the answers differ with the vectors.

Queries (``query_pool``): the vector is a fresh point around the centre of
a corpus row drawn at random, the tags one (share ``one_tag_share``) or
two of THAT row's bag — the first its most popular tag with probability
``popular_share`` (a year, a country, a camera maker are what callers
filter by most), else any — so tags arrive by popularity and every query
matches at least one row (fewer than k where its tags are rare enough:
such a query is answered with what matches and then empty slots). No jax
on this side.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np


def centres(seed: int, spec: dict, dim: int) -> np.ndarray:
    """(centres, dim) float32 class centres from the seed, on the host."""
    rng = np.random.default_rng([int(seed), 0xC0])
    return (rng.random((int(spec["centres"]), dim))
            * float(spec["centre_scale"])).astype(np.float32)


def own_tag_sets(spec: dict) -> np.ndarray:
    """(centres, own_tags) int32: each centre's own tags, drawn from the
    vocabulary's ranks ``own_from`` and up, without regard to the others'."""
    rng = np.random.default_rng([int(spec["law_seed"]), 0x7A6])
    return rng.integers(
        int(spec["own_from"]), int(spec["vocabulary"]),
        size=(int(spec["centres"]), int(spec["own_tags"])), dtype=np.int32)


def _block(spec: dict, block: int, rows: int, own: np.ndarray):
    """One block's (centre of each row, sorted bags as a (rows, draws)
    matrix with the vocabulary's size where a draw was dropped)."""
    rng = np.random.default_rng([int(spec["law_seed"]), 0xBA6, int(block)])
    vocab, draws = int(spec["vocabulary"]), int(spec["bag_draws"])
    shift = np.float32(spec["shift"])
    which = rng.integers(0, own.shape[0], size=rows, dtype=np.int32)
    u = rng.random((rows, draws), dtype=np.float32)
    tags = np.minimum(
        (shift * np.exp(u * np.float32(np.log((vocab + shift) / shift)))
         - shift).astype(np.int32), vocab - 1)
    # a byte a draw decides whose law it follows and another whether it is
    # kept; an own tag is the lower of two uniform picks of the centre's
    coin = rng.integers(0, 256, size=(2, rows, draws), dtype=np.uint8)
    pick = rng.integers(0, own.shape[1], size=(2, rows, draws),
                        dtype=np.uint8).min(axis=0)
    from_own = coin[0] < np.uint8(round(256 * float(spec["own_share"])))
    tags = np.where(from_own, own[which[:, None], pick], tags)
    drop = coin[1] >= np.uint8(round(256 * float(spec["draw_keep"])))
    drop[:, 0] = False
    tags[drop] = vocab
    tags.sort(axis=1)
    tags[:, 1:][tags[:, 1:] == tags[:, :-1]] = vocab
    return which, tags


def bags(rows: int, spec: dict, threads: int = 8):
    """``(centre of each row (rows,) int32, indptr (rows + 1,) int64,
    indices int32, bag matrix (rows, bag_draws) int32)``: every row's
    centre, the bags as a CSR (a bag's tag ids ascending) and the same
    bags as a matrix, a row's tags first and the vocabulary's size after
    them."""
    block = int(spec["block_rows"])
    own = own_tag_sets(spec)
    vocab = int(spec["vocabulary"])

    def one(b):
        which, tags = _block(spec, b, min(block, rows - b * block), own)
        tags.sort(axis=1)  # the dropped duplicates go last
        keep = tags < vocab
        return which, keep.sum(axis=1), tags[keep], tags

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(one, range(-(-rows // block))))
    indptr = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(np.concatenate([p[1] for p in parts]), out=indptr[1:])
    return (np.concatenate([p[0] for p in parts]), indptr,
            np.concatenate([p[2] for p in parts]),
            np.concatenate([p[3] for p in parts]))


def host_rows(rng: np.random.Generator, which: np.ndarray, cen: np.ndarray,
              spec: dict) -> np.ndarray:
    """A fresh row around each of the centres ``which``, on the host."""
    x = cen[which] + rng.standard_normal(
        (len(which), cen.shape[1])) * float(spec["sigma"])
    return np.clip(np.rint(x), 0.0, 255.0).astype(np.float32)


def match_counts(indptr, indices, filters: np.ndarray) -> np.ndarray:
    """Rows whose bag holds every tag of each filter (n, 2), -1 none,
    straight from the CSR. Not on any run's path: what the configuration
    file's selectivity quantiles were read with (``benchmark/FILTER.md``)."""
    wanted = np.unique(filters[filters >= 0])
    sel = np.isin(indices, wanted)
    row = np.repeat(np.arange(len(indptr) - 1, dtype=np.int64),
                    np.diff(indptr))[sel]
    tag = indices[sel]
    order = np.argsort(tag, kind="stable")
    row, tag = row[order], tag[order]
    lo = np.searchsorted(tag, wanted)
    hi = np.searchsorted(tag, wanted, side="right")
    where = {int(t): (a, b) for t, a, b in zip(wanted, lo, hi)}
    out = np.zeros(len(filters), dtype=np.int64)
    for i, (a, b) in enumerate(filters):
        ra = row[slice(*where[int(a)])]
        out[i] = len(ra) if b < 0 else np.intersect1d(
            ra, row[slice(*where[int(b)])], assume_unique=True).size
    return out


def query_pool(seed: int, n: int, spec: dict, dim: int, which, indptr,
               indices):
    """``((n, dim) float32 vectors, (n, 2) int32 tags, -1 none)``: the
    pool of a serving mix, from the corpus's bags: a corpus row drawn at
    random gives its centre to the vector and one or two tags of its bag
    to the filter."""
    law = np.random.default_rng([int(spec["law_seed"]), 0x71])
    src = law.integers(0, len(which), size=n)
    size = indptr[src + 1] - indptr[src]
    # the first tag: the bag's most popular (its lowest id) with
    # probability popular_share, else any of the bag
    first = np.where(law.random(n) < float(spec["popular_share"]), 0,
                     law.integers(0, size))
    # the second tag: another of the bag, where it has two
    second = (first + 1 + law.integers(0, np.maximum(size - 1, 1))) % size
    two = (law.random(n) >= float(spec["one_tag_share"])) & (size > 1)
    filters = np.stack(
        [indices[indptr[src] + first],
         np.where(two, indices[indptr[src] + second], -1)], axis=1)
    rng = np.random.default_rng([int(seed), 0x71])
    return (host_rows(rng, which[src], centres(seed, spec, dim), spec),
            filters.astype(np.int32))


def device_corpus(seed: int, rows: int, dim: int, spec: dict, which,
                  chunk_rows: int = 65536):
    """(rows, dim) float32 corpus on the default device, one jitted call:
    row i around centre ``which[i]``."""
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
    def make(key, cen, which):
        def body(i, buf):
            w = jax.lax.dynamic_slice_in_dim(which, i * chunk_rows,
                                             chunk_rows)
            x = cen[w] + jax.random.normal(
                jax.random.fold_in(key, i), (chunk_rows, dim),
                jnp.float32) * sigma
            x = jnp.clip(jnp.rint(x), 0.0, 255.0)
            return jax.lax.dynamic_update_slice(buf, x, (i * chunk_rows, 0))

        return jax.lax.fori_loop(
            0, rows // chunk_rows, body,
            jnp.zeros((rows, dim), jnp.float32),
        )

    return make(key, cen, jnp.asarray(which))
