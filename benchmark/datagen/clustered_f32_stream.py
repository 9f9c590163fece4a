"""Web-search-embedding-like rows for a corpus that changes while it is
served: fractional float32, not unit length, in an id space ORDERED BY
CLUSTER, base rows and the rows a runbook will insert alike.

The law. ``clusters`` runbook clusters (the source orders its rows by
k-means cluster, so that every insert and delete range is one cluster's).
The id space is cut into blocks of ``block_rows`` consecutive ids; a block
has a sub-centre of its own, ``cluster centre + sub_sigma * g``, and a row
is ``its block's sub-centre + sigma * g`` (``g`` standard normal): rows of
one block are each other's neighbours (squared distance about ``2 d
sigma**2``), other blocks of the cluster lie ``sub_sigma / sigma`` times
farther, other clusters farther still. Base block ``b`` belongs to cluster
``b // (base blocks / clusters)``; a block past the base rows belongs to the
cluster the runbook gives it (``cluster_of_block``).

The LAW — the cluster centres and every block's sub-centre — follows the
configuration's ``law_seed`` where it names one, so that every ``--seed``
meets the same geometry and with it the same work (how many of a tile's
chunks lie under a row's bound goes with where the clusters lie: six seeds
with a geometry each read 4 % apart, PERF.md §6, PR 53); the ROWS — base
rows, inserted rows, query rows — follow ``--seed``. A configuration
without the key draws the law from ``--seed`` too.

Sub-centres are made on the host with numpy (they are small, and the
jax-free driver needs them for its query rows); base rows are made on the
device block by block, so that nothing corpus-sized crosses the host; the
rows a runbook inserts are made on the host, block by block, by the driver
(to send them) and by the launcher (for the reference) from the same call.
"""

from __future__ import annotations

import numpy as np


def law_seed(seed: int, spec: dict) -> int:
    """The seed of the law: the configuration's, else the run's."""
    return int(spec.get("law_seed", seed))


def cluster_centres(seed: int, spec: dict, dim: int) -> np.ndarray:
    rng = np.random.default_rng([law_seed(seed, spec), 0xC0])
    return (rng.standard_normal((int(spec["clusters"]), dim))
            * float(spec["cluster_sigma"])).astype(np.float32)


def sub_centres(seed: int, spec: dict, dim: int,
                cluster_of_block: np.ndarray) -> np.ndarray:
    """(blocks, dim) float32: every block's sub-centre, base blocks and
    the runbook's alike, in block order."""
    cen = cluster_centres(seed, spec, dim)
    rng = np.random.default_rng([law_seed(seed, spec), 0xB1])
    g = rng.standard_normal((len(cluster_of_block), dim))
    return (cen[np.asarray(cluster_of_block)]
            + g * float(spec["sub_sigma"])).astype(np.float32)


def base_clusters(rows: int, spec: dict) -> np.ndarray:
    """Cluster of each base block: equal shares, in cluster order."""
    block = int(spec["block_rows"])
    clusters = int(spec["clusters"])
    if rows % (block * clusters):
        raise ValueError(f"{rows} base rows are not a whole number of "
                         f"{block}-row blocks in each of {clusters} clusters")
    return np.repeat(np.arange(clusters), rows // block // clusters)


def host_block(seed: int, spec: dict, block: int,
               sub_centre: np.ndarray) -> np.ndarray:
    """The (block_rows, dim) float32 rows of one block, on the host."""
    rng = np.random.default_rng([int(seed), 0x207, int(block)])
    g = rng.standard_normal((int(spec["block_rows"]), sub_centre.shape[0]))
    return (sub_centre[None, :] + g * float(spec["sigma"])).astype(np.float32)


def query_rows(seed: int, spec: dict, targets: np.ndarray,
               subs: np.ndarray) -> np.ndarray:
    """One fresh row of the corpus's law near each target block's
    sub-centre: the query pool of a serving mix."""
    rng = np.random.default_rng([int(seed), 0x71])
    g = rng.standard_normal((len(targets), subs.shape[1]))
    return (subs[np.asarray(targets)] + g * float(spec["sigma"])
            ).astype(np.float32)


def device_corpus(seed: int, rows: int, dim: int, spec: dict,
                  subs: np.ndarray):
    """(rows, dim) float32 base corpus on the default device, one jitted
    call: block ``b`` of ``block_rows`` rows around ``subs[b]``."""
    import jax
    import jax.numpy as jnp

    block = int(spec["block_rows"])
    sigma = float(spec["sigma"])
    subs_d = jnp.asarray(subs[: rows // block])
    # --seed may pass 2**31: fold it in as two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31
    )

    @jax.jit
    def make(key, subs_d):
        def body(b, buf):
            x = subs_d[b][None, :] + jax.random.normal(
                jax.random.fold_in(key, b), (block, dim), jnp.float32) * sigma
            return jax.lax.dynamic_update_slice(buf, x, (b * block, 0))

        return jax.lax.fori_loop(
            0, rows // block, body, jnp.zeros((rows, dim), jnp.float32))

    return make(key, subs_d)
