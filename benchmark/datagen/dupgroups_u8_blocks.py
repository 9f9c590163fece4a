"""Byte rows with planted GROUPS OF NEAR-COPIES, a block at a time: what a
copy-detection corpus looks like to range search. A function of (seed,
block), as ``clustered_u8_blocks.py`` is, so a corpus that no host and no
second device buffer can hold is made twice from the same numbers — for the
streamed reference and for the build in blocks — and never exists whole.

The LAW is the configuration's (``law_seed``): the class centres
(``clustered_u8.centres``), and of every block which rows are planted, the
group of each, the group's centre and each member's noise level — made on
the host with numpy (:func:`block_law`; the jax-free load generator needs
the groups' centres for its query rows). The ROWS are the run's
(``--seed``): the background rows' classes and noise and the members'
noise, drawn on the device.

- background rows (95 %): ``clustered_u8``'s law at the configuration's
  width — a class centre plus N(0, ``sigma``) noise, rounded and clipped to
  [0, 255]. At 256 columns and sigma 30 two of them lie ~4.6e5 apart:
  nothing of the background is within the published radius (96 237) of
  anything.
- planted rows (``planted_share``): groups whose sizes follow a power law
  (``P(s) ~ s^-group_exponent`` on ``group_min``..``group_max``), each
  WITHIN one block, its members scattered over the block's rows (hence its
  tiles). A group's centre is a background-shaped point; a member is the
  centre plus whole-number noise of a per-member sigma drawn uniformly in
  ``member_sigma`` = [4, 20]. A query ``sigma_q`` = 6 from the centre sees
  a member at ~256 x (36 + sigma_m^2): under the radius up to sigma_m ~
  18.4, so members fall on both sides of it.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmark.datagen.clustered_u8 import centres, host_rows  # noqa: F401


def block_rows_of(rows: int, spec: dict) -> list:
    """The rows of each block of a ``rows``-row corpus."""
    step = int(spec["block_rows"])
    return [min(step, rows - lo) for lo in range(0, rows, step)]


@functools.lru_cache(maxsize=4)
def _size_law(lo: int, hi: int, exponent: float):
    sizes = np.arange(lo, hi + 1)
    p = sizes.astype(np.float64) ** -exponent
    return sizes, np.cumsum(p / p.sum())


@functools.lru_cache(maxsize=256)
def _block_law(law_seed: int, block: int, rows: int, dim: int,
               spec_items: tuple):
    spec = dict(spec_items)
    rng = np.random.default_rng([int(law_seed), 0xD0, int(block)])
    planted = int(rows * float(spec["planted_share"]))
    sizes, cdf = _size_law(int(spec["group_min"]), int(spec["group_max"]),
                           float(spec["group_exponent"]))
    # more draws than can be needed (every group holds group_min at least)
    draw = sizes[np.searchsorted(
        cdf, rng.random(planted // int(spec["group_min"]) + 1))]
    ends = np.cumsum(draw)
    n_groups = int(np.searchsorted(ends, planted, side="left")) + 1
    group_size = draw[:n_groups].copy()
    group_size[-1] -= ends[n_groups - 1] - planted  # the last is cut to fit
    if group_size[-1] < int(spec["group_min"]) and n_groups > 1:
        group_size[-2] += group_size[-1]  # too small a rest joins a group
        group_size, n_groups = group_size[:-1], n_groups - 1
    at = rng.permutation(rows)[:planted]  # scattered over the block
    group = np.full(rows, -1, np.int32)
    group[at] = np.repeat(np.arange(n_groups, dtype=np.int32), group_size)
    lo, hi = (float(v) for v in spec["member_sigma"])
    sigma = np.zeros(rows, np.float32)
    sigma[at] = rng.uniform(lo, hi, planted).astype(np.float32)
    cen = centres(law_seed, spec, dim)
    group_centre = np.clip(np.rint(
        cen[rng.integers(0, cen.shape[0], n_groups)]
        + rng.standard_normal((n_groups, dim)) * float(spec["sigma"])),
        0.0, 255.0).astype(np.float32)
    return group, sigma, group_centre, group_size.astype(np.int64)


def _law_items(spec: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in spec.items()
        if k in ("centres", "centre_scale", "sigma", "law_seed",
                 "planted_share", "group_min", "group_max",
                 "group_exponent", "member_sigma")))


def block_law(block: int, rows: int, dim: int, spec: dict):
    """``(group (rows,) int32, -1 background; sigma (rows,) float32;
    group_centre (G, dim) float32; group_size (G,))`` of block ``block``:
    the configuration's, whatever ``--seed``. Host, numpy."""
    return _block_law(int(spec["law_seed"]), int(block), int(rows), int(dim),
                      _law_items(spec))


def expected_results(group: np.ndarray, sigma: np.ndarray, n_groups: int,
                     dim: int, radius: float, sigma_q: float) -> np.ndarray:
    """(G,) the results a query ``sigma_q`` from a group's centre expects:
    a member of noise level s lies at (sigma_q^2 + s^2) x chi^2(dim), so it
    is under ``radius`` with the normal tail's chance."""
    planted = group >= 0
    var = sigma_q ** 2 + sigma[planted].astype(np.float64) ** 2
    z = (radius - dim * var) / (math.sqrt(2.0 * dim) * var)
    p = 0.5 * (1.0 + np.vectorize(math.erf)(z / math.sqrt(2.0)))
    return np.bincount(group[planted], weights=p, minlength=n_groups)


@functools.lru_cache(maxsize=None)
def _make(rows: int, dim: int, sigma: float, chunks: int):
    import jax
    import jax.numpy as jnp

    step = rows // chunks

    @jax.jit
    def make(key, block, cen, group, member_sigma, group_centre):
        key = jax.random.fold_in(key, block)

        def chunk(i):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            g = jax.lax.dynamic_slice(group, (i * step,), (step,))
            s = jax.lax.dynamic_slice(member_sigma, (i * step,), (step,))
            which = jax.random.randint(k1, (step,), 0, cen.shape[0])
            planted = g >= 0
            centre = jnp.where(planted[:, None],
                               group_centre[jnp.maximum(g, 0)], cen[which])
            x = centre + jax.random.normal(
                k2, (step, dim), jnp.float32) * jnp.where(
                    planted, s, sigma)[:, None]
            return jnp.clip(jnp.rint(x), 0.0, 255.0).astype(jnp.uint8)

        return jax.lax.map(chunk, jnp.arange(chunks)).reshape(rows, dim)

    return make


def device_block(seed: int, block: int, rows: int, dim: int, spec: dict):
    """(rows, dim) uint8 on the default device: block ``block`` of the
    corpus the seed stands for, under the configuration's law. Made in
    chunks, so its float32 temporaries are a chunk's."""
    import jax
    import jax.numpy as jnp

    group, member_sigma, group_centre, _ = block_law(block, rows, dim, spec)
    cen = jnp.asarray(centres(int(spec["law_seed"]), spec, dim))
    # --seed may pass 2**31: fold it in as two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg"), int(seed) >> 31)
    chunks = next(c for c in (8, 7, 4, 2, 1) if rows % c == 0)
    return _make(int(rows), int(dim), float(spec["sigma"]), chunks)(
        key, np.int32(block), cen, jnp.asarray(group),
        jnp.asarray(member_sigma), jnp.asarray(group_centre))


# ---------------------------------------------------------------------------
# the serving mix's pool of query rows (host, numpy, no jax)

# results a row, by stratum (the traffic names the edges: 1-9, 10-99,
# 100-999, >= 1000): the band of EXPECTED results a group is picked from
# for each, inside the edges, so that the run's own noise leaves a row in
# its stratum
_PICK = ((2.0, 6.5), (16.0, 75.0), (140.0, 760.0), (1150.0, None))


def query_pool(seed: int, rows: int, config: dict, mix: dict):
    """``(pool (rows, dim) float32, stratum (rows,) int8)``: the mix's
    query rows, laid out with a period of ``mix["pool"]["period"]`` rows —
    every period holds the same number of rows of every stratum at the same
    places (``mix["pool"]["per_period"]``: near-copies expected to have
    1-9, 10-99, 100-999 and >= 1000 results; the rest background rows,
    stratum -1, with none). A near-copy is a group's centre plus N(0,
    ``sigma_q``) noise, rounded and clipped. WHICH groups, and where in a
    period each stratum sits, is the configuration's law; the noise and
    the background rows are the seed's."""
    spec, dim = config["data"], int(config["dim"])
    law, plan = int(spec["law_seed"]), mix["pool"]
    period, per = int(plan["period"]), [int(n) for n in plan["per_period"]]
    sigma_q = float(plan["sigma_q"])
    if rows % period or sum(per) > period:
        raise ValueError("the pool is whole periods, each with room for "
                         "its near-copies")
    periods = rows // period
    sizes = block_rows_of(int(config["rows"]), spec)
    want = [n * periods for n in per]
    found: list = [[] for _ in per]
    cap = int(config["range_cap"])
    for block, n in enumerate(sizes):
        if all(len(f) >= w for f, w in zip(found, want)):
            break
        group, sigma, centre, _ = block_law(block, n, dim, spec)
        expect = expected_results(group, sigma, centre.shape[0], dim,
                                  float(config["radius"]), sigma_q)
        for s, (lo, hi) in enumerate(_PICK):
            ok = (expect >= lo) & (expect <= (hi if hi else 0.9 * cap))
            found[s].extend(centre[g] for g in np.nonzero(ok)[0][
                :max(0, want[s] - len(found[s]))])
    if any(len(f) < w for f, w in zip(found, want)):
        raise ValueError(
            f"the corpus's law holds {[len(f) for f in found]} groups for "
            f"the strata, the pool wants {want}")
    where = np.random.default_rng([law, 0x91]).permutation(period)
    stratum = np.full(period, -1, np.int8)
    stratum[where[:sum(per)]] = np.repeat(np.arange(len(per)), per)
    stratum = np.tile(stratum, periods)
    rng = np.random.default_rng([int(seed), 0x71])
    pool = host_rows(rng, rows, centres(law, spec, dim), spec)
    for s in range(len(per)):
        at = np.nonzero(stratum == s)[0]
        if not len(at):
            continue
        near = np.stack(found[s][:len(at)]) + rng.standard_normal(
            (len(at), dim)) * sigma_q
        pool[at] = np.clip(np.rint(near), 0.0, 255.0)
    return pool.astype(np.float32), stratum
