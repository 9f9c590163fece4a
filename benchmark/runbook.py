"""The runbook of a streaming cell: the steps (``insert {start, end}``,
``search``, ``delete {start, end}``) as a function of the configuration,
the traffic mix and the seed, in the form of the big-ann-benchmarks
streaming track's runbooks, and the query pool that goes with them. Pure
arithmetic on the host: no jax, nothing of the program.

A cycle is three steps. Cycle ``i`` (warm cycles first, then the window's)
inserts one range of ``range_rows`` new ids, all of one cluster, at the end
of the id space; searches; deletes the oldest live range of ``range_rows``
base ids of another cluster. Clusters are visited round robin from a seeded
start (inserts) and from half a turn further on (deletes), so the hot
ranges move and an inserted row lands in a slot another cluster's row left.
The live count is the base count between cycles and ``range_rows`` more
inside one.

Where the configuration's data law names a ``law_seed`` the book follows it
and not ``--seed``: which cluster the first range goes into and which
blocks the pool rows are drawn near are the same in every run, as the
clusters themselves are (``datagen/clustered_f32_stream.py``), so every
seed walks the same book over other rows. The probe block follows
``--seed``.
"""

from __future__ import annotations

import numpy as np


def plan(config: dict, mix: dict, seed: int) -> dict:
    """Every cycle the mix could reach (``max_cycles``), the cluster of
    every block of the id space, and the targets of the query pool."""
    rows, spec = int(config["rows"]), config["data"]
    clusters, block = int(spec["clusters"]), int(spec["block_rows"])
    span = int(mix["range_rows"])
    n_cycles = int(mix["max_cycles"])
    per_cluster = rows // clusters
    if rows % (clusters * block) or span % block:
        raise ValueError("rows, clusters, block_rows and range_rows do not "
                         "divide")
    if (n_cycles // clusters + 1) * span > per_cluster // 2:
        raise ValueError("max_cycles would delete past half a cluster")
    law = int(spec.get("law_seed", seed))
    start = int(np.random.default_rng([law, 0x5B]).integers(clusters))
    cycles = []
    for i in range(n_cycles):
        into = (start + i) % clusters
        out_of = (start + i + clusters // 2) % clusters
        d_lo = out_of * per_cluster + (i // clusters) * span
        cycles.append({
            "insert": (rows + i * span, rows + (i + 1) * span),
            "insert_cluster": into,
            "delete": (d_lo, d_lo + span),
            "delete_cluster": out_of,
        })
    base = np.repeat(np.arange(clusters), per_cluster // block)
    added = np.repeat([c["insert_cluster"] for c in cycles], span // block)
    return {"cycles": cycles, "rows": rows, "block_rows": block,
            "ids": rows + n_cycles * span, "law_seed": law,
            "cluster_of_block": np.concatenate([base, added])}


def steps(cycle: dict):
    """One cycle as runbook steps, in the track's own form."""
    yield {"operation": "insert", "start": cycle["insert"][0],
           "end": cycle["insert"][1]}
    yield {"operation": "search"}
    yield {"operation": "delete", "start": cycle["delete"][0],
           "end": cycle["delete"][1]}


def blocks_of(span: tuple, block: int) -> list:
    return list(range(span[0] // block, span[1] // block))


def pool_targets(plan_: dict, mix: dict, seed: int) -> np.ndarray:
    """The block each pool row is drawn near, by ``row % 8``: three of
    eight near a block inserted by the first checkpoint, one near a block
    inserted between the first and the last, three near a base block
    deleted before the last checkpoint's search, one near a base block no
    step of the window touches. Every 8 consecutive rows, and so the probe
    block, hold that mix: the ranges the window inserts and deletes lie
    among the probes' neighbours, and ``probe_touched_*`` says so."""
    block, cycles = plan_["block_rows"], plan_["cycles"]
    warm, last = int(mix["warm_cycles"]), max(mix["checkpoints"])
    first = min(mix["checkpoints"])
    early = [b for c in cycles[: warm + first]
             for b in blocks_of(c["insert"], block)]
    later = [b for c in cycles[warm + first: warm + last]
             for b in blocks_of(c["insert"], block)]
    gone = [b for c in cycles[: warm + last - 1]
            for b in blocks_of(c["delete"], block)]
    base_blocks = plan_["rows"] // block
    per_cluster = base_blocks // len(set(plan_["cluster_of_block"].tolist()))
    # the upper half of a cluster's blocks is out of every delete's reach
    rng = np.random.default_rng([int(plan_.get("law_seed", seed)), 0x7A])
    n = int(mix["query_pool_rows"])
    quiet = (rng.integers(0, base_blocks // per_cluster, size=n) * per_cluster
             + per_cluster // 2
             + rng.integers(0, per_cluster - per_cluster // 2, size=n))
    out = np.empty(n, np.int64)
    for j in range(n):
        kind, turn = j % 8, j // 8
        if kind < 3:
            out[j] = early[(3 * turn + kind) % len(early)]
        elif kind == 3:
            out[j] = later[turn % len(later)] if later else early[0]
        elif kind < 7:
            out[j] = gone[(3 * turn + kind) % len(gone)]
        else:
            out[j] = quiet[j]
    return out


def probe_block(seed: int, pool_rows: int, probe_rows: int) -> int:
    """First pool row of the probe block, from the seed."""
    rng = np.random.default_rng([int(seed), 0x9B])
    return int(rng.integers(0, pool_rows // probe_rows)) * probe_rows
