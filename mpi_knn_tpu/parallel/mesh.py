"""Device mesh construction for the distributed backends.

The reference's "mesh" is MPI_COMM_WORLD: a logical ring of P processes wired
by hand from point-to-point sends (``/root/reference/mpi-knn-parallel_blocking.c:58-61,
124-147``), with the partition size coming from argv and the ring size from
MPI — two sources of truth that silently corrupt when they disagree
(SURVEY.md §5 Q6). Here the mesh is the single source of truth: a 1-D
``jax.sharding.Mesh`` over ``jax.devices()`` in list order. On a 2 x 2 v5e
host that order (coordinates (0,0), (1,0), (0,1), (1,1)) is not a walk over
neighbouring coordinates, and it does not have to be: one hop of a 0.82 GB
block a chip took 21.3 ms (38.8 GB/s a chip) in list order and in both
coordinate orders alike (PERF.md §6, PR 28). Multi-host runs build the
same mesh over ``jax.devices()`` after ``jax.distributed.initialize`` (see
mpi_knn_tpu.parallel.distributed).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def make_ring_mesh(
    num_devices: Optional[int] = None,
    axis_name: str = "ring",
    devices: Optional[Sequence] = None,
) -> Mesh:
    """1-D mesh over the first `num_devices` visible devices (default: all)."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devices)} visible"
            )
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def make_mesh2d(
    dp: int,
    ring: int,
    dp_axis: str = "dp",
    ring_axis: str = "ring",
    devices: Optional[Sequence] = None,
) -> Mesh:
    """2-D (dp × ring) mesh: queries shard over `dp`, the corpus rings over
    `ring`, so query throughput and corpus capacity scale independently —
    the strategy mix the reference cannot express (its one MPI axis carries
    both partitions in lockstep, SURVEY.md §2a).

    The ring axis is the minor (fastest-varying) axis so each dp group's
    ppermute steps ride adjacent ICI links."""
    if devices is None:
        devices = jax.devices()
    need = dp * ring
    if need > len(devices):
        raise ValueError(
            f"requested {dp}×{ring}={need} devices, only {len(devices)} visible"
        )
    grid = np.asarray(devices[:need]).reshape(dp, ring)
    return Mesh(grid, (dp_axis, ring_axis))
