"""Result containers.

Replaces the reference's ``neighbour{distance, idx[, label]}`` array-of-structs
(``/root/reference/knn-serial.c:14-18``) with structure-of-arrays device
arrays: distances and global indices live in separate, MXU/VPU-friendly
tensors; labels are gathered on demand from a label vector.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

# Sentinel index used for padded / masked-out candidate rows.
INVALID_ID = -1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KNNResult:
    """Top-k nearest neighbors for a batch of queries.

    Attributes:
      dists: (q, k) float array. Distances in *sortable* space — squared L2 for
        the ``l2`` metric (monotone in true L2, per SURVEY.md §5 Q10),
        ``1 − cosine`` for the ``cosine`` metric, the NEGATED inner
        product ``-<q, c>`` for the ``ip`` metric (the rows of largest
        inner product first, at the most negative values). Ascending along
        k.
      ids: (q, k) int32 array of 0-based global corpus ids (the reference uses
        1-based ids, ``/root/reference/knn-serial.c:89``; use ``one_based()``
        for parity output). ``INVALID_ID`` marks unfilled slots (k > valid
        candidates).
      dist_steps: int32 (..., 2), the call's tile steps by the path of their
        distance dot, ``[one-pass, multi-pass]`` (``backends/serial.py
        masked_dist_tile``), or (..., 3) ``[0, 0, cosine]`` from a cosine
        call, or (..., 5) ``[0, 0, 0, 0, ip]`` from an inner-product call,
        or (..., 4) ``[0, multi-pass, 0, fused]`` from a call whose
        one-pass steps ran inside the kernel that walks the whole stack
        (``ops/fused_scan.py``), or (..., 6) ``[0, multi-pass, 0, 0, 0, u8]``
        from a call over a byte stack (``dtype="uint8"``: its one-pass
        steps, kernel or tile steps), or (..., 7) ``[0, 0, 0, 0, 0, 0,
        fused_screen]`` from a call whose screened steps ran inside that
        kernel's three-pass form (fractional float32 rows on the lane grid
        under L2: ``backends/serial.py fused_screen_rule``), one row a device where the rows are counted on
        the ring's devices; comes with the answer, costs no wait of its
        own. ``obs.metrics.MetricsRegistry.count_dist_steps`` adds it to
        ``knn_dist_tile_steps_total``. None from a program that counts no
        such tile step.
      select_tiles: int32 (..., 2), the call's query-tile merges by what
        became of the selection their scans carried, ``[carried,
        rescanned]`` (``backends/serial.py merge_tiles_into_carry``:
        rescanned, some row failed the lane-bin certificate and the flagged
        rows were answered again), one row a ring device; comes with the
        answer as ``dist_steps`` does, and
        ``MetricsRegistry.count_select_tiles`` adds it to
        ``knn_select_query_tiles_total``. None from a program whose scans
        carry no lists.
      bins_chunks: int32 (..., 2), the chunks (16 rows x 1024 columns) of
        the call's distance tiles by what became of them in *bins* under
        the row bound that rides such a scan, ``[inserted, skipped]``
        (``backends/serial.py _merge_carried``), one row a ring device;
        ``MetricsRegistry.count_bins_chunks`` adds it to
        ``knn_select_bins_chunks_total``. None where no bound rides.
      ivf_probe: int32 (7,), what a clustered index's batch probed,
        ``[probes, bucket_cap, live rows probed, distinct partitions,
        their live rows, work items walked (0: the row-major program),
        those walked in one bf16 pass (the store and the batch's query
        rows bf16 numbers; else 0)]``
        (``ivf/search.py probe_counts``);
        ``MetricsRegistry.count_ivf_probe`` adds it to the
        ``ivf_probe_*_total`` counters. None from every other index.
      screen_rows: int32 (2,), the call's query rows (padding included) by
        the verdict of the SCREEN's certificate, ``[certified, flagged]``
        (``backends/serial.py screen_rule`` / ``screen_eps``: the scan
        ranked in three bf16 passes, k' candidates a row were finished at
        the configured precision, and a flagged row was answered again by
        the six-pass re-scan); ``MetricsRegistry.count_screen_rows`` adds
        it to ``knn_screen_rows_total``. None from a program that does
        not screen.
    """

    dists: jax.Array
    ids: jax.Array
    dist_steps: jax.Array | None = None
    select_tiles: jax.Array | None = None
    bins_chunks: jax.Array | None = None
    ivf_probe: jax.Array | None = None
    screen_rows: jax.Array | None = None

    @property
    def k(self) -> int:
        return self.ids.shape[-1]

    def l2_dists(self) -> jax.Array:
        """True (non-squared) L2 distances, like the reference compares in."""
        return jnp.sqrt(jnp.maximum(self.dists, 0.0))

    def one_based(self) -> jax.Array:
        """1-based ids for bit-parity with the reference (invalid stays -1)."""
        return jnp.where(self.ids >= 0, self.ids + 1, self.ids)

    def valid(self) -> jax.Array:
        return self.ids >= 0


@dataclasses.dataclass(frozen=True)
class ClassifyResult:
    """Output of kNN majority-vote classification (SURVEY.md C10)."""

    predictions: jax.Array  # (q,) int32, 0-based class ids
    counts: jax.Array  # (q, num_classes) int32 vote histogram

    def matches(self, true_labels: Any) -> jax.Array:
        """The reference's end-to-end oracle: number of correct predictions
        (``/root/reference/knn-serial.c:127-130``)."""
        return jnp.sum(self.predictions == jnp.asarray(true_labels))
