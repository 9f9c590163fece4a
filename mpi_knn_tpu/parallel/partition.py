"""Corpus partitioning / padding — the replacement for the reference's block
partitioner and augmented row matrix (SURVEY.md C6).

The reference widens every corpus row to n+2 columns, smuggling the global id
and label inside the float payload that circulates the MPI ring
(``/root/reference/mpi-knn-parallel_blocking.c:100-109``), and silently
requires the process count to divide m (SURVEY.md §5 Q6). Here ids/labels ride
as separate int32 arrays sharded identically to the corpus, and divisibility
is handled by padding with sentinel rows (id = −1) that the top-k masks force
to +inf distance (SURVEY.md §8 "Divisibility/padding").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mpi_knn_tpu.types import INVALID_ID


def pad_to_multiple(n: int, multiple: int) -> int:
    """Smallest padded size >= n that is a multiple of `multiple` (>= 1)."""
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    return ((n + multiple - 1) // multiple) * multiple


def pad_rows(x: np.ndarray, target_rows: int, fill=0.0) -> np.ndarray:
    """Pad a (m, ...) array with `fill` rows up to target_rows (no-op if equal)."""
    m = x.shape[0]
    if target_rows < m:
        raise ValueError(f"target_rows {target_rows} < rows {m}")
    if target_rows == m:
        return x
    pad_width = [(0, target_rows - m)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_width, constant_values=fill)


def pad_rows_any(x, target_rows: int, fill=0.0, dtype=None) -> jax.Array:
    """``pad_rows`` that returns a device array and never bounces a
    device-resident input through the host: jax.Array inputs are padded with
    on-device ops, everything else is padded in numpy then transferred once."""
    if isinstance(x, jax.Array):
        out = x if dtype is None else x.astype(dtype)
        extra = target_rows - x.shape[0]
        if extra < 0:
            raise ValueError(f"target_rows {target_rows} < rows {x.shape[0]}")
        if extra:
            widths = [(0, extra)] + [(0, 0)] * (x.ndim - 1)
            out = jnp.pad(out, widths, constant_values=fill)
        return out
    return jnp.asarray(pad_rows(np.asarray(x), target_rows, fill=fill), dtype=dtype)


def pad_cols(x, width: int):
    """``x`` with its last axis zero-filled up to ``width`` columns, in
    numpy or on the device as it came (a stack that rests wider than its
    rows, ``serve/index.py rest_width``: zeros add exact zeros to every dot
    and norm). ``x`` itself where it is that wide already: no operation
    enters a traced program."""
    extra = width - x.shape[-1]
    if extra < 0:
        raise ValueError(f"width {width} < columns {x.shape[-1]}")
    if not extra:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, extra)]
    return (jnp if isinstance(x, jax.Array) else np).pad(x, widths)


def make_global_ids(m: int, padded: int) -> np.ndarray:
    """0-based global ids for m real rows, INVALID_ID for padding rows."""
    ids = np.full(padded, INVALID_ID, dtype=np.int32)
    ids[:m] = np.arange(m, dtype=np.int32)
    return ids
