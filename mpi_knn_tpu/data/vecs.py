"""Reader for TexMex ``*.fvecs`` / ``*.bvecs`` / ``*.ivecs`` vector files —
the on-disk format of the SIFT1M/GIST1M benchmark corpora (the BASELINE.md
SIFT1M config). Native C++ reader (native/vecsio.cpp, streaming, bound via
ctypes like the MAT reader) with a pure-NumPy fallback.

Format, per vector: little-endian int32 dimension d, then d components
(float32 / uint8 / int32). All rows share d. fvecs/bvecs load as float32
(bvecs widened); ivecs (ground-truth id files) load as int32.
:func:`bvecs_blocks` hands a ``.bvecs`` file on as its own BYTES, a block
of rows at a time, for a byte stack's build (``serve.build_index_blocks``).
"""

from __future__ import annotations

import ctypes
from pathlib import Path
from typing import Optional

import numpy as np

from mpi_knn_tpu.data._native import load_native

_KINDS = {".fvecs": "f", ".bvecs": "b", ".ivecs": "i"}


def _bind(lib: ctypes.CDLL) -> None:
    lib.tknn_vecs_read.restype = ctypes.c_void_p
    lib.tknn_vecs_read.argtypes = [ctypes.c_char_p, ctypes.c_char, ctypes.c_int64]
    lib.tknn_vecs_error.restype = ctypes.c_char_p
    lib.tknn_vecs_error.argtypes = [ctypes.c_void_p]
    lib.tknn_vecs_rows.restype = ctypes.c_int64
    lib.tknn_vecs_rows.argtypes = [ctypes.c_void_p]
    lib.tknn_vecs_dim.restype = ctypes.c_int64
    lib.tknn_vecs_dim.argtypes = [ctypes.c_void_p]
    lib.tknn_vecs_copy.restype = None
    lib.tknn_vecs_copy.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.tknn_vecs_close.restype = None
    lib.tknn_vecs_close.argtypes = [ctypes.c_void_p]


def load_native_lib(build: bool = True):
    """Load (building if needed) the C++ vecs reader; None if unavailable."""
    return load_native("libtknn_vecsio.so", _bind, build=build)


def _kind_for(path: Path) -> str:
    try:
        return _KINDS[path.suffix]
    except KeyError:
        raise ValueError(
            f"{path}: not a .fvecs/.bvecs/.ivecs file"
        ) from None


def read_vecs_native(path, limit: Optional[int] = None,
                     lib=None) -> Optional[np.ndarray]:
    """Native read; None if the native lib is unavailable. Raises ValueError
    on malformed files (truncation, inconsistent dims). ``lib`` overrides
    the default library (the ASan sweep passes the sanitizer build so THIS
    loop runs under the sanitizer)."""
    if lib is None:
        lib = load_native_lib()
    else:
        _bind(lib)  # idempotent; an unbound CDLL would truncate pointers
    if lib is None:
        return None
    path = Path(path)
    kind = _kind_for(path)
    h = lib.tknn_vecs_read(
        str(path).encode(), kind.encode(), -1 if limit is None else limit
    )
    try:
        err = lib.tknn_vecs_error(h)
        if err:
            raise ValueError(f"{path}: {err.decode()}")
        rows, dim = lib.tknn_vecs_rows(h), lib.tknn_vecs_dim(h)
        dtype = np.int32 if kind == "i" else np.float32
        out = np.empty((rows, dim), dtype=dtype)
        if rows:
            lib.tknn_vecs_copy(h, out.ctypes.data_as(ctypes.c_void_p))
        return out
    finally:
        lib.tknn_vecs_close(h)


def read_vecs_numpy(path, limit: Optional[int] = None) -> np.ndarray:
    """Pure-NumPy fallback. Validation semantics match the native reader
    exactly (including under ``limit``): only the first `limit` rows are
    validated, a clean EOF at a row boundary is fine, a row truncated inside
    the requested range raises — so the two paths succeed and fail on the
    same inputs."""
    path = Path(path)
    kind = _kind_for(path)
    out_dtype = np.int32 if kind == "i" else np.float32
    with open(path, "rb") as f:
        head = f.read(4)
    if len(head) == 0 or limit == 0:
        return np.empty((0, 0), out_dtype)
    if len(head) < 4:
        raise ValueError(f"{path}: truncated dimension field at row 0")
    d = int(np.frombuffer(head, np.int32)[0])
    if d <= 0 or d > (1 << 24):
        raise ValueError(f"{path}: implausible dimension {d} at row 0")
    comp = 1 if kind == "b" else 4
    stride = 4 + d * comp
    # read only what the limit needs — a SIFT1B-scale file with a small
    # limit must not be slurped whole (the native path streams likewise)
    count = -1 if limit is None else limit * stride
    raw = np.fromfile(path, dtype=np.uint8, count=count)
    full_rows = raw.size // stride
    rows = full_rows if limit is None else min(limit, full_rows)
    if (limit is None or full_rows < limit) and raw.size % stride:
        # a partial trailing row inside the requested range: the native
        # reader reports the same condition row by row
        raise ValueError(
            f"{path}: truncated row {full_rows} (size {raw.size} not a "
            f"multiple of row stride {stride})"
        )
    mat = raw[: rows * stride].reshape(rows, stride)
    dims = mat[:, :4].copy().view(np.int32).reshape(rows)
    if not (dims == d).all():
        bad = int(np.argmax(dims != d))
        raise ValueError(
            f"{path}: inconsistent dimension ({int(dims[bad])} vs {d}) at "
            f"row {bad}"
        )
    body = np.ascontiguousarray(mat[:, 4:])
    if kind == "b":
        return body.astype(np.float32)
    return body.view(out_dtype)


def read_vecs(path, limit: Optional[int] = None) -> np.ndarray:
    """(n, d) array from a .fvecs/.bvecs/.ivecs file: native reader when the
    toolchain is available, NumPy otherwise. Same output either way."""
    out = read_vecs_native(path, limit=limit)
    if out is None:
        out = read_vecs_numpy(path, limit=limit)
    return out


def bvecs_blocks(path, block_rows: int = 1 << 19,
                 limit: Optional[int] = None):
    """``((rows, dim), blocks)`` of a ``.bvecs`` file read as BYTES, a block
    at a time: ``blocks(i)`` is rows ``[i * block_rows, (i + 1) *
    block_rows)`` as a contiguous (n, dim) ``uint8`` array — None after the
    last — so that ``serve.build_index_blocks((rows, dim), blocks,
    KNNConfig(dtype="uint8", ...))`` never holds the file, and nothing is
    widened on the host. The file's size fixes ``rows`` up front (``limit``
    caps it); a block whose rows do not all state ``dim``, or a file whose
    size is no whole number of rows, raises ``ValueError``."""
    path = Path(path)
    if _kind_for(path) != "b":
        raise ValueError(f"{path}: not a .bvecs file")
    size = path.stat().st_size
    if size < 4:
        raise ValueError(f"{path}: truncated dimension field at row 0")
    dim = int(np.fromfile(path, dtype=np.int32, count=1)[0])
    if dim <= 0 or dim > (1 << 24):
        raise ValueError(f"{path}: implausible dimension {dim} at row 0")
    stride = 4 + dim
    rows = size // stride
    if limit is not None:
        rows = min(rows, int(limit))
    elif size % stride:
        raise ValueError(
            f"{path}: truncated row {rows} (size {size} not a multiple of "
            f"row stride {stride})")

    def blocks(i: int):
        lo = i * block_rows
        if lo >= rows:
            return None
        n = min(block_rows, rows - lo)
        mat = np.fromfile(path, dtype=np.uint8, count=n * stride,
                          offset=lo * stride).reshape(n, stride)
        dims = mat[:, :4].copy().view(np.int32).reshape(n)
        if not (dims == dim).all():
            bad = lo + int(np.argmax(dims != dim))
            raise ValueError(
                f"{path}: inconsistent dimension ({int(dims[bad - lo])} vs "
                f"{dim}) at row {bad}")
        return np.ascontiguousarray(mat[:, 4:])

    return (rows, dim), blocks
