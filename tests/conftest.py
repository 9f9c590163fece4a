"""Test environment, two modes:

- default: force CPU with 8 virtual devices so the full ppermute ring runs
  without TPU hardware (SURVEY.md §4 "Distributed-without-a-cluster"), and
  enable x64 for the float64 debug/oracle paths (SURVEY.md §5 Q10).
- ``TKNN_TPU_TESTS=1``: run the hardware-parity subset on the REAL chip —
  core math modules only (topk/vote/distance/serial/pallas/data), small
  shapes, f64-dependent tests auto-skipped (TPUs have no f64). This is the
  one-command "does the whole stack work on hardware" gate (VERDICT r2
  next-step #10); the pallas tests in this mode compile via Mosaic instead
  of the CPU interpreter.

Invariant: force_platform must run before the first JAX *device access*
(backend creation), not before `import jax` — importing mpi_knn_tpu below
already imports jax, which is fine because XLA_FLAGS and jax_platforms are
both read at backend creation time. force_platform raises if a backend
already exists. Never add device access (jax.devices(), array creation) at
module import time anywhere in the package.
"""

import os

from mpi_knn_tpu.utils.platform import force_platform

TPU_MODE = os.environ.get("TKNN_TPU_TESTS") == "1"

if not TPU_MODE:
    force_platform("cpu", n_devices=8)

import jax  # noqa: E402

if not TPU_MODE:
    jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# modules whose tests are meaningful and safe on one real chip: single-device
# math parity + host-side data parsing. Ring/mesh/multihost/resume modules
# need the 8-device CPU mesh or OS-process control; harness/CLI tests spawn
# their own platform-forcing subprocesses.
_TPU_MODULES = {
    "test_topk",
    "test_vote",
    "test_distance",
    "test_serial",
    "test_pallas",
    "test_data",
    "test_vecs",
}


def pytest_collection_modifyitems(config, items):
    if not TPU_MODE:
        return
    skip = pytest.mark.skip(
        reason="outside the on-TPU subset (TKNN_TPU_TESTS=1)"
    )
    for it in items:
        mod = it.module.__name__.rsplit(".", 1)[-1] if it.module else ""
        if mod not in _TPU_MODULES or "f64" in it.name:
            it.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def debug_nans():
    """``jax_debug_nans`` on for one test, restored unconditionally. As a
    fixture (not an in-test try/finally) a crash anywhere in the test body
    — including during collection-time fixture setup — can never leak the
    flag into later tests, where it would silently recompile every jit
    with NaN checks and distort timings."""
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", False)


@pytest.fixture
def dist_steps():
    """``dist_steps()`` -> (one-pass, multi-pass) tile steps a server has
    counted so far (``knn_dist_tile_steps_total``; a one-shot call carries
    its own on ``KNNResult.dist_steps``)."""
    from mpi_knn_tpu.obs.metrics import DIST_STEPS, get_registry

    def read():
        reg = get_registry()
        return tuple(
            reg.counter(DIST_STEPS, labels={"path": p}).value
            for p in ("onepass", "multipass")
        )

    return read
