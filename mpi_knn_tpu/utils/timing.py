"""Timing and profiling (SURVEY.md §6 "Tracing / profiling").

The reference times only the distance phase with a ``gettimeofday`` pair
(``/root/reference/knn-serial.c:70,94-98``). With an async dispatch runtime
that approach lies: the host returns before the device finishes. PhaseTimer
therefore blocks on the phase's result arrays before reading the clock, and
optional ``jax.profiler`` traces expose MXU utilization / ICI overlap for the
ring backends.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

import jax


def device_sync(*trees):
    """Wait for the device work producing these arrays (every leaf, every
    shard) — the rebuild's answer to the reference's ``gettimeofday`` pair
    (``/root/reference/knn-serial.c:70,94-98``), which had the same
    measure-the-real-work intent in a synchronous world."""
    jax.block_until_ready(trees)


class PhaseTimer:
    """Named wall-clock phases with device synchronization.

    Usage::

        timer = PhaseTimer()
        with timer.phase("knn"):
            result = all_knn(...)
            timer.block_on(result.dists)   # device sync inside the phase
        timer.seconds["knn"]
    """

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + (
                time.perf_counter() - t0
            )

    @staticmethod
    def block_on(*arrays):
        """Wait for device work producing `arrays` — call before a phase ends
        so the measurement covers compute, not dispatch."""
        device_sync(*arrays)


@contextlib.contextmanager
def profile_trace(trace_dir: Optional[str]):
    """jax.profiler trace (TensorBoard/XProf-compatible) when a dir is given."""
    if not trace_dir:
        yield
        return
    with jax.profiler.trace(trace_dir):
        yield
