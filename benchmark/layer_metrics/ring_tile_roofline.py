"""Least time ONE chip could take for its share of the traced work
(``benchmark/opcount_ring.py``: its rows of every traced call against all
the corpus's blocks; the larger of the operations over the bf16 peak and the
bytes over the HBM peak) over that chip's busy time in the traced span; the
worst chip. ``tile_roofline`` divides one chip's least time for the whole
work by the mean busy time and would read ``chips`` times too high on a
ring. Source: device trace (``run["ring"]``)."""

from benchmark.harness import load_by_path


def read(run: dict):
    ring = run.get("ring")
    if not ring or not ring.get("events") or not ring.get("chip_least_s"):
        return None
    red = load_by_path("layer_metrics", "ring_collective_exposed_pct")
    slowest = max(red.busy(evs) for evs in ring["events"])
    if slowest <= 0:
        return None
    return 100.0 * ring["chip_least_s"] / slowest
