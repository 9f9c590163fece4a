"""Bytes one chip sent over the interconnect in the traced calls (the
program's ``ring_wire_bytes_total`` moved by that much, all chips together,
so a chip's part is the delta over the chips) over the time in which a
permute was in flight on that chip (from each ``-start`` to the end of its
``-done``); the mean of the chips, in 1e9 bytes a second. A hidden permute
stays "in flight" until the round's compute lets its ``-done`` run, so this
is the rate the ring needed, and the link's own only where the permutes are
exposed. No share of a peak: ``peaks.json`` has no interconnect entry.
Source: device trace and program counter (``run["ring"]``)."""

from benchmark.harness import load_by_path


def read(run: dict):
    ring = run.get("ring")
    delta = (ring or {}).get("counters_delta") or {}
    sent = delta.get("ring_wire_bytes_total")
    if not sent or not ring.get("events"):
        return None
    red = load_by_path("layer_metrics", "ring_collective_exposed_pct")
    flights = [red.seconds(red.in_flight(evs)) for evs in ring["events"]]
    if not all(f > 0 for f in flights):
        return None
    per_chip = sent / ring["chips"]
    return sum(per_chip / f for f in flights) / len(flights) / 1e9
