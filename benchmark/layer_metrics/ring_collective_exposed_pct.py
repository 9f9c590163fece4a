"""Share of the traced span in which a chip ran a collective-permute
operation of the ring (the ``-start`` that issues it or the ``-done`` that
waits for the block) and no compute operation overlapped it: what the
rotation costs that the overlap did not hide. Per chip, then the mean of
the chips. Source: device trace (``run["ring"]``, from
``drivers/allknn_ring.py``).

The other ``ring_*`` readers load this file for its reductions of a chip's
event list ``[(name, start_s, seconds)]``, names as ``trace.short_name``
leaves them: ``%collective-permute-done.1 collective-permute-done f32[..]``.
"""

from benchmark.trace import gaps, union

# operations that hold others (a ``while`` holds its body's): not work
CONTAINERS = ("while", "conditional", "call")


def kind(name: str) -> str:
    parts = name.split(" ")
    return parts[1] if len(parts) > 1 else ""


def is_permute(name: str) -> bool:
    return kind(name).startswith("collective-permute")


def seconds(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def exposed(events: list) -> list:
    """Disjoint intervals in which a permute operation ran and no compute
    operation did."""
    permute = union([(s, s + d) for n, s, d in events if is_permute(n)])
    compute = union([(s, s + d) for n, s, d in events
                     if not is_permute(n) and kind(n) not in CONTAINERS])
    out = []
    for s, e in permute:
        inside = [(max(a, s), min(b, e)) for a, b in compute
                  if a < e and b > s]
        out.extend(gaps(inside, s, e))
    return out


def in_flight(events: list) -> list:
    """Disjoint intervals in which a permute was in flight: from each
    ``-start`` to the end of the ``-done`` of the same number."""
    open_at, spans = {}, []
    for n, s, d in sorted(events, key=lambda ev: ev[1]):
        op = n.split(" ", 1)[0]
        if kind(n) == "collective-permute-start":
            open_at.setdefault(op.partition("-start")[2], s)
        elif kind(n) == "collective-permute-done":
            began = open_at.pop(op.partition("-done")[2], None)
            if began is not None:
                spans.append((began, s + d))
    return union(spans)


def busy(events: list) -> float:
    return seconds(union([(s, s + d) for _, s, d in events]))


def read(run: dict):
    ring = run.get("ring")
    if not ring or not ring.get("events"):
        return None
    lo, hi = ring["window"]
    if hi <= lo or not any(is_permute(n) for evs in ring["events"]
                           for n, _, _ in evs):
        return None
    shares = [seconds(exposed(evs)) / (hi - lo) for evs in ring["events"]]
    return 100.0 * sum(shares) / len(shares)
