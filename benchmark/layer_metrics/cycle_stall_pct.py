"""Share of the streaming cell's window spent in cycles longer than 1.5
times the window's median cycle, on the walker's own clock (a cycle: its
first request sent to its last acknowledged; the window is whole cycles,
``run["stream"]["cycles"]``, one dict a cycle, ``s`` its seconds). A run whose rate read low with this share
up lost it to stalls (a few long cycles: the server's overruns, the
machine); one whose rate read low with this share where it was had its
typical cycle shift. 0.0 is a window without a long cycle; fewer than
three cycles say nothing. Source: host clock."""

import statistics

LONG = 1.5


def read(run: dict):
    cycles = [c["s"] for c in (run.get("stream") or {}).get("cycles") or []]
    if len(cycles) < 3:
        return None
    typical = statistics.median(cycles)
    return 100.0 * sum(c for c in cycles if c > LONG * typical) / sum(cycles)
