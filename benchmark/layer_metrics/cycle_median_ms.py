"""The streaming cell's typical cycle: the median, over the window's whole
cycles, of a cycle's length on the walker's own clock
(``run["stream"]["cycles"]``, one dict a cycle, ``s`` its seconds). The rate is the pool's rows over the MEAN
cycle; the median leaves the long cycles out, so it is the steadier of the
two, and with ``cycle_stall_pct`` it says whether a rate that read low lost
to stalls or to a shift of every cycle. Fewer than three cycles say nothing.
Source: host clock."""

import statistics


def read(run: dict):
    cycles = [c["s"] for c in (run.get("stream") or {}).get("cycles") or []]
    if len(cycles) < 3:
        return None
    return 1e3 * statistics.median(cycles)
