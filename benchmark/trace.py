"""Reduction of a profiler trace to the numbers the benchmark reports.

``read_xplane`` opens the ``.xplane.pb`` that ``jax.profiler`` wrote with
``jax.profiler.ProfileData`` and nothing else. ``summarize`` is plain Python
over (name, start, duration) tuples, so it is tested without a device:

- busy: the union of the intervals in which an operation ran on the device
  (the device plane's "XLA Ops" line), clipped to the traced window and
  averaged over the chips; idle share = 1 - busy / window;
- device_ops: each operation's own time (its duration less the operations
  nested in it: a ``while`` holds its body's operations), summed by the name
  the trace gives, the ten largest;
- idle_gaps: every gap between device operations is given to the host
  activity that overlapped it most (the most specific one on a tie), and
  the gaps are summed by that name, the ten largest.

Times are seconds. The window is the benchmark's own ``bench:traced``
annotation where the trace has one, else the span of the device events.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_ANNOTATION = "bench:traced"
OPS_LINE = "XLA Ops"
NO_HOST = "(no host event: waiting)"


class TracedSpan:
    """A profiler trace around part of a window, with the benchmark's
    window annotation inside it. The Python tracer is off (it slows the
    host and floods the file); the host's TraceMe events are on."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.annotation = None
        self.started_at = None

    @property
    def running(self) -> bool:
        return self.annotation is not None

    def start(self) -> None:
        import time

        import jax.profiler as jp

        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jp.start_trace(self.log_dir, profiler_options=opts)
        self.annotation = jp.TraceAnnotation(WINDOW_ANNOTATION)
        self.annotation.__enter__()
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        import jax.profiler as jp

        if self.running:
            self.annotation.__exit__(None, None, None)
            self.annotation = None
            jp.stop_trace()

    def summary(self, allow_empty: bool = False):
        return summarize_file(newest_xplane(self.log_dir), allow_empty)


def newest_xplane(log_dir: str) -> str | None:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                               "*.xplane.pb")),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def read_xplane(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """``{"device": {plane: [(name, start_s, dur_s)]}, "host": [...]}``."""
    import jax.profiler as jp

    data = jp.ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (short_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append(
                            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        )
    return {"device": device, "host": host}


def short_name(text: str, limit: int = 96) -> str:
    """The TPU trace names an operation by its whole HLO line; keep the
    name, the kind of operation and the result's shape without layouts:
    ``%fusion.35 = f32[1024,8192]{1,0:T(8,128)} fusion(...), kind=kOutput``
    becomes ``%fusion.35 fusion f32[1024,8192]``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:limit]
    shape, depth, i = "", 0, 0
    for i, ch in enumerate(rest):  # the result shape ends at depth 0
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    shape = re.sub(r"\{[^{}]*\}", "", rest[:i])
    kind = rest[i + 1:].split("(", 1)[0]
    return f"{name} {kind} {shape}"[:limit]


def union(intervals: list) -> list:
    """Sorted, disjoint (start, end) covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: list) -> dict:
    """Own seconds by name: an event's duration less the events nested in
    it (events of one line nest or follow one another)."""
    totals: dict = {}
    stack: list = []  # [name, end, own]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    close(float("inf"))
    return totals


class HostActivity:
    """The host's events, split into the benchmark's own annotations
    (``bench:...``) and the rest, as arrays, so that a gap finds what
    overlapped it without a Python loop over every event."""

    def __init__(self, host: list):
        import numpy as np

        self.sides = []
        for ours in (True, False):
            evs = [(n, s, d) for n, s, d in host
                   if n != WINDOW_ANNOTATION
                   and n.startswith("bench:") == ours]
            self.sides.append((
                [n for n, _, _ in evs],
                np.asarray([s for _, s, _ in evs], dtype=np.float64),
                np.asarray([s + d for _, s, d in evs], dtype=np.float64),
            ))

    def during(self, gap: tuple) -> str:
        """What a gap is given to: the benchmark's annotation and the other
        host event that overlap it most, joined by " / "; on equal overlap
        the shorter, more specific event."""
        import numpy as np

        lo, hi = gap
        found = []
        for names, starts, ends in self.sides:
            if not names:
                continue
            over = np.minimum(hi, ends) - np.maximum(lo, starts)
            best = int(np.lexsort((ends - starts, -over))[0])
            if over[best] > 0:
                found.append(names[best])
        return " / ".join(found) if found else NO_HOST


def summarize(events: dict, top: int = 10, min_gap_s: float = 20e-6) -> dict:
    device, host = events["device"], events["host"]
    if not device:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line: nothing ran on the device")
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_ANNOTATION]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        lo = min(s for evs in device.values() for _, s, _ in evs)
        hi = max(s + d for evs in device.values() for _, s, d in evs)
    window = hi - lo
    busy_s, ops, gap_by_name, n_events = [], {}, {}, 0
    activity = HostActivity(
        [(n, s, d) for n, s, d in host if s < hi and s + d > lo])
    for evs in device.values():
        inside = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                  for n, s, d in evs if s < hi and s + d > lo]
        n_events += len(inside)
        busy = union([(s, s + d) for _, s, d in inside])
        busy_s.append(sum(e - s for s, e in busy))
        for name, sec in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + sec / len(device)
        for g in gaps(busy, lo, hi):
            if g[1] - g[0] < min_gap_s:
                name = "(gaps under 20 us)"
            else:
                name = activity.during(g)
            gap_by_name[name] = (gap_by_name.get(name, 0.0)
                                 + (g[1] - g[0]) / len(device))

    def largest(d: dict) -> list:
        return [[n, s] for n, s in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": window,
        "busy_s": sum(busy_s) / len(busy_s),
        "chips": len(device),
        "device_events": n_events,
        "device_ops": largest(ops),
        "idle_gaps": largest(gap_by_name),
    }


def summarize_file(path: str | None, allow_empty: bool = False):
    """The summary of the trace at ``path``. A trace in which nothing ran
    on the device is an error (``allow_empty``, for the tests on the CPU,
    gives None instead)."""
    try:
        if path is None:
            raise ValueError("the profiler wrote no .xplane.pb")
        return summarize(read_xplane(path))
    except ValueError:
        if allow_empty:
            return None
        raise


def idle_pct(trace: dict | None) -> float | None:
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
