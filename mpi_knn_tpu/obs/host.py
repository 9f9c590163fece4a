"""The host's own evidence, and the overrun rule that asks for it.

A batch or a call that takes seconds where its neighbours take tens of
milliseconds leaves no mark in a mean over a window, and a profiler that
runs for a fifth of one rarely meets it. What can see a stall is the
program itself, at the two places where it already reads its clock once a
unit of work (the serving pump's retire, the one-shot call's entry), if it
keeps what the interpreter and the scheduler did to the thread meanwhile:

- :func:`install_gc_hook` — one ``gc.callbacks`` hook: generation 1 and 2
  collections timed start to stop (``python_gc_seconds_total``,
  ``python_gc_collections_total``, both by ``generation``); a generation-2
  collection is also a ``knn:host.gc`` annotation in a profiler trace;
- :func:`host_sample` — one cheap reading of this thread's clock, CPU
  seconds, involuntary context switches and major faults (ONE system call:
  ``getrusage(RUSAGE_THREAD)``), and the process's collection seconds so
  far; two samples subtract (:func:`host_delta`);
- :class:`OverrunRule` — a unit of work overruns where it exceeds the running
  median of its kind by more than half of that median and by at least 50 ms,
  and the part of it with the largest excess over its own median is where
  it sat;
- :class:`OverrunReport` — what is kept of one: two counters, a flight
  event, one WARNING line (rate-limited), a mark in the trace.

No jax import, no thread, no option: the record is always on.
"""

from __future__ import annotations

import collections
import gc
import logging
import resource
import statistics
import threading
import time

from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans

log = logging.getLogger("mpi_knn_tpu")

# the rule: over the running median by more than this share of it ...
OVERRUN_SHARE = 0.5
# ... and by at least this much (a 2 ms batch that took 4 is no stall)
OVERRUN_FLOOR_S = 0.050
HISTORY = 32  # units of one kind the running median looks back over
ARMED_AFTER = 8  # units of a kind before the rule judges one


# ---------------------------------------------------------------------------
# garbage collection


class _GCWatch:
    """The ``gc.callbacks`` hook. It runs inside the collector, possibly on
    a thread that holds a metric's or the registry's lock (a collection can
    begin between any two bytecodes), so it takes NO lock and touches no
    metric: it adds to plain totals that :meth:`settle` carries into the
    registry at every snapshot. The collector is not re-entrant and holds
    the interpreter lock, so one open reading is enough."""

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0]  # by generation; 0 stays 0
        self.collections = [0, 0, 0]
        self._t0 = 0.0
        self._span = None
        self._lock = threading.Lock()  # settle's own; never the hook's
        self._settled_s = [0.0, 0.0, 0.0]
        self._settled_n = [0, 0, 0]

    def __call__(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if gen == 0:
            return
        if phase == "start":
            if gen == 2:
                self._span = obs_spans.begin_span(
                    "gc", cat="host", flight=False, generation=2)
            self._t0 = time.perf_counter()
        else:
            self.seconds[gen] += time.perf_counter() - self._t0
            self.collections[gen] += 1
            if gen == 2:
                span, self._span = self._span, None
                obs_spans.end_span(span)

    def settle(self, registry) -> None:
        """Advance the registry's counters to the hook's totals (they are
        there from the first snapshot on, at 0: a reader tells a quiet
        window from a program without the hook)."""
        with self._lock:
            for gen in (1, 2):
                labels = {"generation": str(gen)}
                secs, n = self.seconds[gen], self.collections[gen]
                registry.counter(
                    "python_gc_seconds_total",
                    help="seconds the interpreter spent in garbage "
                    "collections of generation 1 and 2 (every thread "
                    "waits: the collector holds the interpreter lock)",
                    labels=labels,
                ).inc(max(0.0, secs - self._settled_s[gen]))
                registry.counter(
                    "python_gc_collections_total",
                    help="garbage collections of generation 1 and 2",
                    labels=labels,
                ).inc(max(0, n - self._settled_n[gen]))
                self._settled_s[gen], self._settled_n[gen] = secs, n


_gc_watch = _GCWatch()


def install_gc_hook() -> bool:
    """Time the interpreter's collections into the default registry.
    Idempotent; True iff the hook was installed by this call."""
    if _gc_watch in gc.callbacks:
        return False
    with _gc_watch._lock:
        if _gc_watch in gc.callbacks:
            return False
        gc.callbacks.append(_gc_watch)
    obs_metrics.get_registry().on_snapshot(_gc_watch.settle)
    return True


# ---------------------------------------------------------------------------
# one reading of the calling thread


Sample = collections.namedtuple("Sample", "at cpu_s nivcsw majflt gc_s")
Sample.__doc__ = """One :func:`host_sample`: ``at`` (``perf_counter``),
this thread's CPU seconds (user + system), involuntary context switches and
major faults, and the process's collection seconds."""


def host_sample() -> Sample:
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return Sample(time.perf_counter(), ru.ru_utime + ru.ru_stime,
                  ru.ru_nivcsw, ru.ru_majflt,
                  _gc_watch.seconds[1] + _gc_watch.seconds[2])


def host_delta(a: Sample, b: Sample) -> dict:
    """What the host did to this thread between two of its samples, as an
    overrun's record carries it: ``gc_ms`` near the excess says a
    collection held the interpreter lock, ``nivcsw`` that the thread was
    taken off its core, ``majflt`` that it waited for pages; all near zero
    with ``cpu_ms`` small says the thread was blocked or stopped, ``cpu_ms``
    near the excess that it was busy itself. (What a kernel does not keep
    reads 0: the chip's host keeps neither count and a CPU clock of 10 ms
    steps.)"""
    return {
        "gc_ms": round(1e3 * (b.gc_s - a.gc_s), 3),
        "cpu_ms": round(1e3 * (b.cpu_s - a.cpu_s), 3),
        "nivcsw": b.nivcsw - a.nivcsw,
        "majflt": b.majflt - a.majflt,
    }


# ---------------------------------------------------------------------------
# the rule


Overrun = collections.namedtuple(
    "Overrun", "excess_s median_s where where_median_s")
Overrun.__doc__ = """A unit that overran: its excess over the running
median ``median_s`` of its kind, the part ``where`` the excess sat, and that
part's own running median."""


class OverrunRule:
    """The last :data:`HISTORY` units of each kind (a bucket height, a query
    height), each as its parts' seconds. Confined to the thread that judges
    (the pump; a call's caller under its own lock)."""

    def __init__(self):
        self._past: dict = {}

    def judge(self, kind, parts: dict) -> Overrun | None:
        """Add one unit — ``parts`` partition its seconds — and say whether
        it overran the running median of the units of its kind before it.
        An overrunning unit enters the history like any other: a level that
        moved for good is the new median after half a history."""
        past = self._past.get(kind)
        if past is None:
            past = self._past[kind] = collections.deque(maxlen=HISTORY)
        total = sum(parts.values())
        out = None
        if len(past) >= ARMED_AFTER:
            median = statistics.median([t for t, _ in past])
            excess = total - median
            if excess > OVERRUN_SHARE * median and excess >= OVERRUN_FLOOR_S:
                medians = {
                    name: statistics.median([p.get(name, 0.0) for _, p in past])
                    for name in parts
                }
                where = max(parts, key=lambda n: parts[n] - medians[n])
                out = Overrun(excess, median, where, medians[where])
        past.append((total, parts))
        return out

    def reset(self) -> None:
        self._past.clear()


class OverrunReport:
    """What is kept of an overrun of one family of units (``serve_batch``,
    ``knn_call``): ``<family>_overruns_total{where}`` and
    ``<family>_overrun_seconds_total{where}`` (the excess, so a window's sum
    is the seconds it lost), an ``overrun`` event in the flight record, a
    zero-length ``knn:<cat>.overrun`` mark in a profiler trace, and the same
    fields as one WARNING line — at most one a second, unless one more than
    doubles the excess of the last line said (a run of small overruns must
    not swallow the stall that follows it); the rest are counted and owned
    up to on the next."""

    def __init__(self, family: str, cat: str, clock=time.monotonic):
        self.family, self.cat, self._clock = family, cat, clock
        self._said_at = None
        self._said_excess_s = 0.0
        self._unsaid = 0

    def __call__(self, registry, where: str, excess_s: float,
                 seq: int, **fields) -> None:
        labels = {"where": where}
        registry.counter(
            f"{self.family}_overruns_total",
            help="units of work that overran the running median of their "
            "kind by more than half of it and by 50 ms, by where the "
            "excess sat",
            labels=labels).inc()
        registry.counter(
            f"{self.family}_overrun_seconds_total",
            help="seconds by which they overran that median: what the "
            "stalls took of a window",
            labels=labels).inc(excess_s)
        fields = {"seq": seq, "where": where,
                  "excess_ms": round(1e3 * excess_s, 3), **fields}
        obs_spans.event("overrun", cat=self.cat, **fields)
        obs_spans.end_span(obs_spans.begin_span(
            "overrun", cat=self.cat, flight=False, seq=seq,
            excess_us=int(1e6 * excess_s)))
        now = self._clock()
        if (self._said_at is not None and now - self._said_at < 1.0
                and excess_s <= 2.0 * self._said_excess_s):
            self._unsaid += 1
            return
        if self._unsaid:
            fields["unsaid_before"] = self._unsaid
        self._said_at, self._said_excess_s, self._unsaid = now, excess_s, 0
        log.warning("overrun %s %s", self.cat, " ".join(
            f"{k}={_word(v)}" for k, v in fields.items()))


def _word(value) -> str:
    """A field of the log line with no space in it (a dict as ``a:1,b:2``),
    so the line splits on spaces."""
    if isinstance(value, dict):
        return ",".join(f"{k}:{v}" for k, v in value.items())
    return str(value)
