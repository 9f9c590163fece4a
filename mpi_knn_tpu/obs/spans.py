"""Span flight recorder — structured trace spans written *incrementally*
to an append-only JSONL ring file.

Why incremental append: the bench rounds that died (BENCH_r01/r03/r04/
r05) lost not just their measurements but the whole story of where the
time went, because every in-memory trace died with the process. This is
the heartbeat trick applied to tracing: every record (span begin, span
end, instant event) is one JSON line, written and flushed the moment it
happens, so a SIGKILLed worker leaves a readable flight record up to the
instant of death — an OPEN ``batch`` span in the file IS the diagnosis
("killed mid-batch 7, rung=full, after 2 retries"). The supervisor
(``resilience.worker.run_supervised``) reads the record back and banks it
alongside the structured failure line.

Why a ring: a long-lived server must not grow an unbounded trace file.
When the file exceeds ``max_bytes`` it is rotated once (``path`` →
``path.1``) and writing restarts — readers see the previous generation
plus the current one, so at least ``max_bytes`` of recent history always
survives, and disk use is bounded at ~2×``max_bytes``.

Record schema (one JSON object per line):

- begin:   ``{"ev": "B", "span": id, "parent": id|null, "name": str,
  "cat": str, "ts": epoch_s, "pid": int, "tid": int[, "attrs": {...}]}``
- end:     ``{"ev": "E", "span": id, "ts": epoch_s, "dur_s": float
  [, "attrs": {...}]}`` (``dur_s`` measured on ``perf_counter``, never
  by subtracting epoch stamps)
- instant: ``{"ev": "I", "name": str, "cat": str, "ts": epoch_s,
  "pid": int[, "attrs": {...}]}``
- ring marker: ``{"ev": "R", "gen": n, "ts": epoch_s}`` — first record
  of every post-rotation generation. When a reader's FIRST retained
  record is a marker, the generation before it was dropped by the ring
  (two rotations happened), so ends/parents referencing the truncated
  prefix are expected, not corruption.

:func:`validate_flight` checks exactly this schema (finite non-negative
times, every end matching an open begin, parent references to known
spans — both relaxed for records predating a truncated ring prefix) —
the CI gate's contract. :func:`to_chrome_trace` exports the
record as Chrome trace-event JSON loadable in Perfetto.

Instrumented code uses the module-level :func:`span`/:func:`event`/
:func:`begin_span`/:func:`end_span` helpers: ONE call site, three sinks.

- the flight record, when a recorder is active — installed explicitly
  (:func:`set_recorder`) or inherited from a supervisor via the
  ``TKNN_FLIGHT_RECORD`` env var, which is read when this module is
  imported and at every :func:`get_recorder`/:func:`set_recorder`, never
  per span (the ``maybe_beat`` convention: no mode flags at call sites);
- the profiler's trace: a ``jax.profiler.TraceAnnotation`` named
  ``knn:<cat>.<name>`` carrying the span's scalar attrs (``seq``,
  ``parent``, ``rows``...), so the program's spans sit on the host plane
  of the ``.xplane.pb`` on the device events' clock. Entered only when
  jax is ALREADY imported in the process (``sys.modules``); inert outside
  a profiler session;
- a metric, when the call site passes ``sink=``: a callable given the
  span's ``perf_counter`` seconds at its end (a counter's ``inc``, a
  histogram's ``observe``) — every duration on ``/metrics`` that belongs
  to a span comes from that span's own two clock reads. A call site that
  chains spans hands each its reading (``at=``): the end of one is the
  beginning of the next, so the chain partitions its parent exactly.

No jax import anywhere in this module.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import math
import os
import sys
import threading
import time

RECORDER_ENV = "TKNN_FLIGHT_RECORD"
TRACE_PREFIX = "knn:"  # every annotation of the program in a profiler trace


class FlightRecorder:
    """One append-only JSONL ring file; thread-safe; every record
    flushed on write (kernel-buffered data survives SIGKILL of the
    writer — only a machine crash loses it, and fsync-per-span would
    tax the serving hot path for a failure mode supervision cannot see
    anyway)."""

    def __init__(self, path: str, max_bytes: int = 8 << 20,
                 fresh: bool = False):
        if max_bytes < 4096:
            raise ValueError(f"max_bytes too small to be useful: {max_bytes}")
        self.path = str(path)
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._f = None
        self._gen = 0
        self._ids = itertools.count(1)
        self._open_t0: dict[int, float] = {}  # span id -> perf_counter
        self._stack = threading.local()
        if fresh:
            for p in (self.path, self.path + ".1"):
                try:
                    os.unlink(p)
                except OSError:
                    pass

    # -- io ---------------------------------------------------------------

    def _write(self, rec: dict) -> None:
        line = json.dumps(rec, separators=(",", ":"), default=str) + "\n"
        with self._lock:
            if self._f is None:
                d = os.path.dirname(os.path.abspath(self.path))
                os.makedirs(d, exist_ok=True)
                self._f = open(self.path, "a", encoding="utf-8")
            if self._f.tell() + len(line) > self.max_bytes:
                # rotate exactly one generation: bounded disk, and the
                # most recent max_bytes of history always survives
                self._f.close()
                os.replace(self.path, self.path + ".1")
                self._f = open(self.path, "a", encoding="utf-8")
                self._gen += 1
                # generation marker: when this is a reader's FIRST
                # retained record, the prefix before it rotated away —
                # validate_flight tolerates dangling ends/parents then
                self._f.write(json.dumps(
                    {"ev": "R", "gen": self._gen, "ts": time.time()},
                    separators=(",", ":"),
                ) + "\n")
            self._f.write(line)
            self._f.flush()  # the incremental-survival property

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    # -- span api ---------------------------------------------------------

    def _top(self):
        stack = getattr(self._stack, "v", None)
        return stack[-1] if stack else None

    def begin(self, name: str, cat: str = "", parent: int | None = None,
              **attrs) -> int:
        """``ts`` is when the record is written: for a span handed an
        earlier reading (:func:`begin_span`'s ``at=``) the begin lies
        ``dur_s`` before its end record's ``ts``."""
        sid = next(self._ids)
        rec = {
            "ev": "B",
            "span": sid,
            "parent": self._top() if parent is None else parent,
            "name": name,
            "cat": cat,
            "ts": time.time(),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if attrs:
            rec["attrs"] = attrs
        with self._lock:
            # under the recorder lock (host-lint H1): begin/end run on
            # every serving thread, and an unguarded dict write here
            # races the pop in end() on another thread
            self._open_t0[sid] = time.perf_counter()
        self._write(rec)
        return sid

    def end(self, sid: int, dur_s: float | None = None, **attrs) -> None:
        """``dur_s``: the span's seconds where its call site read the
        clock itself (``at=``); else measured here."""
        with self._lock:
            t0 = self._open_t0.pop(sid, None)
        if dur_s is None:
            dur_s = 0.0 if t0 is None else time.perf_counter() - t0
        rec = {
            "ev": "E",
            "span": sid,
            "ts": time.time(),
            "dur_s": dur_s,
        }
        if attrs:
            rec["attrs"] = attrs
        self._write(rec)

    def event(self, name: str, cat: str = "", **attrs) -> None:
        rec = {
            "ev": "I",
            "name": name,
            "cat": cat,
            "ts": time.time(),
            "pid": os.getpid(),
        }
        if attrs:
            rec["attrs"] = attrs
        self._write(rec)

    def _push(self, sid: int) -> None:
        stack = getattr(self._stack, "v", None)
        if stack is None:
            stack = self._stack.v = []
        stack.append(sid)

    def _pop(self) -> None:
        self._stack.v.pop()

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", **attrs):
        sid = self.begin(name, cat=cat, **attrs)
        self._push(sid)
        try:
            yield sid
        except BaseException as e:
            self._pop()
            self.end(sid, error=type(e).__name__)
            raise
        else:
            self._pop()
            self.end(sid)


# ---------------------------------------------------------------------------
# process-level recorder (explicit install wins over the env var)

# module lock for the recorder globals (host-lint H1): resolution runs
# on whichever thread installs or asks for the recorder, and an
# unguarded lazy construction here could open two FlightRecorder handles
# onto one path (duplicated, interleaved generations)
_reclock = threading.Lock()
_recorder: FlightRecorder | None = None
_env_recorder: FlightRecorder | None = None
# what the span helpers write to: resolved under _reclock, read bare
_active: FlightRecorder | None = None


def _resolve() -> FlightRecorder | None:
    """Publish the active recorder: the explicitly installed one, else
    one bound to ``TKNN_FLIGHT_RECORD`` (cached per path — supervisors
    point each worker at a fresh file), else None."""
    global _env_recorder, _active
    with _reclock:
        rec = _recorder
        if rec is None:
            path = os.environ.get(RECORDER_ENV)
            if path:
                if _env_recorder is None or _env_recorder.path != path:
                    _env_recorder = FlightRecorder(path)
                rec = _env_recorder
        _active = rec
        return rec


def set_recorder(rec: FlightRecorder | None) -> None:
    """Install (or clear) the process recorder explicitly — the serve
    CLI's ``--flight-record`` path. Overrides ``TKNN_FLIGHT_RECORD``."""
    global _recorder
    with _reclock:
        prev, _recorder = _recorder, rec
    _resolve()
    if prev is not None and prev is not rec:
        prev.close()


def get_recorder() -> FlightRecorder | None:
    """The active recorder, resolved anew (a ``TKNN_FLIGHT_RECORD`` set
    after import arms the helpers from here on)."""
    return _resolve()


_resolve()  # a supervised worker finds its flight file in its environment


_UNSAFE_IN_ANNOTATION = frozenset("#,=\n")  # the trace's own separators


class _Span:
    """One open span and where its end has to go."""

    __slots__ = ("rec", "sid", "ann", "sink", "t0", "chained")


def begin_span(name: str, cat: str = "", *, sink=None,
               parent: _Span | None = None, flight: bool = True,
               at: float | None = None, **attrs) -> _Span | None:
    """Begin a span that :func:`end_span` ends, possibly at a different
    call site (serve dispatch → retire). ``parent`` is another span's
    handle, open or ended (None: the enclosing :func:`span` of this
    thread, if any); ``sink`` gets the span's seconds at its end;
    ``flight=False`` keeps it out of the flight record (an idle wait
    twenty times a second would turn the ring over and push out what a
    kill diagnosis needs). ``at`` is the call site's own reading of its
    clock for the span's beginning — the one that ended the span before
    it — and obliges :func:`end_span` to be handed its end the same way:
    the sink and the flight record's ``dur_s`` then get the difference of
    the two readings (the annotation stays on the profiler's clock, from
    this call to that one). None when there is nowhere to write: no
    recorder, no jax, no sink."""
    rec = _active if flight else None
    jp = sys.modules.get("jax.profiler")
    if rec is None and jp is None and sink is None:
        return None
    h = _Span()
    h.rec, h.sink, h.sid, h.ann = rec, sink, None, None
    up = None if parent is None else parent.sid
    if rec is not None:
        if up is None:
            up = rec._top()
        h.sid = rec.begin(name, cat=cat, parent=up, **attrs)
    if jp is not None:
        ids = _scalars(attrs)
        if h.sid is not None:  # the flight record's ids, for a joint read
            ids["span"] = h.sid
            if up is not None:
                ids["parent"] = up
        h.ann = jp.TraceAnnotation(f"{TRACE_PREFIX}{cat}.{name}", **ids)
        h.ann.__enter__()
    h.chained = at is not None
    if h.chained:
        h.t0 = at
    else:
        h.t0 = time.perf_counter() if sink is not None else 0.0
    return h


def end_span(h: _Span | None, at: float | None = None, **attrs) -> None:
    """End a span; ``attrs`` are what was only known by now (a request's
    ``seq`` after admission, a batch's latency). ``at``: the call site's
    reading for the end of a span begun with one."""
    if h is None:
        return
    dur_s = None  # the flight record measures its own, unless chained
    if h.chained:
        if at is None:
            raise ValueError("a span begun with at= ends with at=")
        dur_s = at - h.t0
    if h.sink is not None:
        h.sink(dur_s if h.chained else time.perf_counter() - h.t0)
    if h.ann is not None:
        if attrs:
            h.ann.set_metadata(**_scalars(attrs))
        h.ann.__exit__(None, None, None)
    if h.rec is not None:
        h.rec.end(h.sid, dur_s=dur_s, **attrs)


def _scalars(attrs: dict) -> dict:
    """The attrs an annotation can carry: whole numbers and plain words
    (the trace encodes ``name#key=value,...#``)."""
    return {
        k: v for k, v in attrs.items()
        if type(v) is int
        or (type(v) is str and _UNSAFE_IN_ANNOTATION.isdisjoint(v))
    }


def event(name: str, cat: str = "", **attrs) -> None:
    rec = _active
    if rec is not None:
        rec.event(name, cat=cat, **attrs)


@contextlib.contextmanager
def span(name: str, cat: str = "", *, sink=None,
         parent: _Span | None = None, flight: bool = True, **attrs):
    """A span around a block; spans begun inside it on this thread name
    it as their flight-record parent. An exception ends it with
    ``error=<type>``."""
    h = begin_span(name, cat, sink=sink, parent=parent, flight=flight,
                   **attrs)
    if h is None:
        yield None
        return
    if h.rec is not None:
        h.rec._push(h.sid)
    failed = {}
    try:
        yield h
    except BaseException as e:
        failed = {"error": type(e).__name__}
        raise
    finally:
        if h.rec is not None:
            h.rec._pop()
        end_span(h, **failed)


# ---------------------------------------------------------------------------
# reading / validation / export


def read_flight(path: str) -> list[dict]:
    """Every record of a flight file (previous ring generation first).
    A torn final line — the one a SIGKILL can produce mid-write — is
    skipped; a torn line anywhere else is impossible under the
    write+flush protocol and therefore *reported* by validate_flight,
    not silently dropped here (unparseable interior lines are kept as
    ``{"ev": "?", "raw": ...}`` markers)."""
    out: list[dict] = []
    for p in (path + ".1", path):
        try:
            with open(p, encoding="utf-8") as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                doc = json.loads(line)
            except ValueError:
                if p == path and i == len(lines) - 1:
                    continue  # torn tail: the kill landed mid-write
                doc = {"ev": "?", "raw": line[:200]}
            out.append(doc if isinstance(doc, dict)
                       else {"ev": "?", "raw": str(doc)[:200]})
    return out


def reconstruct_spans(records: list[dict]) -> tuple[list[dict], list[dict]]:
    """(spans, events): each span dict carries ``name/cat/ts/pid/attrs``
    from its begin record plus ``dur_s``/``end_attrs`` when closed
    (``dur_s`` is None for spans still open at the end of the record —
    the kill diagnosis). Span identity is (pid, span id): records from
    a supervisor and several workers may share one file."""
    spans: dict[tuple, dict] = {}
    # span id -> stack of still-open keys with that id: E records carry
    # no pid, and matching the newest open candidate this way keeps the
    # whole pass O(records) (a large ring file holds ~100k spans)
    open_by_sid: dict[int, list[tuple]] = {}
    events: list[dict] = []
    for rec in records:
        ev = rec.get("ev")
        if ev == "B":
            key = (rec.get("pid"), rec.get("span"))
            spans[key] = {
                "span": rec.get("span"),
                "parent": rec.get("parent"),
                "name": rec.get("name"),
                "cat": rec.get("cat", ""),
                "ts": rec.get("ts"),
                "pid": rec.get("pid"),
                "attrs": rec.get("attrs", {}),
                "dur_s": None,
                "end_attrs": None,
            }
            open_by_sid.setdefault(rec.get("span"), []).append(key)
        elif ev == "E":
            stack = open_by_sid.get(rec.get("span"))
            if stack:
                key = stack.pop()
                spans[key]["dur_s"] = rec.get("dur_s")
                spans[key]["end_attrs"] = rec.get("attrs", {})
        elif ev == "I":
            events.append(rec)
    return list(spans.values()), events


def _finite_nonneg(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) \
        and math.isfinite(v) and v >= 0


def validate_flight(records: list[dict]) -> list[str]:
    """Schema problems in a flight record, empty when clean — the CI
    gate's checker. Checks per record: known ``ev`` kind, required
    fields, finite non-negative timestamps and durations (NaN/negative
    durations are exactly the corruption a misparsed trace produces),
    every end matching a begun-and-still-open span, and parent
    references pointing at spans already begun (well-formed nesting).

    When the FIRST retained record is a ring marker (``ev: "R"``), the
    generation before it was dropped by the ring — a healthy long-lived
    server, not corruption — so ends and parent references that point
    into the truncated prefix are tolerated rather than reported."""
    problems: list[str] = []
    begun: dict[tuple, bool] = {}  # (pid, span) -> still open
    open_by_sid: dict[int, list[tuple]] = {}  # O(records), as above
    truncated = bool(records) and records[0].get("ev") == "R"
    for i, rec in enumerate(records):
        where = f"record {i}"
        ev = rec.get("ev")
        if ev == "?":
            problems.append(f"{where}: unparseable line {rec.get('raw')!r}")
            continue
        if ev not in ("B", "E", "I", "R"):
            problems.append(f"{where}: unknown ev {ev!r}")
            continue
        if not _finite_nonneg(rec.get("ts")):
            problems.append(f"{where}: bad ts {rec.get('ts')!r}")
        if ev == "R":
            gen = rec.get("gen")
            if not isinstance(gen, int) or gen < 1:
                problems.append(f"{where}: ring marker with bad gen {gen!r}")
        elif ev == "B":
            if not rec.get("name"):
                problems.append(f"{where}: begin without name")
            sid, pid = rec.get("span"), rec.get("pid")
            if not isinstance(sid, int):
                problems.append(f"{where}: begin without span id")
                continue
            if begun.get((pid, sid)) is not None:
                problems.append(f"{where}: duplicate span id {sid} (pid {pid})")
            parent = rec.get("parent")
            if parent is not None and (pid, parent) not in begun \
                    and not truncated:
                problems.append(
                    f"{where}: parent {parent} of span {sid} never began"
                )
            begun[(pid, sid)] = True
            open_by_sid.setdefault(sid, []).append((pid, sid))
        elif ev == "E":
            sid = rec.get("span")
            stack = open_by_sid.get(sid)
            if stack:
                begun[stack.pop()] = False
            elif not truncated:
                problems.append(
                    f"{where}: end for span {sid!r} that is not open"
                )
            if not _finite_nonneg(rec.get("dur_s")):
                problems.append(
                    f"{where}: bad dur_s {rec.get('dur_s')!r} "
                    f"for span {sid!r}"
                )
        else:  # I
            if not rec.get("name"):
                problems.append(f"{where}: event without name")
    return problems


def summarize_flight(records: list[dict], tail: int = 3) -> dict | None:
    """The compact form a supervisor banks next to a failure line:
    record/span/event counts, the names of spans left OPEN at death
    (the diagnosis), and the last few raw records. None when the worker
    recorded nothing."""
    if not records:
        return None
    spans, events = reconstruct_spans(records)
    open_spans = [s for s in spans if s["dur_s"] is None]
    return {
        "records": len(records),
        "spans_complete": len(spans) - len(open_spans),
        "events": len(events),
        "open_spans": [
            {"name": s["name"], "cat": s["cat"], "attrs": s["attrs"]}
            for s in open_spans
        ],
        "last": records[-tail:],
    }


def to_chrome_trace(records: list[dict]) -> dict:
    """Chrome trace-event JSON (the ``traceEvents`` array form) loadable
    in Perfetto / chrome://tracing. Closed spans become complete ``X``
    events; spans still open at the end of the record become dangling
    ``B`` events — Perfetto renders them to the end of the trace, which
    is exactly the right picture of a killed worker."""
    trace: list[dict] = []
    spans, events = reconstruct_spans(records)
    for s in spans:
        base = {
            "name": s["name"],
            "cat": s["cat"] or "default",
            "pid": s["pid"] or 0,
            "tid": 0,
            "ts": (s["ts"] or 0.0) * 1e6,
            "args": s["attrs"] or {},
        }
        if s["dur_s"] is None:
            trace.append({**base, "ph": "B"})
        else:
            args = dict(base["args"])
            if s["end_attrs"]:
                args.update(s["end_attrs"])
            trace.append(
                {**base, "ph": "X", "dur": s["dur_s"] * 1e6, "args": args}
            )
    for e in events:
        trace.append({
            "name": e.get("name"),
            "cat": e.get("cat") or "default",
            "pid": e.get("pid") or 0,
            "tid": 0,
            "ts": (e.get("ts") or 0.0) * 1e6,
            "ph": "i",
            "s": "p",
            "args": e.get("attrs", {}),
        })
    trace.sort(key=lambda r: r["ts"])
    return {"traceEvents": trace, "displayTimeUnit": "ms"}
