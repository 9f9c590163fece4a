"""XPlane (``.xplane.pb``) wire-format parser and per-op aggregation —
the library behind ``scripts/trace_ops.py`` and the serve profiler's
device-time attribution.

``jax.profiler.trace`` writes an XSpace protobuf. The installed
tensorboard-plugin-profile converter is incompatible with the installed
TF, so this parses the protobuf WIRE FORMAT directly with the tiny
subset of the XPlane schema we need (message/field numbers from the
public ``tsl/profiler/protobuf/xplane.proto``)::

    XSpace.planes = 1          XPlane.name = 2, .lines = 3,
                               .event_metadata = 4 (map<int64, XEventMetadata>)
    XLine.name = 2, .timestamp_ns = 3, .events = 4
    XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3
    XEventMetadata.id = 1, .name = 2, .display_name = 3, .stats = 5
    XPlane.stat_metadata = 5 (map<int64, XStatMetadata>)
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7

An event whose metadata carries the ``tf_op`` stat — on a TPU's device
planes the HLO ``op_name``, which holds the program's ``knn.*`` scopes
(``jax.named_scope``) — gets it as ``scope``; the event's name there is
the bare HLO line, which has no scope in it.

Unknown fields (every other number the real schema carries) are skipped
by wire type, exactly as a generated proto reader would. Truncated or
garbage input raises :class:`ParseError` — a silent misparse here would
corrupt every attribution number downstream, which is why this module
has its own unit tests over hand-built wire-format fixtures
(``tests/test_obs.py``).

:func:`analyze` aggregates parsed events per plane: top ops by total
self-duration with a category guess (matmul / sort-topk / collective /
copy / dma-wait / other), busy time per category, and two
collective-under-matmul overlap metrics (busy-interval overlap, plus
the async ``-start``/``-done`` span overlap that credits in-flight DMA
time hidden under compute — the quantitative form of lint rule R1's
"overlap achieved"). The ``dma-wait`` category splits the fused
kernel's in-kernel semaphore stalls out of compute so the fused
rotation's overlap numbers stay honest (the stall IS the un-hidden
remainder of the transfer).
"""

from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict


class ParseError(ValueError):
    """Malformed xplane wire format (truncated varint, bad wire type,
    length running past the buffer)."""


def _varint(buf: memoryview, i: int):
    x = 0
    s = 0
    n = len(buf)
    while True:
        if i >= n:
            raise ParseError(f"truncated varint at offset {i}")
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if not b & 0x80:
            return x, i
        s += 7
        if s > 63:
            raise ParseError(f"varint overruns 64 bits at offset {i}")


def _fields(buf: memoryview):
    """Yield (field_number, wire_type, value) over a message buffer.
    value: int for varint/fixed, memoryview for length-delimited."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            if i + 8 > n:
                raise ParseError(f"truncated fixed64 at offset {i}")
            v = int.from_bytes(buf[i: i + 8], "little")
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            if i + ln > n:
                raise ParseError(
                    f"length-delimited field overruns buffer at offset {i}"
                )
            v = buf[i: i + ln]
            i += ln
        elif wt == 5:
            if i + 4 > n:
                raise ParseError(f"truncated fixed32 at offset {i}")
            v = int.from_bytes(buf[i: i + 4], "little")
            i += 4
        else:  # groups (3/4) don't appear in xplane
            raise ParseError(f"unsupported wire type {wt} at offset {i}")
        yield fno, wt, v


SCOPE_STAT = "tf_op"  # the stat that holds an operation's HLO op_name


def _stat(buf: memoryview):
    """(stat metadata id, its string or the id it refers to) of one XStat."""
    sid, value = None, None
    for sf, _, sv in _fields(buf):
        if sf == 1:
            sid = sv
        elif sf == 5:
            value = bytes(sv).decode("utf-8", "replace")
        elif sf == 7:
            value = sv
    return sid, value


def parse_xplane_bytes(raw: bytes) -> list[dict]:
    """Parse one serialized XSpace; returns
    ``[{plane, line, name, start_ps, dur_ps}]`` for every event, with
    ``scope`` where the event's metadata has the ``tf_op`` stat."""
    out = []
    for fno, _, plane_buf in _fields(memoryview(raw)):
        if fno != 1:  # XSpace.planes
            continue
        plane_name = ""
        lines = []
        meta = {}
        meta_stats = {}  # event metadata id -> [(stat metadata id, value)]
        stat_names = {}  # stat metadata id -> name
        for pf, _, pv in _fields(plane_buf):
            if pf == 2:
                plane_name = bytes(pv).decode("utf-8", "replace")
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:  # map entry: key=1 varint, value=2 XEventMetadata
                mid, mname, stats = None, "", []
                for mf, _, mv in _fields(pv):
                    if mf == 1:
                        mid = mv
                    elif mf == 2:
                        for ef, _, ev in _fields(mv):
                            if ef == 2 and not mname:
                                mname = bytes(ev).decode("utf-8", "replace")
                            elif ef == 3:  # display_name wins if present
                                mname = bytes(ev).decode("utf-8", "replace")
                            elif ef == 5:
                                stats.append(_stat(ev))
                if mid is not None:
                    meta[mid] = mname
                    meta_stats[mid] = stats
            elif pf == 5:  # map entry: key=1 varint, value=2 XStatMetadata
                for mf, _, mv in _fields(pv):
                    if mf == 2:
                        sid, sname = None, ""
                        for ef, _, ev in _fields(mv):
                            if ef == 1:
                                sid = ev
                            elif ef == 2:
                                sname = bytes(ev).decode("utf-8", "replace")
                        stat_names[sid] = sname
        scopes = {}
        for mid, stats in meta_stats.items():
            for sid, value in stats:
                if stat_names.get(sid) == SCOPE_STAT:
                    # a string, or a reference to another stat's name
                    scopes[mid] = (value if isinstance(value, str)
                                   else stat_names.get(value, ""))
        for line_buf in lines:
            line_name = ""
            ts_ns = 0
            events = []
            for lf, _, lv in _fields(line_buf):
                if lf == 2:
                    line_name = bytes(lv).decode("utf-8", "replace")
                elif lf == 3:
                    ts_ns = lv
                elif lf == 4:
                    events.append(lv)
            for ev_buf in events:
                mid = None
                off_ps = 0
                dur_ps = 0
                for ef, _, ev in _fields(ev_buf):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        off_ps = ev
                    elif ef == 3:
                        dur_ps = ev
                out.append(
                    {
                        "plane": plane_name,
                        "line": line_name,
                        "name": meta.get(mid, f"meta:{mid}"),
                        "start_ps": ts_ns * 1000 + off_ps,
                        "dur_ps": dur_ps,
                    }
                )
                if scopes.get(mid):
                    out[-1]["scope"] = scopes[mid]
    return out


def parse_xplane(path: str) -> list[dict]:
    """:func:`parse_xplane_bytes` over a file (``.gz`` transparently)."""
    raw = open(path, "rb").read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return parse_xplane_bytes(raw)


CATEGORIES = (
    # dma-wait FIRST: a kernel that issues async copies itself stalls on
    # semaphore waits that the TensorCore trace emits as explicit wait
    # events. Those stalls are COMM time,
    # not compute — if the wait markers fell through to "matmul" (many
    # spell the kernel or fusion they stall inside), every comm stall
    # would inflate the measured overlap_fraction (the R1 dual) by
    # counting blocked-on-wire time as compute the transfer hid under.
    ("dma-wait", ("dma-wait", "dma_wait", "dmawait", "wait-semaphore",
                  "semaphore-wait", "sem-wait", "semaphore_wait",
                  "wait_semaphore", "wait-dma", "wait_dma")),
    ("collective", ("collective-permute", "all-reduce", "all-gather",
                    "all-to-all", "ppermute", "reduce-scatter",
                    "collective")),
    ("sort-topk", ("sort", "top-k", "topk", "partial-reduce", "approx")),
    ("matmul", ("dot", "convolution", "matmul", "fusion")),
    ("copy", ("copy", "transpose", "reshape", "dynamic-slice",
              "dynamic-update-slice", "pad", "concatenate")),
)


def categorize(name: str) -> str:
    low = name.lower()
    for cat, keys in CATEGORIES:
        if any(k in low for k in keys):
            return cat
    return "other"


def overlap_ps(a: list, b: list) -> int:
    """Total overlap between two interval lists [(start, end)] (merged)."""

    def merge(iv):
        iv = sorted(iv)
        out = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], e))
            else:
                out.append((s, e))
        return out

    a, b = merge(a), merge(b)
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def analyze(events: list, top: int = 15):
    planes = defaultdict(list)
    for ev in events:
        planes[ev["plane"]].append(ev)
    report = {}
    for plane, evs in planes.items():
        # device planes are named like '/device:TPU:0'; XLA op lines carry
        # the per-op events (line names vary by backend: 'XLA Ops', 'Steps',
        # thread ids on CPU) — aggregate every line, self-duration only
        by_op = defaultdict(int)
        by_cat = defaultdict(int)
        cat_iv = defaultdict(list)
        for ev in evs:
            if not ev["dur_ps"]:
                continue
            by_op[ev["name"]] += ev["dur_ps"]
            cat = categorize(ev["name"])
            by_cat[cat] += ev["dur_ps"]
            cat_iv[cat].append(
                (ev["start_ps"], ev["start_ps"] + ev["dur_ps"])
            )
        if not by_op:
            continue
        coll_under_mm = overlap_ps(
            cat_iv.get("collective", []), cat_iv.get("matmul", [])
        )
        # Async collectives on TPU appear as '<op>-start.N' / '<op>-done.N'
        # event pairs; the in-flight DMA time is the GAP between them and is
        # attributed to neither event, so the busy-interval overlap above
        # under-reports hidden transfer. Pair starts with dones by name stem
        # and occurrence order and measure the full span instead.
        starts, dones = defaultdict(list), defaultdict(list)
        for ev in evs:
            if not ev["dur_ps"] or categorize(ev["name"]) != "collective":
                continue
            low = ev["name"].lower()
            iv = (ev["start_ps"], ev["start_ps"] + ev["dur_ps"])
            if "-start" in low:
                starts[low.replace("-start", "", 1)].append(iv)
            elif "-done" in low:
                dones[low.replace("-done", "", 1)].append(iv)
        spans = []
        for stem, ss in starts.items():
            ds = dones.get(stem, [])
            if len(ds) != len(ss):
                # a trace cut mid-flight (or a zero-duration done dropped by
                # the busy filter) breaks order-based pairing — a misaligned
                # zip would bridge unrelated rounds and count ordinary
                # compute as hidden transfer. Under-report instead.
                continue
            for (s0, _), (_, d1) in zip(sorted(ss), sorted(ds)):
                if d1 > s0:
                    spans.append((s0, d1))
        span_under_mm = overlap_ps(spans, cat_iv.get("matmul", []))
        report[plane] = {
            "busy_ms_by_category": {
                k: round(v / 1e9, 3) for k, v in sorted(by_cat.items())
            },
            "collective_total_ms": round(
                sum(e - s for s, e in cat_iv.get("collective", [])) / 1e9, 3
            ),
            "collective_overlapped_with_matmul_ms": round(
                coll_under_mm / 1e9, 3
            ),
            # span metrics are 0 when the trace has no async start/done
            # pairs (sync collectives, or CPU traces)
            "collective_span_ms": round(
                sum(e - s for s, e in spans) / 1e9, 3
            ),
            "collective_span_overlapped_with_matmul_ms": round(
                span_under_mm / 1e9, 3
            ),
            "top_ops_ms": {
                k: round(v / 1e9, 3)
                for k, v in sorted(
                    by_op.items(), key=lambda kv: -kv[1]
                )[:top]
            },
        }
    return report


def find_xplanes(path: str) -> list[str]:
    if os.path.isfile(path):
        return [path]
    pats = ["**/*.xplane.pb", "**/*.xplane.pb.gz"]
    out = []
    for p in pats:
        out.extend(glob.glob(os.path.join(path, p), recursive=True))
    return sorted(out)
