"""Device-time attribution — one answer to "what did the device actually
spend its time on, per batch, with provenance".

Built on :mod:`mpi_knn_tpu.obs.xplane`: parse every ``.xplane.pb`` a
profiled run wrote, pick the plane that carries the device work, and
reduce it to the per-category busy split the serve report embeds next to
its p50/p99 — matmul / sort-topk / collective / copy / dma-wait /
other, plus the collective-under-compute overlap fraction (the measured
form of lint rule R1's "overlap achieved", see ``analysis/README.md``).

The ``dma-wait`` category is for a kernel that issues async copies
itself and stalls on their semaphores, which the trace shows as explicit
wait events (no kernel in the tree moves a block over ICI that way; the
ring's hops are ``collective-permute``s). Such stalls are their own
bucket — never ``matmul`` — so that ``overlap_fraction`` does not count
the time a transfer was NOT hidden as compute it hid under. The report
surfaces the bucket both in ``busy_ms`` and as the top-level
``dma_wait_ms``.

Invariant the acceptance test pins: the per-category milliseconds sum to
the total busy time (every event carries exactly one category), so a
report whose categories sum past ``busy_total_ms`` is a parser bug, not
a measurement.
"""

from __future__ import annotations

from mpi_knn_tpu.obs.xplane import analyze, find_xplanes, parse_xplane


def _busy_total(plane_report: dict) -> float:
    return round(sum(plane_report["busy_ms_by_category"].values()), 3)


def pick_device_plane(planes: dict) -> str | None:
    """The plane to attribute: prefer real device planes (named
    '/device:...'), then the busiest plane overall — CPU traces put the
    op events on a '/host:CPU' plane, which is the right (only) story
    there."""
    if not planes:
        return None
    device = [p for p in planes if "/device:" in p]
    pool = device or list(planes)
    return max(pool, key=lambda p: _busy_total(planes[p]))


def attribute_trace(trace_dir: str, top: int = 10) -> dict:
    """Per-category device-time split for one profiled run.

    Returns a report-embeddable dict: ``busy_ms`` (category → ms, over
    the chosen plane), ``busy_total_ms`` (their sum), the collective
    totals, ``overlap_fraction`` (collective time hidden under matmul ÷
    collective time; the async start/done span form when the trace has
    one, else the busy-interval form; None when the trace has no
    collectives), ``top_ops_ms``, and the plane/file census. A run with
    no parseable events returns ``{"error": ...}`` instead of a
    zero-filled split posing as a measurement."""
    files = find_xplanes(trace_dir)
    if not files:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    planes: dict = {}
    casualties = []
    for f in files:
        try:
            for plane, rep in analyze(parse_xplane(f), top=top).items():
                # same plane across files (multi-capture dirs): keep the
                # busier one rather than silently merging disjoint runs
                if plane not in planes or \
                        _busy_total(rep) > _busy_total(planes[plane]):
                    planes[plane] = rep
        except (ValueError, OSError) as e:
            casualties.append({"file": f, "error": f"{type(e).__name__}: {e}"})
    chosen = pick_device_plane(planes)
    if chosen is None:
        return {
            "error": f"no events parsed from {len(files)} xplane file(s)",
            "casualties": casualties,
        }
    rep = planes[chosen]
    coll = rep["collective_total_ms"]
    span = rep["collective_span_ms"]
    if span > 0:
        frac = rep["collective_span_overlapped_with_matmul_ms"] / span
    elif coll > 0:
        frac = rep["collective_overlapped_with_matmul_ms"] / coll
    else:
        frac = None
    out = {
        "plane": chosen,
        "planes_seen": sorted(planes),
        "busy_ms": dict(rep["busy_ms_by_category"]),
        "busy_total_ms": _busy_total(rep),
        "collective_ms": coll,
        "collective_overlapped_with_matmul_ms":
            rep["collective_overlapped_with_matmul_ms"],
        "collective_span_ms": span,
        "collective_span_overlapped_with_matmul_ms":
            rep["collective_span_overlapped_with_matmul_ms"],
        "overlap_fraction": None if frac is None else round(frac, 4),
        # the fused rotation's in-kernel semaphore stalls, split out of
        # compute (0.0 on xla-form and CPU traces — absent wait events,
        # not an unmeasured zero: the category always exists)
        "dma_wait_ms": rep["busy_ms_by_category"].get("dma-wait", 0.0),
        "top_ops_ms": dict(rep["top_ops_ms"]),
    }
    if casualties:
        out["casualties"] = casualties
    return out
