#!/usr/bin/env python
"""What the serving path's instruments cost a request and a batch when
nothing records (no flight record, no profiler session): the handler's
``request`` span with its six phases and the occupancy counter, against the
one ``request`` span a handler kept before; and what a batch adds (the
coalescer's ``ripe_s``, the lag histogram, the pump's ``other`` phase).

    python scripts/bench_request_spans.py          # jax loaded: annotations
                                                   # are entered, and inert
    python scripts/bench_request_spans.py --no-jax

A CPU timing of host code: microseconds of the handler and pump threads, not
a device number (PERF.md §6, PR 36)."""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def per_call_us(f, n: int) -> float:
    f()
    t0 = time.perf_counter()
    for _ in range(n):
        f()
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--no-jax", action="store_true")
    p.add_argument("-n", type=int, default=20000)
    args = p.parse_args(argv)
    if not args.no_jax:
        import jax.profiler  # noqa: F401 — spans annotate when it is loaded

    from mpi_knn_tpu.frontend.coalesce import Coalescer
    from mpi_knn_tpu.frontend.server import Occupancy, _Phases
    from mpi_knn_tpu.obs import metrics, spans

    clock = time.monotonic
    occupancy = Occupancy(clock)

    def request_seconds():
        return metrics.get_registry().histogram(
            "frontend_request_seconds").observe

    def before():  # what a /query handler kept until PR 36
        span = spans.begin_span("request", cat="http", sink=request_seconds())
        spans.end_span(span, seq=7, rows=3, status=200)

    def now():  # Handler.do_POST / _do_query / _answer_query, the 200 path
        with occupancy:
            t0 = clock()
            request = spans.begin_span("request", cat="http", at=t0,
                                       sink=request_seconds())
            phases = _Phases(clock, request, "query", t0)
            phases.next("admit")
            phases.attrs = {"seq": 7}
            phases.next("await", at=clock())
            done_s, woke = clock(), clock()
            phases.next("wake", at=done_s, us=int((woke - done_s) * 1e6))
            phases.next("encode", at=woke)
            phases.next("write")
            spans.end_span(request, at=phases.end(), seq=7, rows=3,
                           status=200)

    was, is_ = per_call_us(before, args.n), per_call_us(now, args.n)
    print(f"request: {was:.1f} us before, {is_:.1f} us now: "
          f"+{is_ - was:.1f} us a request (budget 50)")

    coalescer = Coalescer(max_batch_rows=1024, max_wait_s=0.002)
    for tenant in range(6):  # the bulk mix: six full batches pending
        coalescer.admit(f"t{tenant}", None, 1024, now=float(tenant))
    covered = [0.0]

    def batch():  # what Frontend._dispatch and a pump turn add
        lag_s = max(0.0, 9.0 - coalescer._filled_s())
        metrics.get_registry().histogram(
            "frontend_dispatch_lag_seconds").observe(lag_s)
        for _ in range(6):  # the phase sinks' running sum, six spans a batch
            covered[0] += 1e-3
        metrics.get_registry().counter(
            "serve_batch_phase_seconds_total",
            labels={"phase": "other"}).inc(max(0.0, 7e-3 - covered[0]))
        covered[0] = 0.0

    print(f"batch: +{per_call_us(batch, args.n):.1f} us a batch (budget 20)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
