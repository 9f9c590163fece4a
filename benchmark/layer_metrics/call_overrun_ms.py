"""Seconds the periods of consecutive ``all_knn`` calls overran their
running median, over the whole run: 1e3 x the sum over ``where`` of the
program's ``knn_call_overrun_seconds_total``, read from the registry of this
process (the driver makes the calls itself, as ``drivers/allknn_ring.py
ring_counters`` reads ``ring_*``). The program judges the entry-to-entry
period of calls that hit one prepared corpus against the median of the last
32 (``mpi_knn_tpu/api.py _CallWatch``): ``where="dispatch"`` when the call's
own host span held the excess, ``"outside"`` when it lay after the return —
the device, its runtime, or the caller. In a ``--trace 1`` run the caller is
away twice by design, while the profiler starts and while it stops and
writes its file between two calls: those two read here too. 0.0 where the
program timed calls (``knn_call_host_seconds``) and none overran; None at a
commit before the record. Source: program counter."""

FAMILY = "knn_call_overrun_seconds_total{"
JUDGES = "knn_call_host_seconds"


def read(run: dict):
    from mpi_knn_tpu.obs.metrics import get_registry

    snap = get_registry().snapshot()["metrics"]
    if JUDGES not in snap:
        return None
    return 1e3 * sum(m["value"] for name, m in snap.items()
                     if name.startswith(FAMILY))
