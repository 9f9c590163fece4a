"""Seconds the serving pump's batch cycles overran their running medians
during the window, in the cell whose end-to-end metrics are latencies: 1e3 x
the sum over ``where`` of ``serve_batch_overrun_seconds_total``, as the
difference of the two ``/metrics`` reads around the window. The program
judges every retired batch (``ServeSession._judge``): the pump thread's busy
seconds from one retire to the next, against the running median of the last
32 cycles of that bucket height; a cycle over it by more than half and by 50
ms is an overrun, and the counter takes the excess. A clean window reads
0.0: the family appears with its first overrun, so what says that the
program judges at all is ``serve_pump_cpu_seconds_total``, which moves at
every retire (absent, as at a commit before the record: None). What a
nonzero reading was is on the run's earlier lines, one WARNING line an
overrun (``overrun serve seq= where= ...``). Source: program counter."""

FAMILY = "serve_batch_overrun_seconds_total{"
JUDGES = "serve_pump_cpu_seconds_total"


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta or JUDGES not in delta:
        return None
    return 1e3 * sum(v for name, v in delta.items()
                     if name.startswith(FAMILY))
