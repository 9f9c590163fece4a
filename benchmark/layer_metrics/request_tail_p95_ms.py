"""95th percentile of the latency of the window's requests, each from the
moment it was due: the tail that the coalescer's waits and the queue behind
a batch in flight decide. Per layer and not end to end because no bound up
to 0.1 holds it (PERF.md, section 6). Source: host clock, in the benchmark's
own generator."""

import numpy as np


def read(run: dict):
    log = run.get("loadgen")
    if not log or not log.get("latency_s"):
        return None
    return 1e3 * float(np.percentile(np.asarray(log["latency_s"]), 95))
