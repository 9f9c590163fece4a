"""Mean wait for the index's mutation lock, over the window's batch
dispatches and writes together: ``mutation_lock_wait_seconds_total`` (both
sides: what a batch dispatch waited for a write's dispatch and commit, and
what a write waited for a batch's dispatch) over
``mutation_lock_waits_total``. Source: program counter."""

SECONDS = "mutation_lock_wait_seconds_total"
WAITS = "mutation_lock_waits_total"


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    seconds = [v for n, v in delta.items() if n.startswith(SECONDS)]
    waits = sum(v for n, v in delta.items() if n.startswith(WAITS))
    if not seconds or waits <= 0:
        return None
    return 1e3 * sum(seconds) / waits
