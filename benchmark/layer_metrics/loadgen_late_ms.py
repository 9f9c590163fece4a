"""95th percentile of (actual send - due time) over the window's requests:
a starved generator must not read as a fast server. Source: host clock, in
the benchmark's own generator."""


def read(run: dict):
    log = run.get("loadgen")
    if not log or not log.get("late_s"):
        return None
    late = sorted(log["late_s"])
    return 1e3 * late[min(len(late) - 1, int(0.95 * len(late)))]
