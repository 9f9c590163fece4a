"""What padding every bucket to the largest partition costs every probe,
over the window: bucket slots the probe gathers read
(``ivf_probe_slots_total``: query rows x nprobe x bucket_cap) over the live
corpus rows among them (``ivf_probe_live_rows_total``). At least 1; 1 / the
probed buckets' fill. Source: program counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    live = delta.get("ivf_probe_live_rows_total", 0.0)
    slots = delta.get("ivf_probe_slots_total")
    if slots is None or live <= 0:
        return None
    return slots / live
