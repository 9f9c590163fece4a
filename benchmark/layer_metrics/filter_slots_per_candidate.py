"""The gather regime's padding over the window: candidate slots its
programs read (``filter_gather_slots_total``: every row's candidates
padded to its bucket, every dispatch to its height) over the candidates
the rows had (``filter_candidates_total``). At least 1. Source: program
counter."""


def read(run: dict):
    delta = run.get("window_metrics_delta") or {}
    cands = delta.get("filter_candidates_total", 0.0)
    slots = delta.get("filter_gather_slots_total")
    if slots is None or cands <= 0:
        return None
    return slots / cands
