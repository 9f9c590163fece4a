"""Share of the window in which no request was inside the server: 100 x
``empty`` / (``empty`` + ``occupied``) of
``frontend_occupancy_seconds_total{state}``, as the difference of the two
``/metrics`` reads around the window (each settles the counter). The
program counts ``occupied`` while at least one POST of /query, /upsert or
/delete is inside its handler, on one clock read at each crossing, so the
two states add up to the window's length. It is the callers' side of
``device_idle_pct.tput``: idle time of the device in which the server had
nothing to work on, which no change to the program can win. Source: program
counter."""

SAMPLE = 'frontend_occupancy_seconds_total{state="%s"}'


def read(run: dict):
    delta = run.get("window_metrics_delta")
    if not delta or SAMPLE % "occupied" not in delta:
        return None
    empty = delta.get(SAMPLE % "empty", 0.0)
    whole = empty + delta[SAMPLE % "occupied"]
    if whole <= 0:
        return None
    return 100.0 * empty / whole
