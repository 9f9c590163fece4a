"""Share of the window the caller spent inside insert and delete steps (a
step: its first request sent to its last acknowledged), on the driver's
clock; the rest is search steps. Source: host clock
(``run["stream"]``)."""


def read(run: dict):
    stream = run.get("stream")
    if not stream or stream["window_s"] <= 0:
        return None
    steps = stream["step_s"]
    return 100.0 * (steps["insert"] + steps["delete"]) / stream["window_s"]
