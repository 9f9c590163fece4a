"""Share of the traced window in which no operation ran on the device,
in the cells whose end-to-end metric is a rate. Source: device trace."""

from benchmark.trace import idle_pct


def read(run: dict):
    return idle_pct(run.get("trace"))
