"""Resident bytes a row slot of the served index: the program's gauge
``serve_index_rest_bytes_per_row`` (the tile stack and its id and norm
planes over the stack's slots), read from ``/metrics`` after the window.
136 for 128-d bytes (128 + 4 + 4); a float32 stack creeping back reads
520. None where the program has no such gauge (the parent commit). Source:
program counter."""

GAUGE = "serve_index_rest_bytes_per_row"


def read(run: dict):
    value = (run.get("u8") or {}).get("rest_bytes_per_row")
    return None if value is None else float(value)
