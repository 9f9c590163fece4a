"""``tile_roofline``'s reading (that file says what it is) in the streaming
cell, whose throughput is an end-to-end metric of its own
(``stream_rows_per_s``, PR 53): a per-layer metric moves one end-to-end
metric, so the cell reads the same quantity under this name. Source: as
there."""

from benchmark.harness import load_by_path

read = load_by_path("layer_metrics", "tile_roofline").read
