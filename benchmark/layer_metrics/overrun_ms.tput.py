"""Seconds the serving pump's batch cycles overran their running medians
during the window, in the cells whose end-to-end metric is a rate:
``overrun_ms.lat``'s reading (that file says how the program judges a
cycle). In a saturated loop a cycle is the batch period, so the sum is the
time the window lost to stalls: over ``window_s`` it is the share of
``rows_per_s`` they took. Source: program counter."""

from benchmark.harness import load_by_path

read = load_by_path("layer_metrics", "overrun_ms.lat").read
