"""Time the serving process's interpreter spent in garbage collections of
generation 1 and 2 during the window, in the cells whose end-to-end metric
is a rate: ``gc_pause_ms.lat``'s reading (that file says how the program
times a collection). Source: program counter."""

from benchmark.harness import load_by_path

read = load_by_path("layer_metrics", "gc_pause_ms.lat").read
