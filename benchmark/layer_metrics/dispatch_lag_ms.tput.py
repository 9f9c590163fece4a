"""Mean time a batch of the window had been dispatchable when the pump
handed it to the engine, in the cells whose end-to-end metric is a rate:
``dispatch_lag_ms.lat``'s reading (``frontend_dispatch_lag_seconds`` sum
over count; that file says how the program measures it). In a closed loop
that keeps the engine's dispatch-ahead window full, a filled batch waits
while the pump sits in ``session.submit`` for a slot: there the lag is the
queue the loop builds. Source: program counter."""

from benchmark.harness import load_by_path

read = load_by_path("layer_metrics", "dispatch_lag_ms.lat").read
