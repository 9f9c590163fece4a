"""What every driver shares in the process that holds the chip: the look
for the chip, the table of peaks, the program's configuration built from the
cell's configuration file, and the device part of the result line."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "out")  # traces and run files; gitignored


def say(msg: str) -> None:
    print(msg, flush=True)


def find_chip(chips: int, allow_cpu: bool) -> tuple[dict, float]:
    """``{"platform", "kind", "count"}`` as jax reports it, and the seconds
    the runtime took to hand this process the chip: the one call that
    brings the backend up, 5.7-12.6 s on a v5e host and the machine's own, not
    the program's, so a driver takes it out of ``setup_s`` (importing jax
    stays in). No TPU, or fewer chips than the cell asks for, ends the run
    with code 3 and no result line. (``allow_cpu`` is for benchmark/tests
    only.)"""
    import time

    import jax

    t = time.perf_counter()
    devs = jax.devices()
    chip_wait_s = time.perf_counter() - t
    say(f"chip_wait_s {chip_wait_s:.3f} (the runtime's hand-over of the "
        "chip; not part of setup_s)")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if allow_cpu:
        return info, chip_wait_s
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"error: the cell needs {chips} TPU chip(s); jax found "
              f"{info}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    return info, chip_wait_s


def peaks_for(kind: str, allow_cpu: bool) -> dict | None:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind in table and not kind.startswith("_"):
        return table[kind]
    if allow_cpu:
        return None
    print(f"error: device kind {kind!r} is not in benchmark/peaks.json",
          file=sys.stderr, flush=True)
    raise SystemExit(3)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the backend
    does not report it, which is the CPU)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def knn_config(config: dict, control: bool):
    """The program's ``KNNConfig`` for a configuration file; ``control``
    switches on the lower-precision path that the configuration names."""
    from mpi_knn_tpu.config import KNNConfig

    fields = dict(config["knn"])
    if control:
        fields["matmul_precision"] = config["control"]["matmul_precision"]
    return KNNConfig(**fields)


def compile_cache() -> str:
    """The program's own persistent compilation cache: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` at the root of
    this checkout, a fixed path inside it."""
    from mpi_knn_tpu.utils.platform import use_compile_cache

    return use_compile_cache()


def load_by_path(kind: str, name: str):
    """Import ``benchmark/<kind>/<name>.py`` by file name (names may hold
    dots and dashes, so this is not an ordinary import)."""
    import importlib.util

    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"error: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metrics(cell: dict, run: dict) -> dict:
    """The per-layer metrics of a traced line. Each is a reader of its own
    under ``layer_metrics/``; one that finds nothing to read returns None
    and is left out."""
    out = {}
    for m in cell["per_layer"]:
        value = load_by_path("layer_metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def datagen_for(config: dict):
    return load_by_path("datagen", config["data"]["generator"])


def end_to_end(cell: dict, values: dict) -> dict:
    """The cell's end-to-end metrics of the result line, from the values a
    driver measured, with the units ``BENCHMARK.json`` gives them."""
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if values.get(m["name"]) is not None}


def add_trace(result: dict, cell: dict, summary: dict | None,
              peaks: dict | None, q_rows: float, batches: float,
              **record) -> None:
    """What a ``--trace 1`` run adds to a result: the device's busy time,
    the breakdown, and the ``run`` record that the per-layer readers get
    (README.md lists its keys; one a driver has nothing for is None).
    ``q_rows`` query rows in ``batches`` batches are the traced work."""
    from benchmark import opcount

    config, device = cell["config"], result["device"]
    work = None
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        say(f"trace: {summary['device_events']} device events, busy "
            f"{summary['busy_s']:.4f}s of {summary['window_s']:.4f}s")
        if peaks:
            least, bound = opcount.least_seconds(
                q_rows, batches, config["rows"], config["dim"], config["k"],
                peaks)
            work = {"least_s": least, "bound": bound}
            say(f"roofline: {q_rows:.0f} rows in {batches:.0f} batches "
                f"traced, least {least:.4f}s ({bound} bound applies)")
    result["run"] = {
        "device": device, "peaks": peaks, "trace": summary,
        "traced_work": work, "traced_call_walls_s": None,
        "traced_metrics_delta": None, "window_metrics_delta": None,
        "loadgen": None, **record,
    }


def query_pool(config: dict, seed: int, rows: int):
    """The serving mixes' pool of query rows: fresh corpus-shaped points
    (a centre plus noise) made on the host from the seed. No jax."""
    import numpy as np

    gen = datagen_for(config)
    cen = gen.centres(seed, config["data"], config["dim"])
    rng = np.random.default_rng([int(seed), 0x71])
    return gen.host_rows(rng, rows, cen, config["data"])
