"""The one command of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration (``configs/<config>.json``) and traffic mix
(``traffic/<mix>.json``) by name, and hands both to the driver the traffic
file names (``drivers/<driver>.py``). This file never imports jax: a driver
decides which process holds the chip. The last line of standard output is
the result object the contract names; everything else goes on earlier lines.
``BENCH_RUN`` in the environment is not read.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()  # process start, for setup_s

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(bench: dict, workload: str) -> dict:
    """Everything a driver needs to know about one cell, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"error: no workload {workload!r} in BENCHMARK.json "
            f"(have {sorted(cells)})"
        )
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = read_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    )

    def in_cell(metric: dict, reports=None) -> bool:
        if "workloads" in metric:
            return workload in metric["workloads"]
        return reports is None or metric["moves"] in reports

    end_to_end = [m for m in bench["end_to_end"] if in_cell(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if in_cell(m, e2e_names)]
    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "config": config,
        "traffic": traffic,
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    # for benchmark/tests and the builder's own control runs only; the
    # driver's command never passes them
    p.add_argument("--allow-cpu", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--control", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    sys.path[:] = [ROOT] + [d for d in sys.path if d != ROOT]  # first
    from benchmark.harness import layer_metrics, load_by_path

    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = resolve_cell(bench, args.workload)
    driver = load_by_path("drivers", cell["traffic"]["driver"])
    result = driver.run(cell, args, T_START)
    if result is None:
        return 1
    # metrics of the line: end-to-end with --trace 0, per-layer with 1
    if args.trace:
        result["metrics"] = layer_metrics(cell, result.pop("run"))
    else:
        result.pop("run", None)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if args.trace and result.get("breakdown"):
        keys.append("breakdown")
    sys.stdout.flush()
    print(json.dumps({k: result[k] for k in keys}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
