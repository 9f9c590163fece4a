"""The ten-run reading: how widely a cell's untraced runs spread, and for
the streaming cell what the spread is made of. No jax; it starts
``run.py`` once a run, one after another, as the driver does.

    python3 benchmark/spread.py --workload <cell> --seeds 11,11,11,11,11,12,13,14,15,16 \
        --seconds 51 --out chiprun_out/spread/<label>.jsonl
    python3 benchmark/spread.py --table chiprun_out/spread/<label>.jsonl [...]

A run's record is one JSON line: the seed, the result line, and the
driver's ``window``, ``cycles`` and ``host`` lines where it prints them
(``drivers/serve_stream.py``), with the run's ``overrun`` and ``check``
lines. ``--table``
reckons the spread the driver's way — largest less smallest over the median,
leaving out the run farthest from the median where that narrows it — and the
contract's way (the distance between the quartiles of
``statistics.quantiles(values, n=4)`` over the median), and for a record
with cycles splits a run's mean cycle (the rate is ``query_pool_rows`` over
it) into the typical cycle (the median) and the rest (long cycles).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STALL = 1.5  # a cycle longer than this many medians is a stall


def one_run(workload: str, seed: int, seconds: float, extra: list) -> dict:
    t = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", "0",
         *extra], cwd=ROOT, capture_output=True, text=True)
    rec = {"workload": workload, "seed": seed, "rc": p.returncode,
           "wall_s": time.time() - t, "overruns": []}
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    for ln in lines:
        for key in ("window", "cycles", "host", "bodies"):
            if ln.startswith(key + " {"):
                rec[key] = json.loads(ln[len(key) + 1:])
    for ln in (p.stdout + p.stderr).splitlines():
        if "overrun " in ln:
            rec["overruns"].append(ln.strip()[-400:])
        elif ln.startswith("check ") and " value=" in ln:
            # every number compared for `correct`, beside its limit
            rec.setdefault("checks", []).append(ln[6:].strip())
    if p.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    else:
        rec["tail"] = (p.stdout[-1500:], p.stderr[-1500:])
    return rec


def drivers_spread(values: list) -> float:
    """Largest less smallest over the median, without the run farthest
    from the median where leaving it out narrows the spread."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1] or values
    return min(max(values) - min(values), max(kept) - min(kept)) / med


def quartile_spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def metric(rec: dict, name: str):
    m = (rec.get("result") or {}).get("metrics", {}).get(name)
    return m and m["value"]


def cycle_split(rec: dict) -> dict | None:
    """A run's mean cycle as its typical cycle plus what long cycles add."""
    c = rec.get("cycles")
    if not c or not c["s"]:
        return None
    s = c["s"]
    med = statistics.median(s)
    out = {"cycles": len(s), "mean_ms": 1e3 * statistics.fmean(s),
           "median_ms": 1e3 * med,
           "stall_s": sum(x for x in s if x > STALL * med),
           "stalls": sum(x > STALL * med for x in s)}
    for key in ("insert", "search", "delete", "turn", "empty"):
        out[key + "_ms"] = 1e3 * statistics.fmean(c[key])
    host = rec.get("host") or {}
    out["overrun_s"] = sum(v for k, v in host.items()
                           if k.startswith("serve_batch_overrun_seconds"))
    out["gc_s"] = sum(v for k, v in host.items()
                      if k.startswith("python_gc_seconds_total"))
    return out


def pct(part: float, whole: float) -> str:
    return f"{100 * part / whole:.3f} %"


def spread_lines(name: str, seeds: list, vals: list) -> None:
    """One metric's median and spreads; then the two sets where every seed
    ran twice (two sets in turn with the same seeds: the contract's way to
    set a bound, and an A/A reading of one tree), else one seed's repeats
    beside the other seeds."""
    med = statistics.median(vals)
    if len(vals) < 2:
        print(f"{name}: {vals}")
        return
    print(f"{name}: median {med:.6g}  spread (driver's) "
          f"{pct(drivers_spread(vals), 1)}  (quartiles) "
          f"{pct(quartile_spread(vals), 1)}")
    by_seed: dict = {}
    for seed, v in zip(seeds, vals):
        by_seed.setdefault(seed, []).append(v)
    if len(by_seed) > 2 and all(len(v) == 2 for v in by_seed.values()):
        sets = [list(part) for part in zip(*by_seed.values())]
        for n, part in enumerate(sets, 1):
            print(f"  set {n} ({len(part)} seeds): median "
                  f"{statistics.median(part):.6g}, spread (driver's) "
                  f"{pct(drivers_spread(part), 1)}, (quartiles) "
                  f"{pct(quartile_spread(part), 1)}")
        a, b = (statistics.median(part) for part in sets)
        print(f"  the second set's median over the first's: "
              f"{100 * (b / a - 1):+.3f} %; two runs of one seed differ by "
              f"at most {pct(max(abs(x - y) for x, y in zip(*sets)), med)}")
        return
    same = [v for vs in by_seed.values() if len(vs) > 1 for v in vs]
    other = [vs[0] for vs in by_seed.values()]
    for label, part in (("one seed repeated", same),
                        ("different seeds", other)):
        if len(part) > 2:
            print(f"  {label} ({len(part)} runs): median "
                  f"{statistics.median(part):.6g}, largest less smallest "
                  f"{pct(max(part) - min(part), med)}")


def cycle_lines(splits: list, rate: str) -> None:
    """The streaming cell's runs cycle by cycle: a row a run, then how far
    each part of a cycle ranges over the runs."""
    cols = ("cycles", "mean_ms", "median_ms", "stalls", "stall_s",
            "insert_ms", "search_ms", "delete_ms", "turn_ms", "empty_ms",
            "overrun_s", "gc_s")
    print("| seed | rows/s | setup_s | " + " | ".join(cols) + " |")
    print("|" + "---|" * (len(cols) + 3))
    for r, s in splits:
        print(f"| {r['seed']} | {metric(r, rate):.1f} | "
              f"{metric(r, 'setup_s') or float('nan'):.2f} | "
              + " | ".join(f"{s[c]:.4g}" for c in cols) + " |")

    def span(key):
        v = [s[key] for _, s in splits]
        return min(v), max(v)

    mean = [s["mean_ms"] for _, s in splits]
    rest = [s["mean_ms"] - s["median_ms"] for _, s in splits]
    ref = statistics.median(mean)
    lo, hi = span("median_ms")
    print(f"mean cycle: largest less smallest "
          f"{pct(max(mean) - min(mean), ref)} of its median; the typical "
          f"(median) cycle alone {pct(hi - lo, ref)}; what long cycles add "
          f"(mean less median) {pct(max(rest) - min(rest), ref)}")
    for key in ("turn_ms", "empty_ms", "insert_ms", "search_ms", "delete_ms"):
        lo, hi = span(key)
        print(f"  {key}: {lo:.3f} ... {hi:.3f} a cycle "
              f"(range {pct(hi - lo, ref)} of a cycle)")
    window = statistics.median(r["window"]["window_s"] for r, _ in splits)
    for key in ("overrun_s", "gc_s", "stall_s"):
        lo, hi = span(key)
        print(f"  {key}: {lo:.3f} ... {hi:.3f} a window "
              f"(range {pct(hi - lo, window)} of the window)")


def table(paths: list, name: str | None) -> None:
    for path in paths:
        with open(path) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
        good = [r for r in recs if r.get("result")]
        if not good:
            print(f"{path}: no run gave a result")
            continue
        metrics = [m for m in good[0]["result"]["metrics"]
                   if name in (None, m)]
        print(f"## {path}: {len(good)} of {len(recs)} runs gave a result; "
              f"correct {sum(r['result']['correct'] for r in good)}, failed "
              f"requests {sum(r['result']['failed'] for r in good)}")
        for m in metrics:
            spread_lines(m, [r["seed"] for r in good],
                         [metric(r, m) for r in good])
        splits = [(r, cycle_split(r)) for r in good]
        if all(s for _, s in splits):
            cycle_lines(splits, metrics[0])
        else:
            for r in good:
                print(f"  seed {r['seed']}: " + "  ".join(
                    f"{m} {metric(r, m):.6g}" for m in metrics))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seeds", help="comma-separated, one a run")
    p.add_argument("--seconds", type=float, default=51.0)
    p.add_argument("--out")
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--table", nargs="+", metavar="RECORD")
    p.add_argument("--metric")
    args = p.parse_args(argv)
    if args.table:
        table(args.table, args.metric)
        return 0
    if not (args.workload and args.seeds and args.out):
        p.error("--workload, --seeds and --out, or --table")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    extra = ["--allow-cpu"] if args.allow_cpu else []
    with open(args.out, "a") as f:
        for seed in args.seeds.split(","):
            rec = one_run(args.workload, int(seed), args.seconds, extra)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(f"seed {seed}: rc {rec['rc']} in {rec['wall_s']:.1f}s "
                  + json.dumps((rec.get("result") or {}).get("metrics")),
                  flush=True)
    table([args.out], args.metric)
    return 0


if __name__ == "__main__":
    sys.exit(main())
