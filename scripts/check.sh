#!/usr/bin/env bash
# Local CI gate — the one entry point future PRs run before pushing.
#
#   scripts/check.sh            # everything
#   scripts/check.sh --fast     # skip the native sanitizer builds
#
# Order is cheapest-first so broken syntax fails in seconds, not after a
# three-minute pytest run. Tools that may be absent in a given container
# (ruff, mypy, a C++ toolchain) are SKIPPED with a notice, never silently:
# the tier-1 pytest gate and compileall always run.

set -u -o pipefail
cd "$(dirname "$0")/.."

FAST=0
[ "${1:-}" = "--fast" ] && FAST=1

fail=0
note() { printf '\n== %s\n' "$*"; }

note "compileall (syntax gate)"
if ! python -m compileall -q mpi_knn_tpu tests scripts; then
    fail=1
fi

note "ruff (pyproject.toml [tool.ruff])"
if command -v ruff >/dev/null 2>&1; then
    ruff check mpi_knn_tpu tests scripts || fail=1
else
    echo "SKIP: ruff not installed (pip install -e .[dev])"
fi

note "mypy (pyproject.toml [tool.mypy])"
if command -v mypy >/dev/null 2>&1; then
    mypy || fail=1
else
    echo "SKIP: mypy not installed (pip install -e .[dev])"
fi

if [ "$FAST" = 0 ]; then
    note "native sanitizer builds (asan + ubsan + tsan)"
    if command -v "${CXX:-g++}" >/dev/null 2>&1; then
        make -C native asan ubsan || fail=1
        # tsan is best-effort at BUILD time (older toolchains lack
        # -fsanitize=thread); the threaded reader sweep in
        # tests/test_sanitizers.py skip-guards the same way
        make -C native tsan || echo "SKIP: toolchain lacks -fsanitize=thread"
    else
        echo "SKIP: no C++ toolchain (\$CXX/g++)"
    fi
fi

note "host concurrency lint (ISSUE 13: mpi-knn lint --host)"
# the threaded host modules — frontend pump + HTTP handlers, serve
# engine, aot cache, metrics registry, span recorder, worker supervisor
# — against the enforced guard map: H1 lock discipline (every shared
# mutable attribute declared AND every access site inside its lock),
# H2 lock-order acyclicity, H3 thread confinement, H4 atomic publish
# (bare open(...,"w") in a threaded module is a finding; writers go
# through utils.atomicio). Zero findings required; the waiver count is
# PINNED so intentional unguarded access cannot accrete silently, and
# the lock-acquisition graph is asserted acyclic from the report.
python -m mpi_knn_tpu lint --host -q --out artifacts/lint || fail=1
python - <<'HOSTEOF' || fail=1
import json
doc = json.load(open("artifacts/lint/host_report.json"))
s = doc["summary"]
assert doc["ok"] is True, "host lint not ok"
assert s["findings"] == 0, f"host findings: {s['findings']}"
assert s["problems"] == 0, f"stale guard map: {doc['problems']}"
assert s["lock_graph_acyclic"] is True, doc["lock_graph"]["cycles"]
assert s["waivers"] == 7, (
    f"waiver count changed ({s['waivers']} != 7): every new waiver "
    "needs a rationale in analysis/host/guards.py AND this pin bumped"
)
print(f"host lint gate: {s['targets']} targets, "
      f"{s['classes_checked']} classes, {s['lock_edges']} lock edges, "
      f"{s['waivers']} waivers (pinned)")
HOSTEOF

note "static lint of every backend's compiled program (mpi-knn lint)"
# the default sweep is the full backend × metric × dtype matrix PLUS the
# precision_policy=mixed cells for every backend × metric — R3 certifies
# the compress-and-rerank dot contract there (exactly one DEFAULT compress
# dot per tile computation, rerank at HIGHEST) — PLUS the
# ring_schedule=bidir cells for both ring backends × metric × both
# policies, where R4 certifies the full-duplex accounting (exactly 2
# counter-directed collective-permutes per torus direction; wrong-direction
# or missing permutes are findings) — PLUS the serving-engine cells
# (every backend's per-batch program from the bucketed executable cache,
# `--serve` to run them alone), where R5 certifies the scratch donation
# (every output aliased to a donated input in the compiled program) and
# that nothing copies the resident corpus per batch — PLUS the clustered
# (IVF) cells (`--backend ivf` to run them alone: one-shot + serve ×
# exact/mixed over a real k-means-trained index), where R6 certifies
# that corpus payload reaches a dot only through the per-query probe
# gather and R2 runs in STRICT mode (the probed-bytes bound
# nprobe·bucket_cap·d replaces the largest-input floor — the sublinear
# claim as a compiled-program fact) — PLUS the degradation-ladder cells
# (ladder-bucket on serial+ivf, ladder-nprobe on ivf): R5 re-certifies
# the donation/no-corpus-copy contract on exactly the programs
# resilience/ladder.py's rungs lower under sustained deadline breach
# (degrading, and the retry paths around it, must introduce no new
# copies), and the nprobe rung must fit R2-strict's SMALLER probed-bytes
# budget; any finding fails the gate — PLUS the peak-HBM axis (ISSUE
# 15): R7-peak-memory runs on every cell (aliasing-aware liveness peak
# vs the cell's derived budget, cross-checked against PJRT's own
# memory_analysis within the declared band) and --memory --ledger-check
# recomputes every cell's numbers and fails on drift beyond tolerance
# vs the committed artifacts/lint/memory_ledger.json in EITHER
# direction (growth = regression, shrinkage = stale ledger) — PLUS the
# cost axis (ISSUE 16): R8-cost prices every cell (MXU FLOPs from dot
# shapes × static execution counts, cross-checked EXACTLY against the
# closed-form analytical count from the cell's own config; modeled HBM
# traffic; wire-priced ICI census — an unpriced collective is a
# finding) and --cost --ledger-check holds the numbers to the committed
# artifacts/lint/cost_ledger.json the same way (growth = perf
# regression naming the culprit op, shrinkage = stale ledger)
python -m mpi_knn_tpu lint -q --memory --cost --ledger-check \
    --out artifacts/lint || fail=1

note "peak-HBM memory gate (ISSUE 15: R7 liveness + the memory ledger)"
# the full sweep above just REGENERATED every cell's liveness numbers
# and held them to the committed ledger (--memory --ledger-check: zero
# R7 findings, drift green — a red ledger fails the sweep command by
# exit code). The named assertions here prove the committed artifact
# itself is complete and honest: every checked default cell has a
# ledger entry, every entry carries the PJRT cross-check evidence, and
# every peak sits inside its derived budget. The injected
# counterexamples (un-donated scratch doubling residency, corpus-sized
# temp under R2's per-buffer radar, ledger drift both directions) fire
# through the production rule path in tests/test_memory_lint.py — so a
# green matrix can never be green by vacuity.
python - <<'MEMEOF' || fail=1
import json
ledger = json.load(open("artifacts/lint/memory_ledger.json"))
report = json.load(open("artifacts/lint/report.json"))
cells = ledger["cells"]
checked = [t for t in report["targets"] if t["skipped"] is None]
missing = [t["label"] for t in checked if t["label"] not in cells]
assert not missing, f"checked cells missing from the ledger: {missing}"
for label, cell in cells.items():
    assert cell["pjrt"] is not None, f"{label}: no PJRT cross-check"
    assert cell["peak_bytes"] <= cell["budget_bytes"], (
        f"{label}: peak {cell['peak_bytes']} > budget "
        f"{cell['budget_bytes']}")
    assert cell["largest_temp"]["op"], f"{label}: no temp culprit named"
print(f"memory gate: {len(cells)} ledger cells, all budgeted + "
      f"PJRT-cross-checked (tolerance {ledger['tolerance']})")
MEMEOF
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_memory_lint.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || fail=1

note "static cost gate (ISSUE 16: R8 roofline + the cost ledger)"
# the sweep above just re-priced every cell and held it to the committed
# cost ledger (--cost --ledger-check, drift green by exit code). The
# named assertions prove the committed artifact is complete and honest:
# every checked cell has a ledger entry, every entry's HLO-derived FLOP
# count EQUALS the closed-form analytical count (the R8 exactness
# contract — not within tolerance, equal), and every roofline names its
# binding resource. The injected counterexamples (a doctored dot the
# analytical form cannot name, an unpriced collective, ledger drift both
# directions through the real CLI) and the planner refusal matrix fire
# in tests/test_cost_plan.py below.
python - <<'COSTEOF' || fail=1
import json
ledger = json.load(open("artifacts/lint/cost_ledger.json"))
report = json.load(open("artifacts/lint/report.json"))
cells = ledger["cells"]
checked = [t for t in report["targets"] if t["skipped"] is None]
missing = [t["label"] for t in checked if t["label"] not in cells]
assert not missing, f"checked cells missing from the cost ledger: {missing}"
for label, cell in cells.items():
    assert cell["mxu_flops"] == cell["analytical_flops"], (
        f"{label}: HLO flops {cell['mxu_flops']} != analytical "
        f"{cell['analytical_flops']}")
    assert cell["roofline"]["bound"] in ("mxu", "hbm", "ici"), (
        f"{label}: roofline names no binding resource")
print(f"cost gate: {len(cells)} ledger cells, HLO == analytical FLOPs "
      f"on every cell (tolerance {ledger['tolerance']} for drift only)")
COSTEOF
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_cost_plan.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || fail=1

note "capacity-planner boot gate (ISSUE 16: mpi-knn plan round trip)"
# `mpi-knn plan` solves a small corpus, then the gate BOOTS the exact
# serve command the planner emitted and holds the deployment to the
# promise: /healthz peak_hbm_bytes (the measured PJRT peak of the
# largest built executable) must be ≤ the plan's predicted peak — the
# planner may over-reserve, never under-promise. Refusal exit codes and
# the in-matrix ledger byte-equality are tier-1 (tests/test_cost_plan.py).
PLAN_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP" "$PLAN_TMP"' EXIT
python -m mpi_knn_tpu plan --corpus 2048 --dim 32 --bucket 128 \
    --recall-target 0.9 --dtype float32 -q \
    > "$PLAN_TMP/plan.json" || fail=1
PLAN_SERVE="$(python -c "import json; print(json.load(open(
    '$PLAN_TMP/plan.json'))['commands']['serve'].replace('mpi-knn ', '', 1))")"
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m mpi_knn_tpu \
    $PLAN_SERVE --port 0 --ready-file "$PLAN_TMP/ready" -q &
PLAN_PID=$!
plan_ok=0
for _ in $(seq 1 120); do
    [ -s "$PLAN_TMP/ready" ] && { plan_ok=1; break; }
    kill -0 "$PLAN_PID" 2>/dev/null || break
    sleep 1
done
if [ "$plan_ok" = 1 ]; then
    timeout -k 10 180 python - "$(cat "$PLAN_TMP/ready")" \
        "$PLAN_TMP/plan.json" <<'PLANEOF' || fail=1
import json, sys, time, urllib.request
url, plan_path = sys.argv[1], sys.argv[2]
for _ in range(150):
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        h = json.load(r)
    if h["warming"]["done"]:
        break
    time.sleep(1)
else:
    raise AssertionError("serve never finished warming")
plan = json.load(open(plan_path))
pred = plan["predicted"]["peak_hbm_bytes"]
measured = h["peak_hbm_bytes"]
assert measured > 0, "booted serve reports no measured peak"
assert measured <= pred, (
    f"planner under-promised: measured peak {measured}B > "
    f"predicted {pred}B for {plan['config']}")
assert h.get("device_profile"), "/healthz carries no device profile"
print(f"plan boot gate: {plan['config']['backend']} plan booted, "
      f"measured peak {measured}B <= predicted {pred}B "
      f"(profile {h['device_profile']['name']})")
PLANEOF
    kill -TERM "$PLAN_PID" 2>/dev/null
    wait "$PLAN_PID" || fail=1
else
    echo "plan boot gate: planner-emitted serve failed to come up"
    kill "$PLAN_PID" 2>/dev/null
    fail=1
fi

note "sharded-IVF lint gate (ISSUE 8: routed candidate exchange)"
# the sharded clustered cells by name (they also run inside the full
# sweep above — the named pass exists so an exchange-accounting
# regression is called out as such): the bucket store distributed over a
# 4-shard CPU mesh, one-shot + serve × exact/mixed + the ladder-nprobe
# rung, where R4 pins the program to exactly the four exchange
# all-to-alls (full-ring replica groups, payload within the declared
# per-tile budget — an unrouted full-bucket broadcast or an over-budget
# per-shard gather is a finding) and R2-strict prices the probed-bytes
# budget PER SHARD; the multi-shard recall-parity tests are tier-1 in
# tests/test_ivf_sharded.py (the pytest gate below)
python -m mpi_knn_tpu lint -q --backend ivf-sharded \
    --out artifacts/lint_sharded || fail=1

note "quantization lint gate (ISSUE 9: block-scaled int8/int4)"
# the quantized cells by name (they also run inside the full sweep above
# — the named pass exists so a quantization regression is called out as
# such): the int8-transfer ring cells (R3's quant/dequant contract —
# exactly one dequant convert + scale multiply feeding each compress
# dot, no dot touching raw codes; R4's 3-permutes-per-direction
# accounting with every payload priced at the wire dtype; R1's overlap
# certification with the scale row in the schedule) and the int8/int4
# at-rest clustered cells (R2's wire-priced gather bound — dequantize
# AFTER the gather; the serve cells re-certify R5's donation on
# quantized bucket-cache programs). The injected counterexamples — raw-
# code dots, dropped/double dequants, float-sized gathers, float-width
# rotations under an int8 label — must FIRE (tests/test_hlo_lint.py -k
# quant), so a green matrix can never be green by vacuity.
python -m mpi_knn_tpu lint -q --quant xfer-int8 --quant int8 --quant int4 \
    --out artifacts/lint_quant || fail=1
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_hlo_lint.py -k quant -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || fail=1

note "fault-injection / resilience suite (ISSUE 6 gate)"
# the resilience layer's whole fault matrix, exercised on CPU rather than
# trusted: injected hang → heartbeat-starvation kill with a structured
# timeout result; transient fault → success-after-N with the exact
# backoff sequence; NaN poison → sentinel trips with batch provenance;
# injected deadline breaches → the serving degradation ladder walks with
# recall gated at each rung's own bar. The bench/doctor subprocess
# regressions (partial-round banking, the BENCH_r05 shape) run here too —
# this is a named gate so a resilience regression is called out by name,
# not buried in the tier-1 roll-up (the file runs again there; it is
# ~35 s, cheap enough to pay twice for the naming)
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_resilience.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly || fail=1

note "observability artifacts (ISSUE 7 gate: mpi-knn metrics)"
# run a real (tiny) serve session with the flight recorder and metrics
# snapshot on, then prove the artifacts are machine-readable: every span
# record validates against the schema (no NaN/negative durations, ends
# match opens, parents exist — `--validate` exits 1 on any problem) and
# the Prometheus exposition round-trips through the strict parser
# (`--check`). This is the same obs stack test_obs.py exercises, but
# driven through the production CLIs end to end, so a serialization
# regression fails here by name
OBS_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP"' EXIT
if timeout -k 10 180 env JAX_PLATFORMS=cpu python -m mpi_knn_tpu query \
        --data synthetic:2048x32c4 --synthetic 512 --batch 128 \
        --bucket 128 --k 10 --backend serial \
        --flight-record "$OBS_TMP/flight.jsonl" \
        --metrics-out "$OBS_TMP/metrics.json" >/dev/null; then
    python -m mpi_knn_tpu metrics --flight "$OBS_TMP/flight.jsonl" \
        --validate || fail=1
    python -m mpi_knn_tpu metrics "$OBS_TMP/metrics.json" --check || fail=1
else
    echo "obs gate: serve session failed"
    fail=1
fi

note "serving front end gate (ISSUE 11: mpi-knn serve + loadgen)"
# boot the REAL server on an ephemeral loopback port, drive a short
# multi-tenant smoke through the production `mpi-knn loadgen` CLI, then
# prove the operational artifacts are machine-readable: /metrics is
# scraped over HTTP and re-parsed with the strict parse_prometheus (the
# per-tenant labeled counters must survive the round trip), and the
# flight record — coalesce spans, batch spans with tenant composition —
# passes the schema gate. The coalescing/fairness/shedding BEHAVIOR is
# tier-1 (tests/test_frontend*.py); this gate proves the network path
# end to end through the CLIs. The frontend lint cell (the coalesced
# batch lowered through the production lower_bucket — no new programs)
# runs inside the full `mpi-knn lint` sweep above; `--frontend` selects
# it alone.
FE_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP" "$PLAN_TMP" "$FE_TMP"' EXIT
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m mpi_knn_tpu serve \
    --data synthetic:2048x32c4 --k 10 --backend serial --bucket 128 \
    --corpus-tile 512 --port 0 --ready-file "$FE_TMP/ready" \
    --flight-record "$FE_TMP/flight.jsonl" \
    --metrics-out "$FE_TMP/metrics.json" -q &
FE_PID=$!
fe_ok=0
for _ in $(seq 1 120); do
    [ -s "$FE_TMP/ready" ] && { fe_ok=1; break; }
    kill -0 "$FE_PID" 2>/dev/null || break
    sleep 1
done
if [ "$fe_ok" = 1 ]; then
    FE_URL="$(cat "$FE_TMP/ready")"
    timeout -k 10 120 python -m mpi_knn_tpu loadgen --url "$FE_URL" \
        --tenants 2 --qps 40 --requests 10 --rows 16 \
        --report "$FE_TMP/load.json" || fail=1
    timeout -k 10 60 python - "$FE_URL" <<'PYEOF' || fail=1
import sys, urllib.request
from mpi_knn_tpu.obs.metrics import parse_prometheus
with urllib.request.urlopen(sys.argv[1] + "/metrics", timeout=30) as r:
    samples = parse_prometheus(r.read().decode())
assert samples["serve_batches_total"] >= 1, "no batches served"
assert any(k.startswith("serve_tenant_queries_total{") for k in samples), \
    "per-tenant counters missing from the exposition"
assert "frontend_queue_rows" in samples, "frontend gauge missing"
assert samples.get("serve_peak_hbm_bytes", 0) > 0, \
    "peak-HBM gauge missing from the exposition (ISSUE 15)"
print(f"frontend gate: {len(samples)} samples re-parsed, "
      f"{samples['serve_batches_total']:.0f} batches, "
      f"peak HBM {samples['serve_peak_hbm_bytes']:.0f}B")
PYEOF
    kill -TERM "$FE_PID" 2>/dev/null
    wait "$FE_PID" || fail=1
    python -m mpi_knn_tpu metrics --flight "$FE_TMP/flight.jsonl" \
        --validate || fail=1
    python -m mpi_knn_tpu metrics "$FE_TMP/metrics.json" --check || fail=1
else
    echo "frontend gate: server failed to come up"
    kill "$FE_PID" 2>/dev/null
    fail=1
fi

note "cold-start gate (ISSUE 12: persistent AOT executable cache)"
# start the production `mpi-knn serve` TWICE against one --cache-dir:
# the second start must report aot_cache_hits_total > 0 and ZERO
# serve-cache compiles in /metrics (every executable revived from disk,
# the corrupt-entry path counted separately and required silent), and
# its healthz-ready wall time must be under the cold start's. The
# bit-identity and corruption-fallback CONTRACT is tier-1
# (tests/test_aot_cache.py); this gate proves the restart story end to
# end through the CLIs, where a fingerprint or serialization regression
# fails by name. (The lint sweeps above can share compiled artifacts
# the same way via `mpi-knn lint --cache-dir` — jax's own compilation
# cache, see analysis/README.md.)
timeout -k 10 420 python scripts/check_cold_start.py || fail=1

note "live-mutation gate (ISSUE 14: serve + HTTP upsert/delete/query)"
# production `mpi-knn serve` over a CLUSTERED index with headroom and an
# aggressive compaction trigger, driven end to end over HTTP: upserts,
# deletes and queries interleave; /metrics is scraped twice around a
# second churn round and must show ZERO mutation-path compiles between
# scrapes (the warm steady state) with monotone upsert/delete counters;
# the background compactor must fire on the tombstone threshold
# (compactions_total >= 1); then SIGTERM lands while the compactor is
# armed and the flight record must still validate (an open compact span
# is a diagnosis, not corruption). The donation/aliasing CONTRACT on the
# mutation programs is the lint matrix above (mutate-* cells); the
# correctness matrix is tier-1 (tests/test_mutation.py).
MUT_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP" "$PLAN_TMP" "$FE_TMP" "$MUT_TMP"' EXIT
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m mpi_knn_tpu serve \
    --data synthetic:2048x32c8 --k 10 --partitions 16 --nprobe 4 \
    --bucket 128 --bucket-headroom 0.5 --mutation-bucket 64 \
    --compact-tombstone-fraction 0.05 --compactor-interval-s 0.1 \
    --port 0 --ready-file "$MUT_TMP/ready" \
    --flight-record "$MUT_TMP/flight.jsonl" \
    --metrics-out "$MUT_TMP/metrics.json" -q &
MUT_PID=$!
mut_ok=0
for _ in $(seq 1 120); do
    [ -s "$MUT_TMP/ready" ] && { mut_ok=1; break; }
    kill -0 "$MUT_PID" 2>/dev/null || break
    sleep 1
done
if [ "$mut_ok" = 1 ]; then
    MUT_URL="$(cat "$MUT_TMP/ready")"
    timeout -k 10 180 python - "$MUT_URL" <<'PYEOF' || fail=1
import json, sys, time, urllib.request
from mpi_knn_tpu.obs.metrics import parse_prometheus

url = sys.argv[1]

def post(path, doc):
    req = urllib.request.Request(
        url + path, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", "X-Tenant": "ci"},
        method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())

def scrape():
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        return parse_prometheus(r.read().decode())

import numpy as np
rng = np.random.default_rng(0)
rows = lambda n: rng.standard_normal((n, 32)).astype(float).tolist()

# wait for warming to finish so the steady-state claim is honest
for _ in range(120):
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        if json.loads(r.read().decode())["ready"]:
            break
    time.sleep(0.5)
# round 1: warm the mutation cells + interleave a query
post("/upsert", {"ids": list(range(900000, 900064)), "rows": rows(64)})
post("/query", {"queries": rows(16)})
post("/delete", {"ids": list(range(900000, 900064))})
m1 = scrape()
# round 2 (the STEADY STATE): more churn at ragged sizes + queries
for i, n in enumerate((7, 33, 64, 12)):
    base = 910000 + i * 100
    post("/upsert", {"ids": list(range(base, base + n)), "rows": rows(n)})
    post("/query", {"queries": rows(5)})
    post("/delete", {"ids": list(range(base, base + n))})
m2 = scrape()
compiled = "mutation_executables_compiled_total"
assert m2.get(compiled, 0) == m1.get(compiled, 0), (
    f"mutation path compiled in steady state: {m1.get(compiled)} -> "
    f"{m2.get(compiled)}")
assert m2["mutation_upserts_total"] > m1["mutation_upserts_total"], \
    "upsert counter not monotone"
assert m2["mutation_deletes_total"] > m1["mutation_deletes_total"], \
    "delete counter not monotone"
assert m2["index_tombstone_fraction"] >= 0, "tombstone gauge missing"
# a deletes-only round (no upserts to reuse the slots): tombstones cross
# the 5% trigger and the background compactor must fire (monotone
# compactions counter). Chunked under max_batch_rows — an oversized
# mutation is a structured 429 by design.
post("/delete", {"ids": list(range(0, 128))})
post("/delete", {"ids": list(range(128, 256))})
deadline = time.time() + 60
while time.time() < deadline:
    m3 = scrape()
    if m3.get("compactions_total", 0) >= 1:
        break
    time.sleep(0.5)
assert m3.get("compactions_total", 0) >= 1, "compactor never fired"
assert m3["mutation_upserts_total"] >= m2["mutation_upserts_total"]
print(f"mutation gate: {int(m3['mutation_upserts_total'])} upserts, "
      f"{int(m3['mutation_deletes_total'])} deletes, "
      f"{int(m3['compactions_total'])} compaction(s), "
      f"0 steady-state mutation compiles")
PYEOF
    kill -TERM "$MUT_PID" 2>/dev/null
    wait "$MUT_PID" || fail=1
    python -m mpi_knn_tpu metrics --flight "$MUT_TMP/flight.jsonl" \
        --validate || fail=1
    python -m mpi_knn_tpu metrics "$MUT_TMP/metrics.json" --check || fail=1
else
    echo "mutation gate: server failed to come up"
    kill "$MUT_PID" 2>/dev/null
    fail=1
fi

note "replicated-tier gate (ISSUE 18: router + rolling-restart drill)"
# the full replicated story end to end through the production CLIs:
# pre-warm a shared AOT cache dir with one serve boot, then `mpi-knn
# router --spawn 3` over it (every child revives the warm set from
# disk), wait for the health-gated rotation to fill, seed a fanned-out
# mutation, then the DRILL — SIGKILL one supervised child (pid read
# from the router's own /healthz children table) under open-loop load.
# The bar: the client report shows ZERO transport errors and nothing
# but 200s (in-flight requests on the killed replica are retried on a
# surviving one — a single-replica death is the router's problem, never
# the client's); the kill IS visible as membership transitions (evict →
# restart-detected → join) and a supervisor restart counter; the reborn
# child proves it rejoined WARM (aot_cache_hits_total > 0, zero serve
# compiles in its own /metrics); post-churn mutations converge (every
# replica's applied_seq reaches the router's seq, every lag gauge 0 —
# scraped from /metrics, re-parsed with the strict parser). Then a
# production loadgen smoke through the recovered fleet, clean shutdown,
# and the flight record (membership events, replica exits) passes the
# schema gate. The membership/replay/affinity BEHAVIOR is tier-1
# (tests/test_router.py, on modeled replicas); this gate proves the
# real-process story: real serve children, real SIGKILL, real sockets.
RT_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP" "$PLAN_TMP" "$FE_TMP" "$MUT_TMP" "$RT_TMP"' EXIT
RT_SERVE_ARGS="--data synthetic:2048x32c8 --k 10 --partitions 16 \
    --nprobe 4 --bucket 128 --bucket-headroom 0.5 --mutation-bucket 64"
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m mpi_knn_tpu serve \
    $RT_SERVE_ARGS --cache-dir "$RT_TMP/aot" --port 0 \
    --ready-file "$RT_TMP/warm-ready" -q &
RT_WARM_PID=$!
for _ in $(seq 1 180); do
    [ -s "$RT_TMP/warm-ready" ] && break
    kill -0 "$RT_WARM_PID" 2>/dev/null || break
    sleep 1
done
kill -TERM "$RT_WARM_PID" 2>/dev/null
wait "$RT_WARM_PID" 2>/dev/null
if [ ! -s "$RT_TMP/warm-ready" ]; then
    echo "router gate: cache pre-warm serve failed to come up"
    fail=1
fi
timeout -k 10 900 env JAX_PLATFORMS=cpu python -m mpi_knn_tpu router \
    --spawn 3 --cache-dir "$RT_TMP/aot" --workdir "$RT_TMP/work" \
    --probe-interval-ms 100 --port 0 --ready-file "$RT_TMP/ready" \
    --flight-record "$RT_TMP/flight.jsonl" \
    --metrics-out "$RT_TMP/metrics.json" -q \
    -- $RT_SERVE_ARGS &
RT_PID=$!
rt_ok=0
for _ in $(seq 1 120); do
    [ -s "$RT_TMP/ready" ] && { rt_ok=1; break; }
    kill -0 "$RT_PID" 2>/dev/null || break
    sleep 1
done
if [ "$rt_ok" = 1 ]; then
    RT_URL="$(cat "$RT_TMP/ready")"
    timeout -k 10 600 python - "$RT_URL" <<'RTEOF' || fail=1
import json, os, signal, sys, threading, time, urllib.request

import numpy as np

from mpi_knn_tpu.frontend import loadgen
from mpi_knn_tpu.obs.metrics import parse_prometheus

url = sys.argv[1]


def healthz():
    with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
        return json.load(r)


def scrape(base=None):
    with urllib.request.urlopen((base or url) + "/metrics",
                                timeout=30) as r:
        return parse_prometheus(r.read().decode())


def msum(samples, name, **labels):
    tot = 0.0
    for key, v in samples.items():
        if key != name and not key.startswith(name + "{"):
            continue
        if all(f'{lk}="{lv}"' in key for lk, lv in labels.items()):
            tot += v
    return tot


def wait_for(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            if pred():
                return
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    raise AssertionError("timed out waiting for " + what)


def post(path, doc):
    req = urllib.request.Request(
        url + path, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", "X-Tenant": "ci"},
        method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())


# every child revives the pre-warmed cells from the shared cache dir
wait_for(lambda: len(healthz()["rotation"]) == 3, 420,
         "3-replica rotation")
h0 = healthz()
assert h0["role"] == "router" and h0["dim"] == 32, h0
victim = h0["rotation"][0]
pid = h0["children"][victim]["pid"]
assert pid, f"no supervised pid for {victim}"

# a fanned-out mutation BEFORE the kill, so the rejoin has a real gap
rng = np.random.default_rng(0)
rows = lambda n: rng.standard_normal((n, 32)).tolist()  # noqa: E731
d1 = post("/upsert",
          {"ids": list(range(990000, 990032)), "rows": rows(32)})
assert sorted(d1["applied"]) == ["r0", "r1", "r2"], d1

# the DRILL: open-loop load, SIGKILL one supervised child mid-run
box = {}


def _load():
    box["rep"] = loadgen.run_http(
        url, tenants=6, qps=4.0, n_requests=20, rows=16,
        timeout_s=30, connections=6)


t = threading.Thread(target=_load)
t.start()
time.sleep(1.5)
os.kill(pid, signal.SIGKILL)
t.join(300)
rep = box.get("rep")
assert rep is not None, "loadgen never returned"
assert rep["errors"] == 0, f"transport errors under the kill: {rep}"
assert set(rep["by_status"]) == {"200"}, (
    f"client saw non-200 under a 1-of-3 kill: {rep['by_status']}")

# the kill is membership's problem, and visibly so
m1 = scrape()
assert msum(m1, "router_membership_transitions_total",
            event="evict") >= 1, "no evict transition recorded"
wait_for(lambda: len(healthz()["rotation"]) == 3, 300,
         "the killed replica's rebirth to rejoin")
m2 = scrape()
assert msum(m2, "router_replica_restarts_total") >= 1, \
    "supervisor restart not counted"
assert msum(m2, "router_membership_transitions_total",
            event="restart-detected") >= 1, "restart never detected"
assert msum(m2, "router_membership_transitions_total",
            event="join") >= 1, "no join transition recorded"

# the reborn child rejoined WARM: the shared AOT cache fed it every
# executable — zero compiles in its own registry
child_url = healthz()["children"][victim]["url"]
cm = scrape(child_url)
assert cm.get("aot_cache_hits_total", 0) > 0, \
    "reborn replica shows no AOT cache hits"
assert cm.get("serve_executables_compiled_total", 0) == 0, (
    f"reborn replica compiled "
    f"{cm['serve_executables_compiled_total']:.0f} executables — "
    "the rejoin was cold")

# post-churn mutations converge: applied_seq reaches the router's seq
# on every replica (the reborn one replayed its gap in order)
d2 = post("/upsert",
          {"ids": list(range(991000, 991032)), "rows": rows(32)})
assert sorted(d2["applied"]) == ["r0", "r1", "r2"], d2
post("/delete", {"ids": list(range(990000, 990032))})
h1 = healthz()
assert h1["seq"] == 3 and h1["seq"] > h0["seq"], (h0["seq"], h1["seq"])
wait_for(lambda: all(
    r["applied_seq"] == 3
    for r in healthz()["replicas"].values()), 120,
    "applied_seq convergence on every replica")
m3 = scrape()
lags = {k: v for k, v in m3.items()
        if k.startswith("router_replica_lag")}
assert lags and all(v == 0 for v in lags.values()), \
    f"replica lag gauges not drained: {lags}"
assert msum(m3, "router_replayed_mutations_total") >= 1, \
    "rejoin replayed nothing despite a seeded gap"
print(f"router gate: kill-1-of-3 drill green — "
      f"{len(rep['by_status'])} status class(es), "
      f"{msum(m3, 'router_requests_total'):.0f} proxied queries, "
      f"seq {h1['seq']} converged on 3 replicas, reborn child "
      f"{cm['aot_cache_hits_total']:.0f} cache hits / 0 compiles")
RTEOF
    timeout -k 10 120 python -m mpi_knn_tpu loadgen --url "$RT_URL" \
        --tenants 2 --qps 20 --requests 10 --rows 16 \
        --report "$RT_TMP/load.json" || fail=1
    kill -TERM "$RT_PID" 2>/dev/null
    wait "$RT_PID" || fail=1
    python -m mpi_knn_tpu metrics --flight "$RT_TMP/flight.jsonl" \
        --validate || fail=1
    python -m mpi_knn_tpu metrics "$RT_TMP/metrics.json" --check || fail=1
else
    echo "router gate: router failed to come up"
    kill "$RT_PID" 2>/dev/null
    fail=1
fi

note "tier-1 pytest (the ROADMAP.md gate)"
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
[ "$rc" -ne 0 ] && fail=1

note "result"
if [ "$fail" -ne 0 ]; then
    echo "CHECK FAILED"
    exit 1
fi
echo "CHECK OK"
