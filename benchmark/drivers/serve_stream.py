"""Driver of the streaming cells: a corpus that changes while it is served.
This parent never imports jax: it starts one child that holds the chip
(``serve_launcher_stream.py``), walks the runbook (``runbook.py``) against
its HTTP server — ``POST /upsert`` and ``POST /delete`` with raw bodies,
``POST /query`` as the other serving cells send it — closed loop, one
caller, the runbook's own order, and decides ``correct``.

A cycle: ``insert`` one cluster range as requests of
``write_rows_per_request`` rows, each sent when the one before is
acknowledged; ``search`` the whole query pool as requests of
``rows_per_request`` rows, ``search_in_flight`` at a time; ``delete`` the
oldest live range of another cluster as requests of ids. Nothing of a step
is sent before every request of the step before it is back: the runbook's
barrier, and what makes the guarantees checkable (an acknowledged insert
is visible to, an acknowledged delete's ids are returned by no, search sent
after it). ``warm_cycles`` cycles are set-up; the window opens on a cycle's
first request and closes with the cycle during which ``--seconds`` ran
out, so every window holds whole cycles of identical work. ``rows_per_s``
is the query rows answered in the window over its length.

The walker's own work is kept out of the window (PR 53): every body it will
send is made from the seed before its first request, while the child builds
its index (``write_bodies``; the seconds on the ``bodies`` line, what of
them outlasted the child's set-up taken out of ``setup_s`` as the
reference's are); its reader threads live for the whole walk; an answer is
kept as the bytes that came and parsed and checked once the window has
closed (``check_answers``: every answer of the window, each against the
deletes acknowledged before its step's first request), so between a
response's last byte and the next request's first the walker runs a few
lines; and it collects no garbage inside the window. What is left of a
cycle on its clock is printed for every run: ``cycles`` (the seconds of
every cycle of the window, of its three steps, its turn-rounds and its
seconds with no request in flight) and ``host`` (the server's own counters
over the window: the overrun record by cause, collections, phases).

``correct``: at the search steps of the traffic file's ``checkpoints``
every answer for a probe row against the plain reference
(``reference_stream.py``; ``compare.compare_answers`` as it is), at least
two checkpoints reached; over every answer of the window
``deleted_id_returned`` 0 and ``answers_misshapen_or_failed`` 0 (reads and
writes); ``compiled_in_window`` 0 (serve and mutation programs); and, so
that the cell cannot go blind, the share of probe rows whose reference
answer holds an inserted id (every checkpoint) or would hold a deleted one
had it stayed (the last) at least ``touched_share_min`` each.

The first write the server refuses as malformed (400: a program without
the raw write body) ends the run at once, its failure printed, exit code 1.
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import queue
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.parse

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TENANT_HEADER = "X-Tenant"


class Refused(Exception):
    """The server cannot take the cell's traffic at all."""


class Conn:
    """One keep-alive connection with Nagle off (``loadgen.Conn``'s
    transport, any route). ``spans`` keeps, for every request, when its
    first byte went out and when its last byte was in."""

    def __init__(self, url: str, timeout_s: float):
        u = urllib.parse.urlsplit(url)
        self.host, self.port, self.timeout_s = u.hostname, u.port, timeout_s
        self.conn = None
        self.spans: list = []

    def close(self) -> None:
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None

    def post(self, path: str, tenant: str, body: bytes) -> tuple:
        """(status, the answer's bytes as they came); status 0: no answer
        at all. Nothing is parsed here: a caller that has the next request
        to send sends it first."""
        for _ in range(2):
            fresh = self.conn is None
            try:
                if fresh:
                    self.conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout_s)
                    self.conn.connect()
                    self.conn.sock.setsockopt(socket.IPPROTO_TCP,
                                              socket.TCP_NODELAY, 1)
                sent = time.monotonic()
                self.conn.request(
                    "POST", path, body=body,
                    headers={"Content-Type": "application/octet-stream",
                             TENANT_HEADER: tenant})
                resp = self.conn.getresponse()
                data = resp.read()
                self.spans.append((sent, time.monotonic()))
                return resp.status, data
            except (OSError, http.client.HTTPException):
                self.close()
                if fresh:
                    return 0, b""
        return 0, b""


def document(data: bytes) -> dict:
    try:
        doc = json.loads(data)
    except ValueError:
        return {}
    return doc if isinstance(doc, dict) else {}


def write_bodies(config: dict, mix: dict, book: dict, subs, gen,
                 seed: int) -> list:
    """``[(upsert bodies, delete bodies), ...]`` of every cycle of the
    book, made once, before the first request: n little-endian int32 ids,
    then (an upsert) n float32 rows at the index width."""
    from benchmark import runbook

    chunk = int(mix["write_rows_per_request"])
    out = []
    for cycle in book["cycles"]:
        lo, hi = cycle["insert"]
        rows = np.concatenate([
            gen.host_block(seed, config["data"], b, subs[b])
            for b in runbook.blocks_of(cycle["insert"], book["block_rows"])])
        ins = [np.arange(a, a + chunk, dtype="<i4").tobytes()
               + np.ascontiguousarray(rows[a - lo:a - lo + chunk],
                                      dtype="<f4").tobytes()
               for a in range(lo, hi, chunk)]
        lo, hi = cycle["delete"]
        out.append((ins, [np.arange(a, a + chunk, dtype="<i4").tobytes()
                          for a in range(lo, hi, chunk)]))
    return out


class Walk:
    """The runbook's caller. Everything it sends exists before its first
    request (``write_bodies``, the pool cut into request bodies), its reader
    threads live for the whole walk, and an answer to a search is kept as
    the bytes that came: ``check_answers`` parses and checks them once the
    window has closed. Between a response's last byte and the next
    request's first the walker does no work of the check's."""

    def __init__(self, url, mix, book, bodies, pool, timeout_s):
        self.mix, self.book, self.bodies = mix, book, bodies
        self.chunk = int(mix["write_rows_per_request"])
        per = int(mix["rows_per_request"])
        self.queries = [(lo, np.ascontiguousarray(pool[lo:lo + per],
                                                  dtype="<f4").tobytes())
                        for lo in range(0, pool.shape[0], per)]
        self.writer = Conn(url, timeout_s)
        self.readers = [Conn(url, timeout_s)
                        for _ in range(int(mix["search_in_flight"]))]
        self.requests: list = []  # (kind, ok, rows) of the window's writes
        # (window cycle, deletes acknowledged before the step's first
        # request: a count of cycles, pool row, status, bytes) a /query
        self.answers: list = []
        self.deleted_cycles = 0  # cycles whose delete step is acknowledged
        self.step_s = {"insert": 0.0, "search": 0.0, "delete": 0.0}
        self.cycles: list = []  # the window's, one dict a cycle
        self.jobs: queue.SimpleQueue = queue.SimpleQueue()
        self.back: queue.SimpleQueue = queue.SimpleQueue()
        self.threads = [threading.Thread(target=self.reader, args=(c,),
                                         daemon=True, name="runbook-reader")
                        for c in self.readers]
        for t in self.threads:
            t.start()

    def reader(self, conn: Conn) -> None:
        """One reader's whole life: take the step's next request, send it,
        hand back what came."""
        while True:
            job = self.jobs.get()
            if job is None:
                return
            lo, body = job
            self.back.put((lo, *conn.post("/query", "reader", body)))

    def write(self, path: str, body: bytes, window: bool) -> None:
        status, data = self.writer.post(path, "writer", body)
        doc = document(data)
        if status == 400:
            raise Refused(f"the server refused a raw {path} body as "
                          f"malformed: 400 {doc}")
        done = "upserted" if path == "/upsert" else "deleted"
        ok = status == 200 and doc.get(done) == self.chunk
        if window:
            self.requests.append((path[1:], ok, self.chunk))
        elif not ok:
            raise Refused(f"a warm cycle's {path} came back {status} {doc}")

    def search(self, w: int, window: bool) -> None:
        """The whole pool as requests of ``rows_per_request`` rows, the
        mix's number in flight; every answer is kept as it came."""
        for job in self.queries:
            self.jobs.put(job)
        for _ in self.queries:
            lo, status, data = self.back.get()
            if window:
                self.answers.append((w, self.deleted_cycles, lo, status,
                                     data))
            elif status != 200:
                raise Refused(f"a warm cycle's /query came back {status}")

    def cycle(self, i: int, w: int, window: bool, t_cycle: float) -> float:
        """Cycle ``i`` of the runbook (``w``: its number in the window,
        from 1), step by step, from ``t_cycle`` on the walker's clock;
        returns the clock at its end."""
        from benchmark import runbook

        conns = [self.writer, *self.readers]
        took, spans = {}, []  # a step's seconds; a step's requests by conn
        ins, dels = self.bodies[i]
        t = t_cycle
        for op in runbook.steps(self.book["cycles"][i]):
            kind = op["operation"]
            if kind == "insert":
                for body in ins:
                    self.write("/upsert", body, window)
            elif kind == "search":
                self.search(w, window)
            else:
                for body in dels:
                    self.write("/delete", body, window)
                self.deleted_cycles = i + 1
            now = time.monotonic()
            took[kind], t = now - t, now
            for c in conns:
                spans.append(c.spans[:])
                c.spans.clear()
            if window:
                self.step_s[kind] += took[kind]
        if window:
            self.cycles.append(cycle_seconds(t_cycle, t, took, spans))
        return t

    def close(self) -> None:
        for _ in self.threads:
            self.jobs.put(None)
        for t in self.threads:
            t.join(10)
        for c in [self.writer, *self.readers]:
            c.close()


def check_answers(answers: list, book: dict, mix: dict, k: int,
                  probe_lo: int) -> dict:
    """Every ``/query`` answer of the window, parsed and checked, in the
    order of their steps: ``requests`` (``("query", whole, rows)`` each),
    ``deleted_returned`` (ids whose delete was acknowledged before the
    answer's step sent its first request) and ``probe`` (window cycle ->
    the probe rows' (ids, dists) at the mix's checkpoints)."""
    from benchmark import loadgen

    per, n_probe = int(mix["rows_per_request"]), int(mix["probe_rows"])
    deleted = np.zeros(book["ids"], dtype=bool)
    applied = 0
    out = {"requests": [], "deleted_returned": 0, "probe": {}}
    for w, gone, lo, status, data in sorted(answers, key=lambda a: a[:3]):
        for cycle in book["cycles"][applied:gone]:
            deleted[cycle["delete"][0]:cycle["delete"][1]] = True
        applied = max(applied, gone)
        answer = (loadgen.check_answer(document(data), per, k)
                  if status == 200 else None)
        out["requests"].append(("query", answer is not None, per))
        if answer is None:
            continue
        ids, dists = answer
        known = (ids >= 0) & (ids < deleted.shape[0])
        out["deleted_returned"] += int(
            deleted[np.where(known, ids, 0)][known].sum())
        at = probe_lo - lo
        if w in mix["checkpoints"] and 0 <= at < per:
            out["probe"][w] = (ids[at:at + n_probe], dists[at:at + n_probe])
    return out


def cycle_seconds(t0: float, t1: float, took: dict, spans: list) -> dict:
    """One cycle on the walker's clock: its length ``s``, its three steps,
    ``turn`` (summed over the connections: from a response's last byte to
    the same connection's next request's first byte, within a step) and
    ``empty`` (the seconds of the cycle with no request in flight on any
    connection: the server had nothing of the caller's to work on)."""
    turn = sum(b[0] - a[1] for one in spans for a, b in zip(one, one[1:]))
    busy, end = 0.0, t0
    for sent, done in sorted(x for one in spans for x in one):
        if done > end:
            busy += done - max(sent, end)
            end = done
    return {"s": t1 - t0, **took, "turn": turn, "empty": (t1 - t0) - busy}


HOST_FAMILIES = (
    "serve_batch_overrun_seconds_total", "python_gc_seconds_total",
    "python_gc_collections_total", "serve_batch_phase_seconds_total",
    "mutation_phase_seconds_total", "frontend_request_phase_seconds_total",
    "serve_pump_cpu_seconds_total", "serve_batches_total")


def host_delta(window_delta: dict) -> dict:
    """What the server's own counters say of its host side over the window
    (the overrun record by cause, collections, the pump's and the writes'
    phases): read in every run, so that a slow untraced run says why."""
    return {k: round(v, 6) for k, v in sorted(window_delta.items())
            if k.startswith(HOST_FAMILIES) and v}


def run(cell: dict, args, t_start: float):
    if "jax" in sys.modules:
        raise RuntimeError("the serving parent must stay off jax: the "
                           "child holds the chip")
    from benchmark import harness

    run_dir = os.path.join(harness.OUT_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg_path = os.path.join(run_dir, "config.json")
    mix_path = os.path.join(run_dir, "traffic.json")
    for path, doc in ((cfg_path, cell["config"]), (mix_path, cell["traffic"])):
        with open(path, "w") as f:
            json.dump(doc, f)
    cmd = [sys.executable, os.path.join(BENCH, "serve_launcher_stream.py"),
           "--config", cfg_path, "--traffic", mix_path,
           "--seed", str(args.seed), "--run-dir", run_dir,
           "--chips", str(cell["chips"])]
    if args.control:
        cmd.append("--control")
    if args.allow_cpu:
        cmd.append("--allow-cpu")
    child = subprocess.Popen(cmd, cwd=ROOT)
    try:
        return drive(cell, args, t_start, child, run_dir)
    except Refused as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return None
    finally:
        if child.poll() is None:
            child.terminate()
            try:
                child.wait(60)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()


def checkpoint_verdict(probe: dict, ref, config: dict, mix: dict) -> dict:
    """``{name: [value, limit, ok]}`` of the comparison with the reference:
    the worst reading over the checkpoints the window reached, each
    checkpoint's own on a line before."""
    from benchmark import compare
    from benchmark.harness import say

    worst: dict = {}
    pick = {"recall_at_k": min}
    for w in sorted(probe):
        ids, dists = probe[w]
        v = compare.compare_answers(ids, dists, ref[f"ids_{w}"],
                                    ref[f"dists_{w}"], config["limits"])
        say(f"checkpoint cycle {w}: "
            + json.dumps({n: x[0] for n, x in v["numbers"].items()})
            + f" info {json.dumps(v.get('info'))}")
        for name, (value, limit, ok) in v["numbers"].items():
            if name in worst:
                value = pick.get(name, max)(value, worst[name][0])
                ok = ok and worst[name][2]
            worst[name] = [value, limit, ok]
    reached = len(probe)
    worst["checkpoints_reached"] = [reached, 2, reached >= 2]
    least = float(mix["touched_share_min"])
    rows = int(config["rows"])
    by_inserts = min(float((ref[f"ids_{w}"] >= rows).any(axis=1).mean())
                     for w in mix["checkpoints"])
    by_deletes = float(ref["touched_by_deletes"].mean())
    worst["probe_touched_by_inserts_share"] = [by_inserts, least,
                                               by_inserts >= least]
    worst["probe_touched_by_deletes_share"] = [by_deletes, least,
                                               by_deletes >= least]
    return worst


def drive(cell, args, t_start, child, run_dir):
    from benchmark import compare, harness, loadgen, runbook
    from benchmark.harness import say

    serve = harness.load_by_path("drivers", "serve")
    config, mix = cell["config"], cell["traffic"]
    # everything the walk will send, made from the seed while the child
    # builds its index: no row is drawn and no body cut inside the window
    t_made = time.time()
    gen = harness.datagen_for(config)
    book = runbook.plan(config, mix, args.seed)
    subs = gen.sub_centres(args.seed, config["data"], config["dim"],
                           book["cluster_of_block"])
    pool = gen.query_rows(args.seed, config["data"],
                          runbook.pool_targets(book, mix, args.seed), subs)
    probe_lo = runbook.probe_block(args.seed, pool.shape[0],
                                   int(mix["probe_rows"]))
    bodies = write_bodies(config, mix, book, subs, gen, args.seed)
    bodies_s = time.time() - t_made
    ready_path = os.path.join(run_dir, "ready.json")
    ready = serve.wait_for(ready_path, child, 1100)
    if ready is None:
        print("error: the serving child did not come up "
              f"(exit code {child.poll()})", file=sys.stderr, flush=True)
        return None
    # the check's cost, as the reference is: what of it outlasted the
    # child's own set-up (the server stood ready and the walker was still
    # cutting bodies) is taken out of setup_s, like ref_s
    bodies_late_s = max(0.0, t_made + bodies_s - os.path.getmtime(ready_path))
    say("bodies " + json.dumps({
        "cycles": len(bodies), "bytes": sum(
            len(b) for ins, dels in bodies for b in ins + dels),
        "bodies_s": bodies_s, "bodies_late_s": bodies_late_s}))
    url, device = ready["url"], ready["device"]
    peaks = harness.peaks_for(device["kind"], args.allow_cpu)
    walk = Walk(url, mix, book, bodies, pool,
                float(config["request_timeout_s"]))
    warm = int(mix["warm_cycles"])
    t = time.monotonic()
    for i in range(warm):
        t = walk.cycle(i, 0, False, t)

    traced: dict = {}
    tracer = None
    if args.trace:
        tracer = threading.Thread(
            target=serve.traced_span, daemon=True,
            args=(child, run_dir, url, 0.2 * args.seconds,
                  float(mix["trace_seconds"]), traced))
    before = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
    # what the user waited for before the first timed request, less the
    # reference and the bodies (the check's cost, not the system's) and the
    # runtime's hand-over of the chip (the machine's, not the program's)
    setup_s = (time.time() - t_start - ready["ref_s"] - ready["chip_wait_s"]
               - bodies_late_s)
    if tracer:
        tracer.start()
    gc.collect()
    gc.disable()  # the walker's own collections are no part of a cycle
    t0 = t = time.monotonic()
    w = 0
    while t - t0 < args.seconds and warm + w < len(book["cycles"]):
        w += 1
        t = walk.cycle(warm + w - 1, w, True, t)
    window_s = t - t0
    gc.enable()
    after = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
    if tracer:
        tracer.join(200)
    walk.close()
    window_delta = loadgen.metrics_delta(before, after)
    child.send_signal(signal.SIGTERM)  # it writes final.json meanwhile
    # every answer of the window, now that no request waits on the walker
    checked = check_answers(walk.answers, book, mix, int(config["k"]),
                            probe_lo)
    requests = walk.requests + checked["requests"]
    good_rows = sum(r for kind, ok, r in requests if kind == "query" and ok)
    failed = sum(not ok for _, ok, _ in requests)
    numbers = {
        "attempted": len(requests), "failed": failed, "cycles": w,
        "rows_per_s": good_rows / window_s if good_rows else None,
        "rows_answered": good_rows, "window_s": window_s,
        "rows_inserted": sum(r for kind, ok, r in requests
                             if kind == "upsert" and ok),
        "rows_deleted": sum(r for kind, ok, r in requests
                            if kind == "delete" and ok),
        "step_s": walk.step_s, "answers_checked": len(checked["requests"]),
    }
    # the cell's throughput is an end-to-end metric of its own (PR 53: a
    # bound this cell can keep; BENCHMARK.json has no bound a cell)
    numbers["stream_rows_per_s"] = numbers["rows_per_s"]
    say("window " + json.dumps({**numbers, "setup_s": setup_s}))
    say("cycles " + json.dumps({
        key: [round(c[key], 5) for c in walk.cycles]
        for key in ("s", "insert", "search", "delete", "turn", "empty")}))
    say("host " + json.dumps(host_delta(window_delta)))

    try:
        rc = child.wait(300)
    except subprocess.TimeoutExpired:
        rc = None
    if rc != 0:
        print(f"error: the serving child exited with {rc}, not 0",
              file=sys.stderr, flush=True)
        return None
    with open(os.path.join(run_dir, "final.json")) as f:
        final = json.load(f)

    ref = np.load(os.path.join(run_dir, "probe_ref.npz"))
    verdict = checkpoint_verdict(checked["probe"], ref, config, mix)
    compiled = sum(v for name, v in window_delta.items() if name.startswith(
        ("serve_executables_compiled_total",
         "mutation_executables_compiled_total")))
    verdict["deleted_id_returned"] = [checked["deleted_returned"], 0,
                                      checked["deleted_returned"] == 0]
    verdict["compiled_in_window"] = [compiled, 0, compiled == 0]
    verdict["answers_misshapen_or_failed"] = [failed, 0, failed == 0]
    compare.say(verdict)
    say(f"compared the answers for probe rows {probe_lo}.."
        f"{probe_lo + int(mix['probe_rows']) - 1} at the search steps of "
        f"cycles {sorted(checked['probe'])} of {w}; checked "
        f"{len(checked['requests'])} answers of {len(walk.answers)}")
    correct = all(v[2] for v in verdict.values())

    result = {
        "correct": bool(correct),
        "attempted": int(numbers["attempted"]),
        "failed": int(failed),
        "metrics": harness.end_to_end(
            cell, {**numbers, "setup_s": setup_s}),
        "device": final["device"],
    }
    if args.trace:
        delta = traced.get("delta") or {}
        scopes = final.get("scopes")
        harness.add_trace(
            result, cell, final["trace"], peaks,
            q_rows=delta.get("serve_queries_total", 0.0),
            batches=delta.get("serve_batches_total", 0.0),
            traced_metrics_delta=delta or None,
            window_metrics_delta=window_delta,
            scopes=dict(scopes) if scopes else None,
            stream={"window_s": window_s, "step_s": walk.step_s,
                    "dim": config["dim"], "cycles": walk.cycles})
        if scopes and "breakdown" in result:
            result["breakdown"]["scopes"] = scopes
    return result
