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
    transport, any route)."""

    def __init__(self, url: str, timeout_s: float):
        u = urllib.parse.urlsplit(url)
        self.host, self.port, self.timeout_s = u.hostname, u.port, timeout_s
        self.conn = None

    def close(self) -> None:
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None

    def post(self, path: str, tenant: str, body: bytes) -> tuple:
        """(status, document); status 0: no answer at all."""
        for _ in range(2):
            fresh = self.conn is None
            try:
                if fresh:
                    self.conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout_s)
                    self.conn.connect()
                    self.conn.sock.setsockopt(socket.IPPROTO_TCP,
                                              socket.TCP_NODELAY, 1)
                self.conn.request(
                    "POST", path, body=body,
                    headers={"Content-Type": "application/octet-stream",
                             TENANT_HEADER: tenant})
                resp = self.conn.getresponse()
                data = resp.read()
                try:
                    return resp.status, json.loads(data)
                except ValueError:
                    return resp.status, {}
            except (OSError, http.client.HTTPException):
                self.close()
                if fresh:
                    return 0, {}
        return 0, {}


class Walk:
    """The runbook's caller: its connections, the bodies of the writes
    (made a cycle ahead on a thread of their own, so that making rows is
    not inside a write step) and what every request of the window did."""

    def __init__(self, url, config, mix, book, subs, gen, seed, pool,
                 probe_lo, timeout_s):
        from benchmark import runbook

        self.config, self.mix, self.book = config, mix, book
        self.pool, self.probe_lo = pool, probe_lo
        self.k = int(config["k"])
        self.writer = Conn(url, timeout_s)
        self.readers = [Conn(url, timeout_s)
                        for _ in range(int(mix["search_in_flight"]))]
        self.deleted = np.zeros(book["ids"], dtype=bool)  # acknowledged
        self.requests: list = []  # (kind, ok, rows) of the window
        self.probe: dict = {}  # window cycle -> (ids, dists) of the probe
        self.deleted_returned = 0
        self.step_s = {"insert": 0.0, "search": 0.0, "delete": 0.0}
        self.lock = threading.Lock()
        chunk = int(mix["write_rows_per_request"])

        def bodies(cycle):
            lo, hi = cycle["insert"]
            rows = np.concatenate([
                gen.host_block(seed, config["data"], b, subs[b])
                for b in runbook.blocks_of(cycle["insert"],
                                           book["block_rows"])])
            ins = [np.arange(a, a + chunk, dtype="<i4").tobytes()
                   + np.ascontiguousarray(rows[a - lo:a - lo + chunk],
                                          dtype="<f4").tobytes()
                   for a in range(lo, hi, chunk)]
            lo, hi = cycle["delete"]
            return ins, [np.arange(a, a + chunk, dtype="<i4").tobytes()
                         for a in range(lo, hi, chunk)]

        self.ahead: queue.Queue = queue.Queue(maxsize=2)

        def make():
            for cycle in book["cycles"]:
                self.ahead.put(bodies(cycle))

        threading.Thread(target=make, daemon=True,
                         name="runbook-bodies").start()

    def write(self, path: str, body: bytes, rows: int, window: bool) -> None:
        status, doc = self.writer.post(path, "writer", body)
        if status == 400:
            raise Refused(f"the server refused a raw {path} body as "
                          f"malformed: 400 {doc}")
        done = "upserted" if path == "/upsert" else "deleted"
        ok = status == 200 and doc.get(done) == rows
        if window:
            self.requests.append((path[1:], ok, rows))
        elif not ok:
            raise Refused(f"a warm cycle's {path} came back {status} {doc}")

    def search(self, w: int, window: bool) -> None:
        """The whole pool as requests of ``rows_per_request`` rows, the
        mix's number in flight; keeps the probe rows' answers of a
        checkpoint cycle."""
        from benchmark import loadgen

        per = int(self.mix["rows_per_request"])
        jobs: queue.Queue = queue.Queue()
        for lo in range(0, self.pool.shape[0], per):
            jobs.put(lo)
        deleted = self.deleted  # acknowledged before any of them is sent
        keep = window and w in self.mix["checkpoints"]
        n_probe = int(self.mix["probe_rows"])

        def reader(conn):
            while True:
                try:
                    lo = jobs.get_nowait()
                except queue.Empty:
                    return
                body = np.ascontiguousarray(self.pool[lo:lo + per],
                                            dtype="<f4").tobytes()
                status, doc = conn.post("/query", "reader", body)
                answer = (loadgen.check_answer(doc, per, self.k)
                          if status == 200 else None)
                if not window:
                    if answer is None:
                        raise Refused("a warm cycle's /query came back "
                                      f"{status}")
                    continue
                with self.lock:
                    self.requests.append(("query", answer is not None, per))
                    if answer is None:
                        continue
                    ids, dists = answer
                    known = (ids >= 0) & (ids < deleted.shape[0])
                    self.deleted_returned += int(
                        deleted[np.where(known, ids, 0)][known].sum())
                    at = self.probe_lo - lo
                    if keep and 0 <= at < per:
                        self.probe[w] = (ids[at:at + n_probe],
                                         dists[at:at + n_probe])

        errors: list = []

        def guarded(conn):
            try:
                reader(conn)
            except Refused as e:
                errors.append(e)

        threads = [threading.Thread(target=guarded, args=(c,), daemon=True)
                   for c in self.readers]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def cycle(self, i: int, w: int, window: bool) -> None:
        """Cycle ``i`` of the runbook (``w``: its number in the window,
        from 1), step by step."""
        from benchmark import runbook

        chunk = int(self.mix["write_rows_per_request"])
        ins, dels = self.ahead.get()
        for op in runbook.steps(self.book["cycles"][i]):
            t = time.monotonic()
            kind = op["operation"]
            if kind == "insert":
                for body in ins:
                    self.write("/upsert", body, chunk, window)
            elif kind == "search":
                self.search(w, window)
            else:
                for body in dels:
                    self.write("/delete", body, chunk, window)
                self.deleted[op["start"]:op["end"]] = True
            if window:
                self.step_s[kind] += time.monotonic() - t

    def close(self) -> None:
        for c in [self.writer, *self.readers]:
            c.close()


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


def checkpoint_verdict(walk: Walk, ref, config: dict, mix: dict) -> dict:
    """``{name: [value, limit, ok]}`` of the comparison with the reference:
    the worst reading over the checkpoints the window reached, each
    checkpoint's own on a line before."""
    from benchmark import compare
    from benchmark.harness import say

    worst: dict = {}
    pick = {"recall_at_k": min}
    for w in sorted(walk.probe):
        ids, dists = walk.probe[w]
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
    reached = len(walk.probe)
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
    ready = serve.wait_for(os.path.join(run_dir, "ready.json"), child, 1100)
    if ready is None:
        print("error: the serving child did not come up "
              f"(exit code {child.poll()})", file=sys.stderr, flush=True)
        return None
    url, device = ready["url"], ready["device"]
    peaks = harness.peaks_for(device["kind"], args.allow_cpu)
    gen = harness.datagen_for(config)
    book = runbook.plan(config, mix, args.seed)
    subs = gen.sub_centres(args.seed, config["data"], config["dim"],
                           book["cluster_of_block"])
    pool = gen.query_rows(args.seed, config["data"],
                          runbook.pool_targets(book, mix, args.seed), subs)
    probe_lo = runbook.probe_block(args.seed, pool.shape[0],
                                   int(mix["probe_rows"]))
    walk = Walk(url, config, mix, book, subs, gen, args.seed, pool, probe_lo,
                float(config["request_timeout_s"]))
    warm = int(mix["warm_cycles"])
    for i in range(warm):
        walk.cycle(i, 0, window=False)

    traced: dict = {}
    tracer = None
    if args.trace:
        tracer = threading.Thread(
            target=serve.traced_span, daemon=True,
            args=(child, run_dir, url, 0.2 * args.seconds,
                  float(mix["trace_seconds"]), traced))
    before = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
    # what the user waited for before the first timed request, less the
    # reference (the check's cost, not the system's) and the runtime's
    # hand-over of the chip (the machine's, not the program's)
    setup_s = time.time() - t_start - ready["ref_s"] - ready["chip_wait_s"]
    if tracer:
        tracer.start()
    t0 = time.monotonic()
    w = 0
    while time.monotonic() - t0 < args.seconds and warm + w < len(
            book["cycles"]):
        w += 1
        walk.cycle(warm + w - 1, w, window=True)
    window_s = time.monotonic() - t0
    after = loadgen.parse_metrics(loadgen.fetch(url, "/metrics"))
    if tracer:
        tracer.join(200)
    walk.close()
    window_delta = loadgen.metrics_delta(before, after)
    good_rows = sum(r for kind, ok, r in walk.requests
                    if kind == "query" and ok)
    failed = sum(not ok for _, ok, _ in walk.requests)
    numbers = {
        "attempted": len(walk.requests), "failed": failed, "cycles": w,
        "rows_per_s": good_rows / window_s if good_rows else None,
        "rows_answered": good_rows, "window_s": window_s,
        "rows_inserted": sum(r for kind, ok, r in walk.requests
                             if kind == "upsert" and ok),
        "rows_deleted": sum(r for kind, ok, r in walk.requests
                            if kind == "delete" and ok),
        "step_s": walk.step_s,
    }
    say("window " + json.dumps({**numbers, "setup_s": setup_s}))

    child.send_signal(signal.SIGTERM)
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
    verdict = checkpoint_verdict(walk, ref, config, mix)
    compiled = sum(v for name, v in window_delta.items() if name.startswith(
        ("serve_executables_compiled_total",
         "mutation_executables_compiled_total")))
    verdict["deleted_id_returned"] = [walk.deleted_returned, 0,
                                      walk.deleted_returned == 0]
    verdict["compiled_in_window"] = [compiled, 0, compiled == 0]
    verdict["answers_misshapen_or_failed"] = [failed, 0, failed == 0]
    compare.say(verdict)
    say(f"compared the answers for probe rows {probe_lo}.."
        f"{probe_lo + int(mix['probe_rows']) - 1} at the search steps of "
        f"cycles {sorted(walk.probe)} of {w}")
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
                    "dim": config["dim"]})
        if scopes and "breakdown" in result:
            result["breakdown"]["scopes"] = scopes
    return result
