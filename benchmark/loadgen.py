"""The benchmark's load generator for the serving cells. No jax.

One general generator reads a traffic mix (a data file of parameters under
``traffic/``) and drives a running server over HTTP with raw little-endian
float32 bodies on keep-alive connections (transport copied from
``mpi_knn_tpu/frontend/loadgen.py``: ``post_query``, the keep-alive worker,
``_percentiles_ms``):

- ``"loop": "open"``: requests go out on a schedule fixed before the window,
  whatever the server does; latency runs from the moment a request was DUE,
  and how late each was really sent is reported beside it. The inter-arrival
  gaps are the quantiles of the exponential law at the mix's rate (arrivals
  are Poisson in law), the sizes the quantiles of the mix's size law, the
  tenants the mix's shares, in an order drawn once from the mix's own
  ``schedule_seed``; ``--seed`` turns that cycle to another start and makes
  the data. The seed must not change the work, nor which requests meet.
- ``"loop": "closed"``: each client sends a request, waits for the reply and
  sends the next. Clients start ``lead_in_s`` before the window opens and
  finish their request in flight after it, so the window opens and closes at
  the end of a burst of completions (the last before its nominal start, the
  first after its nominal end): they come in bursts of one batch, and a
  window cut at fixed instants, or inside a burst, would count part of a
  batch more or less from one run to the next.

Query rows come from a pool of corpus-shaped rows made from the seed. One
256-row block of the pool is the probe: the launcher holds the reference
answers for it, and every answer of the window for a row of that block is
kept and compared after the window.
"""

from __future__ import annotations

import http.client
import json
import queue
import socket
import threading
import time
import urllib.parse

import numpy as np

TENANT_HEADER = "X-Tenant"
PROBE_BLOCK = 256


# ---------------------------------------------------------------------------
# the schedule: pure arithmetic from (mix, seed, seconds)


def size_quantiles(law: dict, n: int) -> np.ndarray:
    """``n`` request sizes: the (i + 0.5) / n quantiles of the size law."""
    if law["law"] == "fixed":
        return np.full(n, int(law["rows"]), dtype=np.int64)
    if law["law"] == "inverse":  # P(r) ~ 1/r on min..max
        r = np.arange(int(law["min"]), int(law["max"]) + 1)
        cdf = np.cumsum(1.0 / r) / np.sum(1.0 / r)
        u = (np.arange(n) + 0.5) / n
        return r[np.searchsorted(cdf, u, side="left").clip(0, len(r) - 1)]
    raise ValueError(f"unknown size law {law['law']!r}")


def tenant_counts(shares: list, n: int) -> np.ndarray:
    """How many of ``n`` requests each tenant sends (largest remainders)."""
    want = np.asarray(shares, dtype=np.float64) / np.sum(shares) * n
    base = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - base))[: n - int(base.sum())]:
        base[i] += 1
    return base


def pool_offsets(rng, n: int, rows: np.ndarray, pool_rows: int,
                 probe_lo: int) -> np.ndarray:
    """Where in the pool each request's rows start. The first of the longest
    requests starts at the probe block, so the longest is always checked."""
    off = rng.integers(0, pool_rows, size=n)
    if n:
        off[int(np.argmax(rows))] = probe_lo
    return off


def open_schedule(mix: dict, seed: int, seconds: float, pool_rows: int,
                  probe_lo: int) -> dict:
    """The window's requests: when each is due, its rows, its tenant and
    where in the pool its rows start.

    The sequence of (gap, rows, tenant) is drawn once, from the mix's own
    ``schedule_seed``, and ``--seed`` only says where in that cycle the
    window starts: every seed sends the same requests with the same
    neighbours, so the bursts and lulls that make a tail are the same in
    every run, and what differs is the data and the start."""
    rate = float(mix["rate_requests_per_s"])
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng([int(mix["schedule_seed"]), 0x5C])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = base.permutation(gaps * (seconds / gaps.sum()))
    rows = base.permutation(size_quantiles(mix["rows_per_request"], n))
    counts = tenant_counts(mix["tenant_shares"], n)
    tenant = base.permutation(np.repeat(np.arange(len(counts)), counts))
    rng = np.random.default_rng([int(seed), 0x5C])
    turn = int(rng.integers(0, n))
    gaps, rows, tenant = (np.roll(a, -turn) for a in (gaps, rows, tenant))
    return {
        "due_s": np.cumsum(gaps) - gaps[0],  # the first request is due at 0
        "rows": rows, "tenant": tenant,
        "offset": pool_offsets(rng, n, rows, pool_rows, probe_lo),
    }


def closed_request(mix: dict, client: int, j: int, pool_rows: int) -> tuple:
    """(rows, pool offset) of client ``client``'s j-th request: the clients
    walk the pool's blocks, each from a block of its own."""
    rows = int(mix["rows_per_request"]["rows"])
    blocks = max(1, pool_rows // rows)
    return rows, ((client * 5 + j) % blocks) * rows


def probe_block(seed: int, pool_rows: int) -> int:
    """First pool row of the probe block, from the seed."""
    rng = np.random.default_rng([int(seed), 0x9B])
    return int(rng.integers(0, pool_rows // PROBE_BLOCK)) * PROBE_BLOCK


def take(pool: np.ndarray, offset: int, rows: int) -> np.ndarray:
    idx = (offset + np.arange(rows)) % pool.shape[0]
    return pool[idx]


# ---------------------------------------------------------------------------
# transport


def fetch(url: str, path: str, timeout_s: float = 10.0) -> str:
    import urllib.request

    with urllib.request.urlopen(url.rstrip("/") + path,
                                timeout=timeout_s) as resp:
        return resp.read().decode()


def parse_metrics(text: str) -> dict:
    """``{sample name with labels: value}`` of a Prometheus exposition."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        out[name] = float(value)
    return out


def metrics_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Conn:
    """One keep-alive connection with Nagle off (headers and the raw body
    go out as separate sends; Nagle with delayed ACK would stall every
    second one ~40 ms)."""

    def __init__(self, url: str, timeout_s: float):
        u = urllib.parse.urlsplit(url)
        self.host, self.port, self.timeout_s = u.hostname, u.port, timeout_s
        self.conn = None

    def open(self) -> None:
        self.conn = http.client.HTTPConnection(self.host, self.port,
                                               timeout=self.timeout_s)
        self.conn.connect()
        self.conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def close(self) -> None:
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None

    def post(self, tenant: str, q: np.ndarray) -> tuple:
        """(status, document). A stale keep-alive connection is reopened
        and the request sent once more; a failure on a fresh connection is
        the server's and comes back as status 0."""
        body = np.ascontiguousarray(q, dtype="<f4").tobytes()
        for _ in range(2):
            fresh = self.conn is None
            try:
                if fresh:
                    self.open()
                self.conn.request(
                    "POST", "/query", body=body,
                    headers={"Content-Type": "application/octet-stream",
                             TENANT_HEADER: tenant},
                )
                resp = self.conn.getresponse()
                data = resp.read()
                if resp.status != 200:
                    return resp.status, {}
                return 200, json.loads(data)
            except (OSError, http.client.HTTPException, ValueError):
                self.close()
                if fresh:
                    return 0, {}
        return 0, {}


def check_answer(doc: dict, rows: int, k: int):
    """(ids, dists) as arrays where the reply has the right shape and
    finite ascending distances, else None."""
    try:
        ids = np.asarray(doc["ids"], dtype=np.int64)
        dists = np.asarray(doc["dists"], dtype=np.float64)
    except (KeyError, ValueError, TypeError):
        return None
    if ids.shape != (rows, k) or dists.shape != (rows, k):
        return None
    if not np.isfinite(dists).all() or (np.diff(dists, axis=1) < 0).any():
        return None
    return ids, dists


class Log:
    """What the window's requests did, filled by the worker threads."""

    def __init__(self, probe_lo: int, pool_rows: int, k: int):
        self.lock = threading.Lock()
        self.requests: list = []  # dicts
        self.probe: list = []  # (pool row, ids row, dists row)
        self.probe_lo, self.pool_rows, self.k = probe_lo, pool_rows, k

    def record(self, *, due, sent, done, status, rows, offset, tenant, doc):
        answer = check_answer(doc, rows, self.k) if status == 200 else None
        entry = {"due": due, "sent": sent, "done": done, "status": status,
                 "rows": rows, "tenant": tenant,
                 "ok": answer is not None}
        kept = []
        if answer is not None:
            pool_idx = (offset + np.arange(rows)) % self.pool_rows
            hit = np.nonzero((pool_idx >= self.probe_lo)
                             & (pool_idx < self.probe_lo + PROBE_BLOCK))[0]
            kept = [(int(pool_idx[i]), answer[0][i], answer[1][i], done)
                    for i in hit]
        with self.lock:
            self.requests.append(entry)
            self.probe.extend(kept)


def run_open(url: str, mix: dict, sched: dict, pool: np.ndarray, log: Log,
             seconds: float, timeout_s: float) -> dict:
    """Send the schedule; return when every request is done or the drain
    time after the window has passed."""
    jobs: queue.Queue = queue.Queue()
    n = len(sched["due_s"])
    t0 = time.monotonic() + 0.05

    def worker():
        conn = Conn(url, timeout_s)
        try:
            conn.open()
        except OSError:
            conn.conn = None
        while True:
            i = jobs.get()
            if i is None:
                break
            rows, off = int(sched["rows"][i]), int(sched["offset"][i])
            tenant = f"tenant-{int(sched['tenant'][i])}"
            q = take(pool, off, rows)
            sent = time.monotonic()
            status, doc = conn.post(tenant, q)
            log.record(due=t0 + float(sched["due_s"][i]), sent=sent,
                       done=time.monotonic(), status=status, rows=rows,
                       offset=off, tenant=tenant, doc=doc)
        conn.close()

    workers = [threading.Thread(target=worker, daemon=True,
                                name=f"loadgen-{w}")
               for w in range(int(mix["connections"]))]
    for w in workers:
        w.start()
    for i in range(n):
        delay = t0 + float(sched["due_s"][i]) - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        jobs.put(i)
    for _ in workers:
        jobs.put(None)
    deadline = t0 + seconds + float(mix["drain_s"])
    for w in workers:
        w.join(max(0.0, deadline - time.monotonic()))
    with log.lock:
        done = len(log.requests)
    return {"t0": t0, "t_end": t0 + seconds, "scheduled": n,
            "unfinished": n - done}


def run_closed(url: str, mix: dict, pool: np.ndarray, log: Log,
               seconds: float, timeout_s: float) -> dict:
    """Clients start now; the window opens ``lead_in_s`` later and closes
    ``seconds`` after that; each client finishes its request in flight."""
    lead = float(mix["lead_in_s"])
    t_start = time.monotonic()
    t0, t_end = t_start + lead, t_start + lead + seconds

    def client(c: int):
        conn = Conn(url, timeout_s)
        tenant = f"tenant-{c % int(mix['tenants'])}"
        j = 0
        while time.monotonic() < t_end:
            rows, off = closed_request(mix, c, j, pool.shape[0])
            sent = time.monotonic()
            status, doc = conn.post(tenant, take(pool, off, rows))
            log.record(due=sent, sent=sent, done=time.monotonic(),
                       status=status, rows=rows, offset=off, tenant=tenant,
                       doc=doc)
            j += 1
            if status != 200:
                time.sleep(0.05)  # a refusing server is not hammered
        conn.close()

    clients = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"loadgen-client-{c}")
               for c in range(int(mix["clients"]))]
    for c in clients:
        c.start()
    for c in clients:
        c.join(max(0.0, t_end + timeout_s - time.monotonic()))
    return {"t0": t0, "t_end": t_end, "scheduled": None,
            "unfinished": sum(c.is_alive() for c in clients)}


# ---------------------------------------------------------------------------
# reduction of the log to numbers


def percentile_ms(lat_s: list, pct: float):
    if not lat_s:
        return None
    return float(np.percentile(np.asarray(lat_s), pct)) * 1e3


def reduce_open(log: Log, span: dict) -> dict:
    reqs = log.requests
    good = [r for r in reqs if r["ok"]]
    lat = [r["done"] - r["due"] for r in good]
    by_due = sorted(good, key=lambda r: r["due"])
    third = max(1, len(by_due) // 3)
    first = [r["done"] - r["due"] for r in by_due[:third]]
    last = [r["done"] - r["due"] for r in by_due[-third:]]
    refused = sum(r["status"] in (429, 503) for r in reqs)
    return {
        "attempted": span["scheduled"],
        "failed": span["scheduled"] - len(good),
        "refused": refused,
        "unfinished": span["unfinished"],
        "request_p50_ms": percentile_ms(lat, 50),
        "first_third_p50_ms": percentile_ms(first, 50),
        "last_third_p50_ms": percentile_ms(last, 50),
        "rows_answered": int(sum(r["rows"] for r in good)),
        "late_s": [r["sent"] - r["due"] for r in reqs],
        "latency_s": lat,
        "window_s": span["t_end"] - span["t0"],
    }


def burst_ends(done: np.ndarray) -> np.ndarray:
    """Completions come in bursts of one batch: the instants at which a
    burst ended, a burst being completions closer together than half the
    mean spacing of all of them (evenly spaced completions, one a batch, are
    each a burst of their own)."""
    if len(done) < 2:
        return done
    spacing = (done[-1] - done[0]) / (len(done) - 1)
    last_of_burst = np.append(np.diff(done) > 0.5 * spacing, True)
    return done[last_of_burst]


def reduce_closed(log: Log, span: dict) -> dict:
    """The window opens at the end of the last burst of completions that
    ended at or before ``t0`` (at ``t0`` where none did) and closes at the
    end of the first that ended at or after ``t_end`` — the clients finish
    the requests in flight — or at ``t_end`` itself where no completion
    follows: whole batches only, and never less than the nominal window, so
    a stall or an outage anywhere in it is inside both the time and the
    count of failures. The rate is every row completed between the two over
    the time between them; a request that did not come back whole and was
    in flight at any moment of the window has failed, and so has one that
    never came back at all (``unfinished``)."""
    good = sorted((r for r in log.requests if r["ok"]),
                  key=lambda r: r["done"])
    ends = burst_ends(np.asarray([r["done"] for r in good]))
    before, after = ends[ends <= span["t0"]], ends[ends >= span["t_end"]]
    a = float(before[-1]) if len(before) else float(span["t0"])
    b = float(after[0]) if len(after) else float(span["t_end"])
    counted = [r for r in good if a < r["done"] <= b]
    failed = [r for r in log.requests
              if not r["ok"] and r["sent"] <= b and r["done"] > a]
    n_failed = len(failed) + int(span["unfinished"])
    rows = int(sum(r["rows"] for r in counted))
    lat = [r["done"] - r["sent"] for r in counted]
    return {
        "attempted": len(counted) + n_failed,
        "failed": n_failed,
        "rows_per_s": rows / (b - a) if rows else None,
        "rows_answered": rows,
        "window_s": b - a,
        "window": (a, b),
        "bursts": int(((ends > a) & (ends <= b)).sum()),
        "request_p50_ms": percentile_ms(lat, 50),
        "late_s": [],
        "latency_s": lat,
    }
