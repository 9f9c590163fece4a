"""The thin shells around the pure front end: a threaded dispatch pump
binding the scheduler to one ``ServeSession``, and a stdlib HTTP server.

Layering (the testability contract): ``coalesce.py`` and ``scheduler.py``
are pure state machines with injected clocks — everything with behavior
worth asserting lives there and in the serve engine. THIS module only
adds the two unavoidable impurities, each as thin as it can be made:

- :class:`Frontend` — threads and real time: client threads enqueue
  through ``submit`` (admission under one lock, O(µs)); ONE pump thread
  polls the scheduler, stacks each coalesced batch, drives the session
  (the session is single-threaded by design — the pump is its only
  caller), and scatters retired results back to per-request tickets.
- :class:`FrontendHTTPServer` — sockets: ``POST /query`` (JSON or raw
  little-endian f32 rows, tenant id in ``X-Tenant``), ``GET /metrics``
  (the obs Prometheus exposition, the exact text ``parse_prometheus``
  re-parses in CI), ``GET /healthz`` (liveness + serving posture: rung,
  queue, uptime — and the index facts a load generator needs to shape
  requests). Handlers translate: 429 from a :class:`Rejection`, 400 from
  malformed payloads, 200 with per-row results otherwise.

Why one pump thread: the serve engine's dispatch-ahead pipeline
(``dispatch_depth``) already provides the useful concurrency on the
device side; a second submitting thread would only interleave
``submit``/``drain`` nondeterministically. The pump wakes on new work
(condition variable) or the oldest request's coalescing deadline —
idle-spinning would burn a core, sleeping a fixed quantum would add it
to every light-load latency.

No jax import at module load (the session object carries everything).
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

from mpi_knn_tpu.frontend.scheduler import (
    FrontendScheduler,
    Rejection,
    SLOPolicy,
)
from mpi_knn_tpu.config import RangeCapError
from mpi_knn_tpu.obs import metrics as obs_metrics
from mpi_knn_tpu.obs import spans as obs_spans




class FrontendError(RuntimeError):
    """The pump died (or the session raised) with requests outstanding;
    carried to every waiting ticket so no client blocks forever."""


class Ticket:
    """One admitted request's rendezvous: the submitting thread waits on
    ``result``; the pump fulfills (or fails) it at retire."""

    __slots__ = ("request", "_clock", "_event", "_dists", "_ids", "_error",
                 "done_s", "_lims")

    def __init__(self, request, clock=time.monotonic):
        self.request = request
        self._clock = clock
        self._event = threading.Event()
        self._dists = None
        self._ids = None
        self._lims = None  # a range request's offsets into the flat answer
        self._error = None
        # the front end's clock at fulfill: the one ``arrival_s`` is on
        # (time.monotonic unless injected: loadgen's clock)
        self.done_s = None

    def _fulfill(self, dists, ids, lims=None) -> None:
        self._dists, self._ids, self._lims = dists, ids, lims
        self.done_s = self._clock()
        self._event.set()

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self.done_s = self._clock()
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """(dists, ids) for this request's rows — of a RANGE request
        (lims, dists, ids): row i's results are ``dists[lims[i]:lims[i +
        1]]`` — blocks until the coalesced batch carrying it retires.
        Raises the serving error on failure (``RangeCapError``: a row of
        the request has more results than the cap), TimeoutError on
        timeout."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request seq={self.request.seq} not served within "
                f"{timeout}s (tenant={self.request.tenant!r})"
            )
        if self._error is not None:
            raise self._error
        if self._lims is not None:
            return self._lims, self._dists, self._ids
        return self._dists, self._ids


class Frontend:
    """Bind a :class:`FrontendScheduler` to one ``ServeSession`` with a
    dispatch pump thread. ``session`` should be constructed with a
    ``ResiliencePolicy`` (even the default one) when shedding is wanted:
    the degradation ladder is built at session construction, and a
    policy-less session has only its full rung to serve."""

    def __init__(self, session, policy: SLOPolicy,
                 clock=time.monotonic):
        self.session = session
        self.policy = policy
        self._clock = clock
        self.scheduler = FrontendScheduler(
            policy,
            on_shed=lambda: session.shed_rung(reason="queue-overload"),
            on_recover=lambda: session.restore_rung(
                reason="queue-recovered"
            ),
        )
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._tickets: dict[int, Ticket] = {}  # request seq -> ticket
        self._dispatched = []  # CoalescedBatch FIFO awaiting retire
        self._stop = False
        self._crashed: BaseException | None = None
        # highest router-stamped mutation sequence number applied here
        # (ISSUE 18): the router fans mutations out with X-Mutation-Seq
        # and reads this back from /healthz to track per-replica lag;
        # seq <= applied is a replayed duplicate and must not re-apply,
        # and seq > applied + 1 is a GAP and must not apply either (409)
        # — applying over a hole would advance the mark past a mutation
        # this replica never saw, losing it silently: the router's
        # in-order replay is the only path that moves a lagging replica
        self._applied_seq = 0
        self.started_s = time.monotonic()
        # declared device profile (ISSUE 16), resolved once here —
        # jax is already loaded by the session, and a construction-time
        # write keeps the attribute immutable across threads (H1); None
        # is a legitimate /healthz value (no shipped profile for this
        # hardware — never a guessed device)
        from mpi_knn_tpu.analysis.cost import detected_profile

        self._profile_facts: dict | None = detected_profile()
        # cold-start readiness (ISSUE 12): set once start-up warming —
        # executable builds at every rung plus the one-time dispatch-path
        # plumbing — has finished. While unset, admission is PER BUCKET:
        # a request whose row bucket's executable has landed serves, the
        # rest get a structured 503 "warming" with the progress counters.
        self._serving_ready = threading.Event()
        self._warm_thread: threading.Thread | None = None
        self._pump = threading.Thread(
            target=self._run, name="frontend-pump", daemon=True
        )

    # -- lifecycle --------------------------------------------------------

    def start(self, warm_sizes=None, background: bool = False,
              warm_parallel: int | None = None,
              warm_writes: bool = True) -> "Frontend":
        """Start the pump; ``warm_sizes`` (row counts) pre-builds those
        buckets at EVERY ladder rung first — via the persistent AOT
        cache when one is active, across ``warm_parallel`` threads
        (None = auto) — so neither the first batch nor a shed rung ever
        cold-compiles into live traffic (default: the policy's full
        batch target). One real zero-batch dispatch then runs per size
        via the one-shot ``query_knn`` path: the first dispatch pays
        jax's one-time dispatch-path setup (~hundreds of ms) on top of
        the AOT cache, and that cost belongs in startup, not in the
        first client's latency. ``query_knn`` shares the executables and
        dispatch machinery but feeds NO session window stats and NO
        serving counters/histograms — the warm-up is plumbing and must
        be invisible to /metrics, not merely wiped from the session
        window.

        ``background=True`` is the bind-the-port-first cold-start shape
        (ISSUE 12): the pump starts IMMEDIATELY and the warm-up runs on
        a daemon thread, so the HTTP server can listen while executables
        are still landing. Until the warm-up finishes, ``submit`` admits
        per bucket (``session.coalesced_ready``): traffic whose whole
        coalescable bucket span has landed serves at once, the rest get
        a structured 503 "warming" rejection carrying the buckets-
        ready/total progress that ``/healthz`` also reports.

        The default warm set is the full bucket LADDER from the config's
        base bucket up to the fill target — not just the fill target:
        a coalesced batch can land in any power-of-two bucket in that
        span (a ragged deadline dispatch, a lull), and per-bucket
        admission during warming is only safe when the span a request
        could reach is entirely built.

        ``warm_writes=False`` leaves the write path cold (the host mirror
        of the id plane, the upsert / delete / assign cells and, for a
        clustered index, the compaction cell, which holds the store
        twice): for a corpus that is served and never written, where the
        warm-up is set-up time and memory spent on programs nobody calls.
        A write still works; the first one builds what it needs."""
        if warm_sizes is None:
            base = self.session.cfg.query_bucket
            top = self.policy.max_batch_rows
            sizes, b = [], base
            while b < top:
                sizes.append(b)
                b *= 2
            sizes.append(top)
        else:
            sizes = list(warm_sizes)

        def _warm():
            from mpi_knn_tpu.serve.mutate import (
                supports_mutation,
                warm_mirror,
                warm_mutation,
            )

            mirror = None
            try:
                if warm_writes and supports_mutation(self.session.index):
                    # the host mirror of the id plane, beside the serve
                    # programs' warm-up (numpy and a device fetch: the
                    # GIL is free for most of it)
                    mirror = threading.Thread(
                        target=warm_mirror, args=(self.session.index,),
                        name="frontend-mirror", daemon=True)
                    mirror.start()
                if sizes:
                    from mpi_knn_tpu.serve.engine import query_knn

                    self.session.warm(sizes, parallel=warm_parallel)
                    dim = self.session.index.dim
                    for n in sizes:
                        query_knn(
                            np.zeros((n, dim), np.float32),
                            self.session.index, self.session.cfg,
                        )
                # the mutation cells too (ISSUE 14): a cold upsert would
                # otherwise compile while HOLDING the mutation lock —
                # stalling batch dispatch exactly once, at the worst time
                if mirror is not None:
                    mirror.join()
                    warm_mutation(self.session.index, self.session.cfg)
            finally:
                # a failed warm releases the gate anyway: the same
                # failure will re-raise loudly on the dispatch path
                # (where the pump's error machinery fails tickets),
                # whereas a stuck gate would 503 every client forever
                self._serving_ready.set()

        if background:
            self._pump.start()
            self._warm_thread = threading.Thread(
                target=_warm, name="frontend-warm", daemon=True
            )
            self._warm_thread.start()
        else:
            _warm()
            self._pump.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Flush: every admitted request is served before the pump exits
        (admission stops immediately)."""
        with self._lock:
            self._stop = True
            self._work.notify()
        self._pump.join(timeout)

    # -- client side ------------------------------------------------------

    def submit(self, tenant: str, queries, filters=None, radius=None):
        """Admit one request (non-blocking): a :class:`Ticket` to wait
        on, or the scheduler's structured :class:`Rejection`. ``filters``:
        a predicate a row, (rows, tags) tag ids with -1 for none, against
        an index built with tags; one the index cannot honour raises
        ``ValueError`` (the HTTP layer's 400), it is never ignored.
        ``radius``: a squared L2 radius — the request is a RANGE request
        (every live row strictly under it, for every query row; an index
        built with ``range_cap``), refused the same way where the index or
        the rows cannot honour it."""
        queries = np.ascontiguousarray(queries, dtype=np.float32)
        if queries.ndim != 2:
            raise ValueError(
                f"queries must be (rows, dim), got shape {queries.shape}"
            )
        if radius is not None:
            from mpi_knn_tpu.backends.range_scan import require_byte_rows
            from mpi_knn_tpu.serve.engine import require_range

            radius = float(radius)
            if not (np.isfinite(radius) and radius > 0):
                raise ValueError(
                    f"radius must be a positive finite squared distance, "
                    f"got {radius!r}")
            if filters is not None:
                raise ValueError("range search takes no predicate yet: "
                                 "send the radius or the filters")
            require_range(self.session.index, self.session.cfg)
            require_byte_rows(queries)
        if filters is not None or getattr(
                self.session.index, "tags", None) is not None:
            from mpi_knn_tpu.serve.engine import check_filters, plan_filters

            filters = check_filters(
                self.session.index, filters, queries.shape[0])
        if not self._serving_ready.is_set() and not \
                self.session.coalesced_ready(
                    queries.shape[0], self.policy.max_batch_rows
                ):
            # per-bucket admission while warming (ISSUE 12): traffic
            # whose executable has landed serves immediately; the rest
            # are refused with the warming progress, not queued behind a
            # compile that would blow their deadline anyway. The
            # progress copy goes through warm_snapshot(): a bare
            # dict(warm_state) here raced the warm pool's per-cell
            # updates under the session's OWN lock (host-lint H1)
            ws = self.session.warm_snapshot()
            return Rejection(
                tenant=str(tenant), reason="warming",
                detail=(
                    f"bucket for {queries.shape[0]} rows not compiled "
                    f"yet ({ws['ready']}/{ws['total']} executables "
                    "ready)"
                ),
                retry_after_s=0.5,
                status=503,
            )
        if filters is not None:
            # planned here, on the submitting thread (an HTTP handler's)
            # and once the request is known to be servable: requests are
            # planned side by side and the pump, which every batch waits
            # for, only joins their plans
            filters = plan_filters(self.session.index, filters)
        with self._lock:
            if self._stop or self._crashed is not None:
                return Rejection(
                    tenant=str(tenant), reason="shutting-down",
                    detail="front end is stopping", retry_after_s=0.0,
                    status=503,
                )
            out = self.scheduler.submit(
                tenant, queries, queries.shape[0], self._clock(), filters,
                radius,
            )
            if isinstance(out, Rejection):
                return out
            ticket = Ticket(out, self._clock)
            self._tickets[out.seq] = ticket
            self._work.notify()
            return ticket

    def upsert(self, tenant: str, ids, rows, seq: int | None = None):
        """Admit + execute one tenant's upsert (ISSUE 14): 429-governed
        through the scheduler's shared per-tenant budget, then
        dispatched synchronously on this (handler) thread — the index's
        mutation lock serializes it with the pump's batch dispatch, so
        no ticket machinery is needed. Returns the mutation stats dict,
        or a structured :class:`Rejection`.

        ``seq`` is the router's per-index mutation sequence number
        (ISSUE 18): a seq at or below the high-water mark is a replayed
        duplicate — acknowledged without re-applying (and without
        charging the tenant's mutation budget), so the router's
        rejoin-replay can safely overlap live fan-out — and a seq past
        ``applied + 1`` is a gap, refused with a 409-status rejection
        (the router replays the hole forward in order)."""
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        with self._lock:
            if self._stop or self._crashed is not None:
                return Rejection(
                    tenant=str(tenant), reason="shutting-down",
                    detail="front end is stopping", retry_after_s=0.0,
                    status=503,
                )
            gate = self._seq_gate(tenant, seq, self._applied_seq)
            if gate is not None:
                return gate
            rej = self.scheduler.admit_mutation(
                tenant, rows.shape[0], self._clock()
            )
        if rej is not None:
            return rej
        out = self.session.upsert(ids, rows, tenant=str(tenant))
        return self._note_applied(out, seq)

    def delete(self, tenant: str, ids, seq: int | None = None):
        """Admit + execute one tenant's delete — the upsert path's
        429 governance (and seq duplicate/gap gating) over the
        tombstone scatter."""
        ids = np.asarray(ids).reshape(-1)
        with self._lock:
            if self._stop or self._crashed is not None:
                return Rejection(
                    tenant=str(tenant), reason="shutting-down",
                    detail="front end is stopping", retry_after_s=0.0,
                    status=503,
                )
            gate = self._seq_gate(tenant, seq, self._applied_seq)
            if gate is not None:
                return gate
            rej = self.scheduler.admit_mutation(
                tenant, max(1, ids.shape[0]), self._clock()
            )
        if rej is not None:
            return rej
        out = self.session.delete(ids, tenant=str(tenant))
        return self._note_applied(out, seq)

    @staticmethod
    def _seq_gate(tenant: str, seq: int | None, applied: int):
        """The stream-order gate — pure in ``applied`` (callers read the
        mark under ``_lock`` and pass it in): None means the seq is
        consumable (exactly ``applied + 1``, or unsequenced); a dict is
        the duplicate acknowledgment; a 409 :class:`Rejection` means the
        seq would leave a GAP — 409 is outside the router's
        deterministic set, so the leg stays unacknowledged and the probe
        loop replays the hole forward in order."""
        if seq is None:
            return None
        if seq <= applied:
            return {"duplicate": True, "applied_seq": applied}
        if seq > applied + 1:
            return Rejection(
                tenant=str(tenant), reason="seq-gap",
                detail=(
                    f"seq {seq} skips ahead of applied_seq "
                    f"{applied}; refusing to apply out of order"
                ),
                retry_after_s=0.5, status=409,
            )
        return None

    def _note_applied(self, out: dict, seq: int | None) -> dict:
        """Advance the mutation high-water mark AFTER the session applied
        the mutation (never on admission — a crash between admit and
        apply must leave the seq unacknowledged so replay re-sends it)."""
        if seq is not None:
            with self._lock:
                if seq > self._applied_seq:
                    self._applied_seq = seq
                out["applied_seq"] = self._applied_seq
        return out

    def _note_refused(self, seq: int | None) -> dict | None:
        """A DETERMINISTIC refusal (400/507) consumed its seq: the
        stream position advances exactly as an apply would, because a
        replay could only repeat the refusal — a position that did not
        advance would make this replica 409 every later seq forever
        (the stream has no skip marker). Returns the position facts for
        the refusal body, ``{"gap": True, ...}`` when the seq cannot be
        consumed in order (the handler must answer 409 seq-gap instead
        of its refusal), or None for an unsequenced mutation."""
        if seq is None:
            return None
        with self._lock:
            if seq > self._applied_seq + 1:
                return {"gap": True, "applied_seq": self._applied_seq}
            if seq > self._applied_seq:
                self._applied_seq = seq
            return {"applied_seq": self._applied_seq}

    def stats(self) -> dict:
        """The health/posture snapshot ``GET /healthz`` serves.

        Session state comes through the session's OWN locked snapshots
        (``warm_snapshot``/``stats_snapshot``), taken BEFORE the
        frontend lock: handler threads previously read ``ses.latencies``
        / ``ses.tenant_stats`` raw while the pump mutated them at
        retire — the exact guard-map breach host-lint H1 flags — and
        keeping the two critical sections disjoint also keeps the lock
        graph free of a Frontend→Session edge from this path."""
        ses = self.session
        warm = ses.warm_snapshot()
        posture = ses.stats_snapshot()
        with self._lock:
            return {
                # a stopping frontend FAILS its health check on purpose:
                # a router must pull a draining replica out of rotation
                # before its socket goes away (ISSUE 18)
                "ok": self._crashed is None and not self._stop,
                # cold-start posture (ISSUE 12): executables ready/total
                # while warming, and whether start-up warming is done —
                # the CI gate's time-to-ready rendezvous reads this
                "ready": self._serving_ready.is_set() and not self._stop,
                "warming": {
                    "ready": warm["ready"],
                    "total": warm["total"],
                    "done": self._serving_ready.is_set(),
                },
                "uptime_s": round(time.monotonic() - self.started_s, 3),
                "queue_rows": self.scheduler.coalescer.pending_rows,
                "queue_requests": self.scheduler.coalescer.pending_requests,
                "admitted": self.scheduler.admitted,
                "rejected": self.scheduler.rejected,
                "rung": posture["rung"],
                "ladder": [label for label, _ in ses.ladder],
                "sheds": len(self.scheduler.sheds),
                "recoveries": len(self.scheduler.recoveries),
                "batches_retired": posture["batches_retired"],
                "queries_served": posture["queries_served"],
                "tenants": posture["tenants"],
                # live-mutation posture (ISSUE 14): the session window's
                # upsert/delete/compaction counts
                "mutation": posture.get("mutation", {}),
                # an index built with tags (ISSUE 39): what its two
                # regimes keep and where the threshold lies; absent else
                **({"tags": ses.index.tags.summary()}
                   if getattr(ses.index, "tags", None) is not None else {}),
                # router mutation high-water mark (ISSUE 18): the probe
                # loop reads per-replica lag from here
                "applied_seq": self._applied_seq,
                # what a load generator needs to shape requests
                "dim": ses.index.dim,
                "k": ses.cfg.k,
                "backend": ses.index.backend,
                "max_batch_rows": self.policy.max_batch_rows,
                # static peak HBM of the largest built executable
                # (ISSUE 15): the memory-ledger figure for THIS
                # deployment's shapes, zero device reads — an operator
                # sizing a box reads it here next to dim/k/backend
                "peak_hbm_bytes": posture.get("peak_hbm_bytes", 0),
                # the declared roofline inputs for this hardware
                # (ISSUE 16): the shipped device profile the planner
                # predicted q/s under, so measured throughput and its
                # predicted bar read from the same endpoint; null off
                # the profile map — never a guessed device
                "device_profile": self._device_profile(),
            }

    def _device_profile(self) -> dict | None:
        return self._profile_facts

    # -- pump -------------------------------------------------------------

    def _run(self) -> None:
        try:
            session = self.session
            turn_s = time.perf_counter()
            while True:
                # what no phase of the last turn covered is ``other``: the
                # turn's wall on the spans' own clock, less their seconds
                now_s = time.perf_counter()
                session.phase_remainder(now_s - turn_s)
                turn_s = now_s
                with self._lock:
                    stopping = self._stop
                    # a poll forms batches only out of pending rows: one
                    # of an empty queue is the overload tick alone and
                    # gets no span
                    forming = None
                    if self.scheduler.coalescer.pending_rows:
                        forming = obs_spans.begin_span(
                            "coalesce", cat="pump",
                            sink=session.phase_sink("coalesce"),
                        )
                    batches = self.scheduler.poll(
                        self._clock(), flush=stopping
                    )
                for b in batches:
                    self._dispatch(b, forming)
                    forming = None
                # pending rows whose oldest has not waited max_wait_s yet
                obs_spans.end_span(forming, batches=0)
                if not batches:
                    # nothing formed: retire in-flight work so results
                    # are not held hostage to the NEXT batch arriving
                    # (dispatch-ahead depth > 1 would otherwise strand
                    # the last batch of a lull in the pipeline). The span
                    # says WHY the pump sat in ``wait``: its children
                    # feed the phases, it feeds none
                    if self._dispatched:
                        with obs_spans.span("drain", cat="pump",
                                            batches=len(self._dispatched)):
                            for res in self.session.drain():
                                self._scatter(res)
                    with self._lock:
                        held = self.scheduler.coalescer.pending_rows
                        if self._stop and not (self._dispatched or held):
                            return
                        wake = self.scheduler.next_wake_s()
                        timeout = (
                            0.05 if wake is None
                            else max(0.0, wake - self._clock())
                        )
                        if not self._stop:
                            # ``idle``: nothing pending, nothing in
                            # flight; ``hold``: the coalescer keeps rows
                            # younger than max_wait_s for co-travellers
                            with session.phase(
                                "hold" if held else "idle", cat="pump",
                                flight=False,
                            ):
                                self._work.wait(timeout=min(timeout, 0.05))
        except BaseException as e:  # noqa: BLE001 — fail tickets, re-raise
            with self._lock:
                self._crashed = e
                err = FrontendError(
                    f"frontend pump died: {type(e).__name__}: {e}"
                )
                for t in self._tickets.values():
                    if not t.done():
                        t._fail(err)
                self._tickets.clear()
                self._dispatched.clear()
            raise

    def _dispatch(self, batch, forming=None) -> None:
        """Stack one coalesced batch and hand it to the engine.
        ``forming`` is the open ``coalesce`` span of the poll that formed
        the batch (its first batch closes it; a later one of the same
        poll opens its own around the stacking)."""
        if forming is None:
            forming = obs_spans.begin_span(
                "coalesce", cat="pump",
                sink=self.session.phase_sink("coalesce"),
            )
        q = np.concatenate([r.queries for r in batch.parts], axis=0)
        self._metrics().histogram(
            "frontend_batch_fill_rows",
            help="coalesced rows per dispatched batch",
            buckets=_FILL_BUCKETS,
        ).observe(batch.rows)
        # queue wait: admission (Frontend.submit stamped arrival_s) to
        # this hand-over, both on the pump's clock
        now = self._clock()
        waited = self._metrics().histogram(
            "frontend_queue_wait_seconds",
            help="per request: admitted to its batch handed to the engine",
        )
        for r in batch.parts:
            waited.observe(now - r.arrival_s)
        # dispatch lag: how long the batch had been dispatchable when the
        # pump got to it (a wait is the policy's hold + this)
        lag_s = max(0.0, now - batch.ripe_s)
        self._metrics().histogram(
            "frontend_dispatch_lag_seconds",
            help="per batch: dispatchable (filled, or its oldest request's "
            "deadline) to handed to the engine",
        ).observe(lag_s)
        obs_spans.end_span(
            forming, seq=self.session.next_seq, rows=batch.rows,
            requests=len(batch.parts), reason=batch.reason,
            oldest_wait_ms=round(batch.oldest_wait_s * 1e3, 3),
            lag_ms=round(lag_s * 1e3, 3),
            request_seqs=[r.seq for r in batch.parts],
        )
        self._dispatched.append(batch)
        filters = None
        if getattr(self.session.index, "tags", None) is not None:
            # the predicates ride with the rows, planned at admission:
            # requests of any tenants, with and without them, make one
            # batch, whose plan is the requests' plans joined
            from mpi_knn_tpu.serve.tags import merge_plans

            with self.session.phase("plan", requests=len(batch.parts)):
                filters = merge_plans([r.filters for r in batch.parts])
        for res in self.session.submit(q, tenants=batch.composition(),
                                       filters=filters, radii=batch.radii):
            self._scatter(res)

    def _scatter(self, res) -> None:
        batch = self._dispatched.pop(0)
        phase = self.session.phase
        if res.range_out is not None:
            self._scatter_range(batch, res)
            return
        with phase("d2h", seq=res.seq, parent=res.span):
            # padding stripped; a session with the NaN sentinel on has
            # fetched dists at retire already (its own d2h span)
            dists, ids = res.dists, res.ids
        with phase("reply", seq=res.seq, parent=res.span,
                   requests=len(batch.parts)):
            with self._lock:
                for req, start, stop in batch.slices():
                    t = self._tickets.pop(req.seq, None)
                    if t is not None:
                        t._fulfill(dists[start:stop], ids[start:stop])

    def _scatter_range(self, batch, res) -> None:
        """A range batch back to its requests BY OFFSETS: a request's rows
        are a run of the batch's, so its results are one run of the flat
        answer and its ``lims`` the batch's less their first. A request
        that holds a row over the cap fails whole, by name
        (``RangeCapError``: the rows as the request numbers them, each
        with its true count); its neighbours in the batch are answered."""
        phase = self.session.phase
        with phase("d2h", seq=res.seq, parent=res.span):
            lims, dists, ids, refused = res.range_answer  # cached at retire
        with phase("reply", seq=res.seq, parent=res.span,
                   requests=len(batch.parts)):
            with self._lock:
                for req, start, stop in batch.slices():
                    t = self._tickets.pop(req.seq, None)
                    if t is None:
                        continue
                    over = [(r - start, n) for r, n in refused
                            if start <= r < stop]
                    if over:
                        t._fail(RangeCapError(over, res.range_cap))
                        continue
                    lo, hi = int(lims[start]), int(lims[stop])
                    t._fulfill(dists[lo:hi], ids[lo:hi],
                               lims[start:stop + 1] - lo)

    def _metrics(self):
        return obs_metrics.get_registry()


# ---------------------------------------------------------------------------
# HTTP layer

# fill histogram: powers of two around common bucket grids
_FILL_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def _tuned_server_class():
    """``ThreadingHTTPServer`` tuned for a load-bearing loopback tier:

    - the stdlib's accept backlog of 5 DROPS connection bursts (an
      open-loop generator or a router opening its pool refused at the
      kernel) — raised to 128;
    - Nagle + delayed-ACK stalls the headers/body response pair ~40ms
      per request on KEEP-ALIVE connections (fresh connections hide it
      behind Linux quickack) — NODELAY is set on the accepted socket
      here, because ``disable_nagle_algorithm`` is a *handler* knob and
      the handler classes are per-caller closures;
    - ``server_close`` SEVERS live keep-alive connections: a threaded
      stdlib server otherwise leaves handler threads serving pooled
      connections after shutdown, so a "stopped" server keeps answering
      its old peers — a zombie a router would keep probing forever
      while its replacement listens unvisited on the same port. A real
      process's sockets die with it; an in-process stop must match.
    """
    import socket

    from http.server import ThreadingHTTPServer

    class TunedHTTPServer(ThreadingHTTPServer):
        request_queue_size = 128
        daemon_threads = True

        def __init__(self, *args, **kwargs):
            self._live_socks: set = set()
            self._live_lock = threading.Lock()
            ThreadingHTTPServer.__init__(self, *args, **kwargs)

        def get_request(self):
            sock, addr = ThreadingHTTPServer.get_request(self)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._live_lock:
                self._live_socks.add(sock)
            return sock, addr

        def shutdown_request(self, request):
            with self._live_lock:
                self._live_socks.discard(request)
            ThreadingHTTPServer.shutdown_request(self, request)

        def server_close(self):
            ThreadingHTTPServer.server_close(self)
            with self._live_lock:
                socks = list(self._live_socks)
                self._live_socks.clear()
            for s in socks:
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    s.close()
                except OSError:
                    pass

        def handle_error(self, request, client_address):
            import sys

            # a peer that went away mid-request (severed connection,
            # killed client) is routine for a load-bearing tier, not a
            # traceback; anything else still gets the stdlib report
            if isinstance(sys.exc_info()[1],
                          (ConnectionError, TimeoutError, OSError)):
                return
            ThreadingHTTPServer.handle_error(
                self, request, client_address
            )

    return TunedHTTPServer

class Occupancy:
    """Seconds in which a request was inside the server, and seconds in
    which none was: ``frontend_occupancy_seconds_total{state="occupied"}``
    while at least one POST of a serving route (/query, /upsert, /delete)
    is inside its handler, ``{state="empty"}`` otherwise. It splits an idle
    device between the callers (nothing was asked) and the server (it sat
    on work), over any window, traced or not. A count under one lock; the
    clock is read where the count crosses between 0 and 1 and at
    :meth:`settle` (every ``/metrics`` read), so the two states add up to
    the time since construction. Overlapping requests count once."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._inside = 0
        self._since = clock()

    def _move(self, step: int) -> None:
        """Move the count by ``step`` (0: a reading alone) and, where that
        crosses between empty and occupied or is a reading, hand the
        seconds since the last one to the state they were spent in."""
        with self._lock:
            was = self._inside
            self._inside = was + step
            if step and (was == 0) == (self._inside == 0):
                return
            now = self._clock()
            obs_metrics.get_registry().counter(
                "frontend_occupancy_seconds_total",
                help="seconds with (occupied) and without (empty) a "
                "request of a serving route inside its handler",
                labels={"state": "occupied" if was else "empty"},
            ).inc(max(0.0, now - self._since))
            self._since = now

    def settle(self) -> None:
        self._move(0)

    def __enter__(self) -> "Occupancy":
        self._move(+1)
        return self

    def __exit__(self, *exc) -> None:
        self._move(-1)


class _Phases:
    """The phases of one request on its handler's thread, children of its
    ``request`` span: ``knn:http.read`` / ``.admit`` / ``.await`` /
    ``.wake`` / ``.encode`` / ``.write``, each feeding
    ``frontend_request_phase_seconds_total{phase, route}``. It opens with
    ``read`` at the reading ``at`` (the request span's own); :meth:`next`
    ends the open phase and begins the next at ONE reading of the clock, so
    a request whose phases follow one another without a break (/query) is
    partitioned exactly: the phases' seconds add up to its span's."""

    __slots__ = ("_clock", "_parent", "_route", "_open", "_at", "attrs")

    def __init__(self, clock, parent, route: str, at: float):
        self._clock, self._parent, self._route = clock, parent, route
        self._at = at
        self.attrs: dict = {}  # what every phase from now on carries
        self._begin("read", at, {})

    def _begin(self, name: str, now: float, attrs: dict) -> None:
        self._open = obs_spans.begin_span(
            name, cat="http", parent=self._parent, at=now,
            sink=obs_metrics.get_registry().counter(
                "frontend_request_phase_seconds_total",
                help="seconds of the handler threads by phase of a "
                "request and route",
                labels={"phase": name, "route": self._route},
            ).inc,
            **self.attrs, **attrs,
        )

    def next(self, name: str, at: float | None = None, **attrs) -> None:
        """Begin phase ``name`` where the open one ends: now, or at the
        reading ``at`` taken elsewhere on the same clock (a request's
        ``arrival_s``, a ticket's ``done_s``)."""
        self._begin(name, self.end(at), attrs)

    def end(self, at: float | None = None) -> float:
        """End the open phase without beginning another (a write's middle
        is ``knn:mutate.*``); the reading it ended at, never behind the
        one before it: no phase is negative."""
        self._at = max(self._at, self._clock() if at is None else at)
        obs_spans.end_span(self._open, at=self._at)
        self._open = None
        return self._at


TENANT_HEADER = "X-Tenant"
DEFAULT_TENANT = "default"
# the router's per-index mutation sequence number (ISSUE 18)
SEQ_HEADER = "X-Mutation-Seq"


# /query's raw form with a predicate a row: this header says how many
# int32 tag ids a row (-1: none) follow the rows in the body; without it
# the body is rows alone, as it always was
FILTER_HEADER = "X-Filter-Tags"
# /query's raw form as a RANGE request: the squared L2 radius, one number a
# request (the JSON form's key "radius"); without it the request is a k-NN
# request, as it always was
RADIUS_HEADER = "X-Radius"


def raw_rows(raw: bytes, dim: int, ids: bool, tags: int = 0):
    """The one parser of the raw request form (little-endian, the row
    count from the body's length): ``(ids-or-None, (n, dim) float32
    rows)``. With ``ids`` the body is n int32 ids followed by the n rows
    (/upsert); without, the rows alone (/query). ``tags`` > 0 (/query
    with ``X-Filter-Tags``): n x tags int32 tag ids follow the rows, and a
    third element is returned, the (n, tags) filters."""
    per_row = 4 * dim + (4 if ids else 0) + 4 * tags
    if len(raw) % per_row:
        raise ValueError(
            f"raw body of {len(raw)} bytes is not a whole number of "
            f"{'int32 id + ' if ids else ''}dim={dim} float32 rows"
            f"{f' + {tags} int32 tags' if tags else ''} "
            f"({per_row} bytes each)"
        )
    n = len(raw) // per_row
    head = 4 * n if ids else 0
    rows = np.frombuffer(
        raw, dtype="<f4", offset=head, count=n * dim).reshape(n, dim)
    first = np.frombuffer(raw, dtype="<i4", count=n) if ids else None
    if not tags:
        return first, rows
    return first, rows, np.frombuffer(
        raw, dtype="<i4", offset=head + 4 * n * dim).reshape(n, tags)


def json_filters(doc: dict, rows: int):
    """The (rows, widest) int32 filters of a JSON /query body's
    ``"filters"`` (a list of tag ids a row, ``[]`` for none), -1 padded;
    None where the body has none."""
    lists = doc.get("filters")
    if lists is None:
        return None
    if not isinstance(lists, list) or len(lists) != rows or not all(
            isinstance(row, list) for row in lists):
        raise ValueError(
            f"filters must be one list of tag ids a query row ({rows})")
    out = np.full((rows, max(map(len, lists), default=0)), -1, np.int64)
    for i, row in enumerate(lists):
        if not all(isinstance(t, int) and not isinstance(t, bool)
                   and 0 <= t < 2 ** 31 for t in row):
            raise ValueError(f"filters[{i}]: tag ids are whole numbers >= 0")
        out[i, :len(row)] = row
    return out


def _http_handler(frontend: Frontend, request_timeout_s: float,
                  quiet: bool, occupancy: Occupancy):
    """The BaseHTTPRequestHandler subclass bound to one frontend —
    built by closure (stdlib handlers have no constructor channel). The
    handlers read the front end's clock: ``arrival_s`` and ``done_s``
    are on it."""
    from http.server import BaseHTTPRequestHandler

    clock = frontend._clock

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _send(self, status: int, body: bytes, ctype: str,
                  headers=()) -> None:
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            for name, value in headers:
                self.send_header(name, value)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _json(self, status: int, doc: dict, phases: _Phases | None = None,
                  headers=()) -> None:
            """``phases``: the request's, in ``encode`` since before
            ``doc`` was made; its ``write`` begins with the body done."""
            body = (json.dumps(doc) + "\n").encode()
            if phases is not None:
                phases.next("write")
            self._send(status, body, "application/json", headers)

        def _text(self, status: int, text: str, ctype: str) -> None:
            self._send(status, text.encode(), ctype)

        def log_message(self, fmt, *args):  # noqa: A003
            if not quiet:
                BaseHTTPRequestHandler.log_message(self, fmt, *args)

        def _read_body(self):
            """(body bytes, whether it is the raw form): every POST
            route takes JSON or ``application/octet-stream``."""
            n = int(self.headers.get("Content-Length") or 0)
            if n <= 0:
                raise ValueError("empty request body")
            ctype = (self.headers.get("Content-Type") or "").split(";")[0]
            return self.rfile.read(n), ctype == "application/octet-stream"

        def _read_queries(self):
            """``((rows, dim) f32, filters-or-None, radius-or-None)`` from
            the request body: JSON ``{"queries": [[...], ...], "filters":
            [[t], [t, u], []], "radius": r}`` or raw little-endian f32
            rows at the index dim (``application/octet-stream``),
            followed, where the header ``X-Filter-Tags: W`` says so, by W
            int32 tag ids a row (-1: none); the raw form names its radius
            in the header ``X-Radius``."""
            raw, is_raw = self._read_body()
            dim = frontend.session.index.dim
            if is_raw:
                radius = self.headers.get(RADIUS_HEADER)
                if radius is not None:
                    radius = float(radius)  # ValueError: a 400
                width = self.headers.get(FILTER_HEADER)
                if width is None:
                    return raw_rows(raw, dim, ids=False)[1], None, radius
                if not width.isdigit() or not 0 < int(width) <= 64:
                    raise ValueError(
                        f"{FILTER_HEADER}: {width!r} is not a tag count")
                return (*raw_rows(raw, dim, ids=False, tags=int(width))[1:],
                        radius)
            doc = json.loads(raw)
            q = np.asarray(doc["queries"], dtype=np.float32)
            if q.ndim != 2 or q.shape[1] != dim:
                raise ValueError(
                    f"queries shape {q.shape} does not match index "
                    f"dim {dim}"
                )
            radius = doc.get("radius")
            if radius is not None and (
                    isinstance(radius, bool)
                    or not isinstance(radius, (int, float))):
                raise ValueError("radius is one number, a squared distance")
            return q, json_filters(doc, q.shape[0]), radius

        def _reject(self, out: Rejection, phases: _Phases) -> None:
            phases.next("encode")
            self._json(out.status, {
                "error": out.reason,
                "detail": out.detail,
                "tenant": out.tenant,
                "retry_after_s": out.retry_after_s,
            }, phases, headers=(
                ("Retry-After", str(max(0.0, out.retry_after_s))),
            ))

        def _read_mutation(self):
            """(ids, rows-or-None) of a write's body. JSON for small
            callers: ``{"ids": [...], "rows": [[...]]}`` to /upsert,
            ``{"ids": [...]}`` to /delete. Raw
            (``application/octet-stream``, little-endian, the count from
            ``Content-Length``) as ``/query`` takes it: n int32 ids, then
            for /upsert n float32 rows at the index dim."""
            raw, is_raw = self._read_body()
            upsert = self.path == "/upsert"
            dim = frontend.session.index.dim
            if is_raw:
                if upsert:
                    return raw_rows(raw, dim, ids=True)
                if len(raw) % 4:
                    raise ValueError(
                        f"raw int32 body of {len(raw)} bytes is not a "
                        "whole number of ids"
                    )
                return np.frombuffer(raw, dtype="<i4"), None
            doc = json.loads(raw)
            ids = doc["ids"]
            if not upsert:
                return ids, None
            rows = np.asarray(doc["rows"], dtype=np.float32)
            if rows.ndim != 2 or rows.shape[1] != dim:
                raise ValueError(
                    f"rows shape {rows.shape} does not match "
                    f"index dim {dim}"
                )
            if len(ids) != rows.shape[0]:
                raise ValueError(
                    f"{len(ids)} ids but {rows.shape[0]} rows"
                )
            return ids, rows

        def _refuse_mutation(self, status: int, doc: dict, seq,
                             phases: _Phases) -> None:
            """Send a DETERMINISTIC refusal (400/507): the seq is
            consumed (the router acks these — a replay could only
            repeat them, so the stream position must move past), unless
            it would leave a gap, which downgrades the answer to a 409
            the router never acks."""
            phases.next("encode")
            note = frontend._note_refused(seq)
            if note is not None and note.pop("gap", False):
                self._json(409, {"error": "seq-gap", **note}, phases)
                return
            if note is not None:
                doc = {**doc, **note}
            self._json(status, doc, phases)

        def _do_mutation(self, tenant: str, phases: _Phases) -> None:
            """POST /upsert {"ids": [...], "rows": [[...]]} and
            POST /delete {"ids": [...]}, or their raw forms
            (``_read_mutation``) — tenant-attributed (X-Tenant),
            429-governed through the scheduler's shared budget,
            dispatched synchronously (the mutation lock serializes with
            batch dispatch). Headroom overflow on the serial layout
            surfaces as 507 (no re-cluster pass to absorb it); clustered
            layouts compact-and-retry inside the session. Of the
            request's phases a write has ``read``, ``encode`` and
            ``write``: its middle is ``knn:mutate.*``."""
            from mpi_knn_tpu.ivf.mutate import BucketOverflowError
            from mpi_knn_tpu.serve.mutate import mutation_phase

            seq = None
            try:
                seq_h = self.headers.get(SEQ_HEADER)
                seq = None if seq_h is None else int(seq_h)
                with mutation_phase("parse"):
                    ids, rows = self._read_mutation()
            except (ValueError, KeyError, TypeError) as e:
                self._refuse_mutation(400, {"error": str(e)}, seq, phases)
                return
            phases.end()
            try:
                if self.path == "/upsert":
                    out = frontend.upsert(tenant, ids, rows, seq=seq)
                else:
                    out = frontend.delete(tenant, ids, seq=seq)
            except BucketOverflowError as e:
                self._refuse_mutation(
                    507, {"error": "headroom-exhausted",
                          "detail": str(e)}, seq, phases,
                )
                return
            except ValueError as e:
                self._refuse_mutation(400, {"error": str(e)}, seq, phases)
                return
            except Exception as e:  # noqa: BLE001 — serving error
                phases.next("encode")
                self._json(500, {"error": f"{type(e).__name__}: {e}"},
                           phases)
                return
            if isinstance(out, Rejection):
                self._reject(out, phases)
                return
            phases.next("encode")
            self._json(200, out, phases)

        def do_POST(self):  # noqa: N802 — stdlib handler convention
            route = self.path[1:]
            if route not in ("query", "upsert", "delete"):
                self._json(404, {"error": f"no such route {self.path}"})
                return
            tenant = self.headers.get(TENANT_HEADER, DEFAULT_TENANT)
            with occupancy:
                if route == "query":
                    self._do_query(tenant)
                    return
                with obs_spans.span("request", cat="http",
                                    route=route) as request:
                    phases = _Phases(clock, request, route, clock())
                    try:
                        self._do_mutation(tenant, phases)
                    finally:
                        phases.end()

        def _do_query(self, tenant: str) -> None:
            """Answer one POST /query inside its ``request`` span: body
            read to response written, whatever the answer, the six phases
            from consecutive readings of one clock."""
            t0 = clock()
            request = obs_spans.begin_span(
                "request", cat="http", at=t0,
                sink=obs_metrics.get_registry().histogram(
                    "frontend_request_seconds",
                    help="per /query request: body read to response "
                    "written, on the handler's thread",
                ).observe,
            )
            phases = _Phases(clock, request, "query", t0)
            seen = {}
            try:
                seen = self._answer_query(tenant, phases)
            finally:
                obs_spans.end_span(request, at=phases.end(), **seen)

        def _answer_query(self, tenant: str, phases: _Phases) -> dict:
            """What the request's span learns on the way (the admitted
            request's ``seq`` joins it to its batch, and the status)."""
            try:
                q, filters, radius = self._read_queries()
                phases.next("admit")
                out = frontend.submit(tenant, q, filters, radius)
            except (ValueError, KeyError, TypeError) as e:
                phases.next("encode")
                self._json(400, {"error": str(e)}, phases)
                return {"status": 400}
            if isinstance(out, Rejection):
                self._reject(out, phases)
                return {"status": out.status}
            request = out.request
            seen = {"seq": request.seq, "rows": request.rows}
            phases.attrs = {"seq": request.seq}
            # admitted when the scheduler stamped it: queue wait, batch
            # and reply from there on are the ``await``
            phases.next("await", at=request.arrival_s)
            status, doc, lims = 200, None, None
            try:
                *lims, dists, ids = out.result(timeout=request_timeout_s)
            except TimeoutError as e:
                status, doc = 504, {"error": str(e)}
            except RangeCapError as e:
                # refused by name, never cut: the rows (as the request
                # numbers them) with their true counts
                status, doc = 422, {
                    "error": "range-cap", "detail": str(e), "cap": e.cap,
                    "rows": [[r, n] for r, n in e.rows]}
            except Exception as e:  # serving error (sentinel, …)
                status, doc = 500, {"error": f"{type(e).__name__}: {e}"}
            done_s = out.done_s  # None: timed out unfulfilled
            woke = clock()
            if done_s is not None:
                # fulfilled on the pump's thread; since then this one
                # waited for the hand-over and the GIL. On the trace the
                # wait is the tail of ``knn:http.await`` (a blocked thread
                # cannot annotate): ``wake`` there is a mark carrying it
                phases.next("wake", at=done_s,
                            us=int((woke - done_s) * 1e6))
            phases.next("encode", at=woke)
            if doc is None and lims:
                # the suite's range format: rows + 1 offsets and flat
                # lists; ``tolist`` walks the arrays once, so the encode's
                # cost follows the answer's length, not rows x anything
                seen["results"] = int(ids.shape[0])
                doc = {
                    "rows": request.rows,
                    "metric": frontend.session.cfg.metric,
                    "radius": request.radius,
                    "lims": lims[0].tolist(),
                    "dists": dists.astype(np.float64).tolist(),
                    "ids": ids.tolist(),
                }
                phases.attrs = {**phases.attrs, "results": seen["results"]}
            elif doc is None:
                doc = {
                    "rows": int(ids.shape[0]),
                    # what ``dists`` holds, ascending: squared L2 ("l2"),
                    # 1 - cosine similarity ("cosine"), or the NEGATED
                    # inner product -<q, c> ("ip")
                    "metric": frontend.session.cfg.metric,
                    "dists": [[float(v) for v in row] for row in dists],
                    "ids": ids.tolist(),
                }
            self._json(status, doc, phases)
            return {**seen, "status": status}

        def do_GET(self):  # noqa: N802
            if self.path == "/metrics":
                occupancy.settle()
                self._text(
                    200, obs_metrics.get_registry().to_prometheus(),
                    "text/plain; version=0.0.4",
                )
            elif self.path == "/healthz":
                st = frontend.stats()
                self._json(200 if st["ok"] else 503, st)
            else:
                self._json(404, {"error": f"no such route {self.path}"})

    return Handler


class FrontendHTTPServer:
    """``ThreadingHTTPServer`` wrapper: bind, serve in a thread, expose
    the bound address (``--port 0`` picks an ephemeral port)."""

    def __init__(self, frontend: Frontend, host: str = "127.0.0.1",
                 port: int = 0, request_timeout_s: float = 30.0,
                 quiet: bool = True):
        self.frontend = frontend
        self.occupancy = Occupancy(frontend._clock)
        self._httpd = _tuned_server_class()(
            (host, port),
            _http_handler(frontend, request_timeout_s, quiet,
                          self.occupancy),
        )
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="frontend-http",
            daemon=True,
        )

    @property
    def address(self) -> tuple:
        return self._httpd.server_address[:2]

    @property
    def url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}"

    def start(self) -> "FrontendHTTPServer":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(10.0)
