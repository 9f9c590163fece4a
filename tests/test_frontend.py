"""The serving front end's PURE layer (ISSUE 11): the coalescer and the
SLO scheduler as deterministic state machines — no jax, no sockets, no
threads, every clock injected.

What is pinned here:

- batch formation: fill-vs-max-wait tradeoff over randomized (seeded)
  arrival orders, conservation (every admitted request serves exactly
  once, per-tenant FIFO order intact), the max_batch_rows ceiling, and
  bit-determinism (the same arrival sequence always forms the same
  batches);
- fairness: round-robin draining bounds per-batch service skew at one
  request per tenant per pass, and the served max/min ratio over a
  sustained symmetric backlog stays ~1;
- deadline order: the request whose wait budget triggered formation is
  always aboard the batch it triggered;
- backpressure: queue-depth and rate rejections are structured
  (reason + retry-after), deterministic, and replayable;
- overload: sustained queue growth sheds (via the injected callback),
  sustained drain recovers, with hysteresis validated.
"""

from __future__ import annotations

import random

import pytest

from mpi_knn_tpu.frontend.coalesce import Coalescer
from mpi_knn_tpu.frontend.scheduler import (
    FrontendScheduler,
    Rejection,
    SLOPolicy,
)

# ---------------------------------------------------------------------------
# coalescer: formation triggers


def test_no_batch_before_fill_or_deadline():
    co = Coalescer(max_batch_rows=64, max_wait_s=0.010)
    co.admit("a", None, 16, now=0.0)
    co.admit("b", None, 16, now=0.001)
    assert co.pop_ready(0.005) is None  # 32 < 64 rows, oldest waited 5ms
    assert co.pending_rows == 32


def test_fill_triggers_immediately():
    co = Coalescer(max_batch_rows=64, max_wait_s=10.0)
    for i in range(4):
        co.admit(f"t{i}", None, 16, now=0.0)
    b = co.pop_ready(0.0)
    assert b is not None and b.reason == "fill"
    assert b.rows == 64 and len(b.parts) == 4
    assert co.pending_rows == 0 and co.pop_ready(0.0) is None


def test_deadline_triggers_ragged_batch():
    co = Coalescer(max_batch_rows=128, max_wait_s=0.010)
    co.admit("a", None, 16, now=0.0)
    assert co.pop_ready(0.0099) is None
    b = co.pop_ready(0.010)
    assert b is not None and b.reason == "deadline"
    assert b.rows == 16 and b.oldest_wait_s == pytest.approx(0.010)


def test_next_deadline_is_oldest_plus_max_wait():
    co = Coalescer(max_batch_rows=128, max_wait_s=0.010)
    assert co.next_deadline_s() is None
    co.admit("a", None, 8, now=0.002)
    co.admit("b", None, 8, now=0.001)  # later admit, earlier... no:
    # seq order is admission order, so "a" (seq 0) is the oldest even
    # though "b" carries a smaller timestamp — admission order IS the
    # deterministic arrival order under a coarse clock
    assert co.next_deadline_s() == pytest.approx(0.002 + 0.010)


def test_flush_forms_regardless():
    co = Coalescer(max_batch_rows=128, max_wait_s=10.0)
    co.admit("a", None, 8, now=0.0)
    assert co.pop_ready(0.0) is None
    b = co.pop_ready(0.0, flush=True)
    assert b is not None and b.reason == "flush" and b.rows == 8


def test_burst_forms_multiple_batches_in_one_poll():
    co = Coalescer(max_batch_rows=32, max_wait_s=10.0)
    for i in range(6):
        co.admit("a", None, 16, now=0.0)
    batches = []
    while (b := co.pop_ready(0.0)) is not None:
        batches.append(b)
    assert [b.rows for b in batches] == [32, 32, 32]


# ISSUE 36: a formed batch says since when it was dispatchable (ripe_s);
# the pump's dispatch lag is its own clock less that


@pytest.mark.parametrize("max_rows, max_wait_s, arrivals, polled, flush, want", [
    # polled at its deadline: no lag
    (128, 0.010, [(0.0, 16)], 0.010, False, [("deadline", 0.010)]),
    # polled 5 ms late: ripe since the deadline
    (128, 0.010, [(0.0, 16)], 0.015, False, [("deadline", 0.010)]),
    # fill: ripe since the arrival of the request that filled it (the
    # third), whatever came after
    (64, 10.0, [(0.0, 32), (0.001, 16), (0.003, 16), (0.004, 16)], 0.007,
     False, [("fill", 0.003)]),
    # a queue that filled after its oldest had expired was ripe at the expiry
    (64, 0.002, [(0.0, 32), (0.010, 32)], 0.012, False, [("fill", 0.002)]),
    # two batches of one poll: the second filled later
    (64, 10.0, [(0.0, 32), (0.001, 32), (0.002, 32), (0.003, 32)], 0.004,
     False, [("fill", 0.001), ("fill", 0.003)]),
    # a flush is ripe only now
    (128, 10.0, [(0.0, 8)], 0.5, True, [("flush", 0.5)]),
])
def test_ripe_s_is_when_the_batch_became_dispatchable(
        max_rows, max_wait_s, arrivals, polled, flush, want):
    co = Coalescer(max_batch_rows=max_rows, max_wait_s=max_wait_s)
    for i, (at, rows) in enumerate(arrivals):
        co.admit(f"t{i}", None, rows, now=at)
    got = []
    while (b := co.pop_ready(polled, flush=flush)) is not None:
        got.append((b.reason, b.ripe_s))
        assert b.formed_s == polled and b.ripe_s <= polled
    assert got == [(r, pytest.approx(t, abs=1e-12)) for r, t in want]


def test_oversized_and_empty_requests_raise_at_admit():
    co = Coalescer(max_batch_rows=32, max_wait_s=0.0)
    with pytest.raises(ValueError, match="exceeds max_batch_rows"):
        co.admit("a", None, 33, now=0.0)
    with pytest.raises(ValueError, match=">= 1 row"):
        co.admit("a", None, 0, now=0.0)


# ---------------------------------------------------------------------------
# coalescer: property tests over arrival orders


def _drive(events, max_batch_rows=64, max_wait_s=0.01):
    """Replay (kind, ...) events; returns the formed batches."""
    co = Coalescer(max_batch_rows=max_batch_rows, max_wait_s=max_wait_s)
    batches = []
    for ev in events:
        if ev[0] == "admit":
            _, tenant, rows, now = ev
            co.admit(tenant, None, rows, now)
        else:
            _, now = ev
            while (b := co.pop_ready(now)) is not None:
                batches.append(b)
    while (b := co.pop_ready(events[-1][-1], flush=True)) is not None:
        batches.append(b)
    return batches


def _random_events(seed, n_tenants=4, n_requests=60):
    rng = random.Random(seed)
    events, now = [], 0.0
    for _ in range(n_requests):
        now += rng.random() * 0.004
        events.append(
            ("admit", f"t{rng.randrange(n_tenants)}",
             rng.choice([1, 4, 8, 16, 32]), now)
        )
        if rng.random() < 0.5:
            events.append(("poll", now))
        if rng.random() < 0.3:
            now += 0.012  # jump past the wait budget
            events.append(("poll", now))
    events.append(("poll", now + 0.02))
    return events


@pytest.mark.parametrize("seed", range(8))
def test_property_conservation_fifo_and_caps(seed):
    """Over random arrival orders: every request serves exactly once,
    per-tenant FIFO order survives coalescing, no batch exceeds the row
    cap, and fill batches only form at/above the cap."""
    events = _random_events(seed)
    batches = _drive(events)
    admitted = [(e[1], e[2]) for e in events if e[0] == "admit"]
    served = [(r.tenant, r.rows) for b in batches for r in b.parts]
    # conservation: same multiset, nothing duplicated or dropped
    assert sorted(served) == sorted(admitted)
    seqs_seen = [r.seq for b in batches for r in b.parts]
    assert len(seqs_seen) == len(set(seqs_seen))
    # per-tenant FIFO: each tenant's seqs appear in admission order
    per_tenant: dict[str, list] = {}
    for b in batches:
        for r in b.parts:
            per_tenant.setdefault(r.tenant, []).append(r.seq)
    for seqs in per_tenant.values():
        assert seqs == sorted(seqs)
    for b in batches:
        assert b.rows == sum(r.rows for r in b.parts) <= 64
        if b.reason == "fill":
            # a fill batch formed because pending >= cap; with whole-
            # request granularity it still lands within one request of
            # full (the first misfit closes it)
            assert b.rows > 64 - 32


@pytest.mark.parametrize("seed", range(4))
def test_property_bit_determinism(seed):
    """The same arrival sequence always forms the same batches — the
    decisions are functions of (state, now) only."""
    events = _random_events(seed)
    a = _drive(events)
    b = _drive(events)
    assert [[r.seq for r in x.parts] for x in a] == \
        [[r.seq for r in x.parts] for x in b]
    assert [(x.rows, x.reason) for x in a] == [(x.rows, x.reason) for x in b]


def test_property_max_wait_bound():
    """No request waits beyond its budget when the pump polls at the
    deadline the coalescer itself announces."""
    co = Coalescer(max_batch_rows=1024, max_wait_s=0.010)
    rng = random.Random(5)
    now, pending, worst = 0.0, [], 0.0
    for i in range(200):
        now += rng.random() * 0.003
        co.admit(f"t{i % 3}", None, rng.choice([1, 8, 16]), now)
        pending.append(now)
        wake = co.next_deadline_s()
        if wake is not None and wake <= now:
            while (b := co.pop_ready(now)) is not None:
                for r in b.parts:
                    worst = max(worst, now - r.arrival_s)
                    pending.remove(r.arrival_s)
    # polls happen exactly at announced deadlines, so the worst wait is
    # bounded by max_wait plus one inter-arrival gap (< 3 ms here)
    assert worst <= 0.010 + 0.003 + 1e-9


def test_fairness_round_robin_bound():
    """Symmetric sustained backlog: round-robin draining serves every
    tenant the same number of requests per batch (skew <= 1 request),
    and the served max/min ratio over the run stays ~1 — the
    no-starvation bound."""
    co = Coalescer(max_batch_rows=64, max_wait_s=10.0)
    n_tenants = 4
    for i in range(40):  # 10 requests of 8 rows per tenant, interleaved
        co.admit(f"t{i % n_tenants}", None, 8, now=0.0)
    served: dict[str, int] = {}
    batches = []
    while (b := co.pop_ready(0.0)) is not None:
        batches.append(b)
        per_batch: dict[str, int] = {}
        for r in b.parts:
            served[r.tenant] = served.get(r.tenant, 0) + 1
            per_batch[r.tenant] = per_batch.get(r.tenant, 0) + 1
        # within one batch: at most one request of skew between tenants
        assert max(per_batch.values()) - min(per_batch.values()) <= 1
    assert len(batches) == 5  # 320 rows / 64
    assert max(served.values()) / min(served.values()) <= 1.5
    assert sum(served.values()) == 40


def test_fairness_flooder_cannot_starve_slow_tenant():
    """One tenant floods, one trickles: the trickler's request rides the
    very next batch (one-request-per-tenant-per-pass), not the tail of
    the flooder's backlog."""
    co = Coalescer(max_batch_rows=32, max_wait_s=10.0)
    for _ in range(20):
        co.admit("flood", None, 16, now=0.0)
    co.admit("slow", None, 16, now=0.001)
    b = co.pop_ready(0.001)
    assert sorted(r.tenant for r in b.parts) == ["flood", "slow"]


def test_deadline_triggered_batch_contains_the_oldest():
    """The request whose expired budget triggered formation is aboard —
    the rotation starts at its tenant."""
    co = Coalescer(max_batch_rows=32, max_wait_s=0.010)
    co.admit("a", None, 4, now=0.0)  # the oldest
    for _ in range(3):
        co.admit("b", None, 4, now=0.008)
    b = co.pop_ready(0.010)
    assert b.reason == "deadline"
    assert b.parts[0].tenant == "a" and b.parts[0].seq == 0


# ---------------------------------------------------------------------------
# scheduler: structured backpressure


def _policy(**kw):
    base = dict(max_batch_rows=64, max_wait_s=0.01, max_queue_rows=128)
    base.update(kw)
    return SLOPolicy(**base)


def test_queue_depth_rejection_is_structured_and_deterministic():
    sched = FrontendScheduler(_policy())
    outs = [sched.submit("a", None, 64, now=0.0) for _ in range(3)]
    assert not isinstance(outs[0], Rejection)
    assert not isinstance(outs[1], Rejection)  # 128 rows queued = the cap
    r = outs[2]
    assert isinstance(r, Rejection) and r.reason == "queue-depth"
    assert r.status == 429 and r.retry_after_s >= 0
    # another tenant is untouched by a's backpressure
    assert not isinstance(sched.submit("b", None, 64, now=0.0), Rejection)
    # determinism: replay the identical sequence — identical verdicts
    sched2 = FrontendScheduler(_policy())
    outs2 = [sched2.submit("a", None, 64, now=0.0) for _ in range(3)]
    assert [isinstance(o, Rejection) for o in outs2] == \
        [isinstance(o, Rejection) for o in outs]


def test_rate_limit_token_bucket():
    sched = FrontendScheduler(
        _policy(max_tenant_qps=10.0, burst=2, max_queue_rows=10_000)
    )
    a = sched.submit("a", None, 1, now=0.0)
    b = sched.submit("a", None, 1, now=0.0)
    c = sched.submit("a", None, 1, now=0.0)  # burst of 2 exhausted
    assert not isinstance(a, Rejection) and not isinstance(b, Rejection)
    assert isinstance(c, Rejection) and c.reason == "rate"
    assert c.retry_after_s == pytest.approx(0.1, rel=0.01)
    # tokens refill on the injected clock
    d = sched.submit("a", None, 1, now=0.2)
    assert not isinstance(d, Rejection)
    # other tenants have their own bucket
    assert not isinstance(sched.submit("b", None, 1, now=0.0), Rejection)


def test_oversized_request_rejected_not_raised():
    sched = FrontendScheduler(_policy())
    r = sched.submit("a", None, 65, now=0.0)
    assert isinstance(r, Rejection) and r.reason == "oversized-request"
    r0 = sched.submit("a", None, 0, now=0.0)
    assert isinstance(r0, Rejection) and r0.reason == "oversized-request"


def test_admitted_requests_always_serve():
    """Backpressure happens at admission ONLY: whatever was admitted
    comes back out of poll, nothing is dropped later."""
    sched = FrontendScheduler(_policy())
    n_admitted = 0
    for i in range(10):
        out = sched.submit(f"t{i % 3}", None, 48, now=0.0)
        n_admitted += 0 if isinstance(out, Rejection) else 1
    served = sum(
        len(b.parts) for b in sched.poll(1.0, flush=True)
    )
    assert served == n_admitted == sched.admitted


# ---------------------------------------------------------------------------
# scheduler: overload shed / recover (injected clock, injected session)


class _FakeLadder:
    """Stands in for ServeSession.shed_rung/restore_rung: a 3-rung walk
    recording every transition."""

    def __init__(self, rungs=("full", "mixed", "bucket/32")):
        self.rungs = rungs
        self.at = 0
        self.log = []

    def shed(self):
        if self.at >= len(self.rungs) - 1:
            self.log.append(("shed", None))
            return None
        self.at += 1
        self.log.append(("shed", self.rungs[self.at]))
        return self.rungs[self.at]

    def restore(self):
        if self.at == 0:
            self.log.append(("restore", None))
            return None
        self.at -= 1
        self.log.append(("restore", self.rungs[self.at]))
        return self.rungs[self.at]


def _overload_sched(ladder, **kw):
    pol = _policy(
        max_queue_rows=100_000,
        shed_queue_rows=256, shed_hold_s=0.05, recover_hold_s=0.10, **kw
    )
    return FrontendScheduler(
        pol, on_shed=ladder.shed, on_recover=ladder.restore
    )


def test_shed_fires_after_sustained_growth_only():
    lad = _FakeLadder()
    sched = _overload_sched(lad)

    def offer(now, rows=300):
        sched.submit("a", None, 64, now)  # keep the queue warm
        while sched.coalescer.pending_rows < rows:
            sched.submit("a", None, 64, now)

    # a single deep poll is a burst, not overload: no shed yet
    offer(0.0)
    sched.poll(0.0)
    assert lad.log == []
    # still deep after the hold time: one shed, exactly one
    offer(0.051)
    sched.poll(0.051)
    assert lad.log == [("shed", "mixed")]
    # the hold re-arms: the next shed needs ANOTHER sustained period
    offer(0.06)
    sched.poll(0.06)
    assert lad.log == [("shed", "mixed")]
    offer(0.12)
    sched.poll(0.12)
    assert lad.log == [("shed", "mixed"), ("shed", "bucket/32")]
    assert len(sched.sheds) == 2


def test_recover_restores_after_sustained_drain():
    lad = _FakeLadder()
    sched = _overload_sched(lad)
    for now in (0.0, 0.06):
        while sched.coalescer.pending_rows < 300:
            sched.submit("a", None, 64, now)
        sched.poll(now)
    assert lad.at == 1
    # queue drained (poll pops everything); recovery needs the hold
    sched.poll(0.10)
    sched.poll(0.15)
    assert lad.log[-1] == ("shed", "mixed")
    sched.poll(0.21)  # 0.10 -> 0.21 >= recover_hold_s below recover_rows
    assert lad.log[-1] == ("restore", "full") and lad.at == 0
    # fully recovered: quiet polls restore nothing further
    sched.poll(0.5)
    sched.poll(1.0)
    assert lad.log[-1] == ("restore", "full")
    assert len(sched.recoveries) == 1


def test_dip_below_threshold_resets_the_shed_hold():
    lad = _FakeLadder()
    sched = _overload_sched(lad)
    while sched.coalescer.pending_rows < 300:
        sched.submit("a", None, 64, now=0.0)
    sched.poll(0.0)
    sched.poll(0.03)  # dip: drained queue before the hold elapsed
    while sched.coalescer.pending_rows < 300:
        sched.submit("a", None, 64, now=0.06)
    sched.poll(0.06)  # deep again, but the hold restarted
    assert lad.log == []


def test_slo_policy_validation():
    with pytest.raises(ValueError, match="hysteresis"):
        SLOPolicy(max_batch_rows=64, max_queue_rows=128,
                  shed_queue_rows=100, recover_queue_rows=100)
    with pytest.raises(ValueError, match="never admit"):
        SLOPolicy(max_batch_rows=64, max_queue_rows=32)
    with pytest.raises(ValueError, match="max_tenant_qps"):
        SLOPolicy(max_batch_rows=64, max_queue_rows=64, max_tenant_qps=0.0)
    assert SLOPolicy(
        max_batch_rows=64, max_queue_rows=64, shed_queue_rows=100
    ).recover_rows == 50


def test_hostile_tenant_id_rejected_at_the_edge():
    """A tenant id the metrics exposition cannot carry (quotes,
    backslashes, newlines) is a structured rejection at admission —
    admitted-then-crash-at-retire would take the dispatch pump down for
    every other tenant (review regression)."""
    sched = FrontendScheduler(_policy())
    for bad in ('a"b', "a\\b", "a\nb", "", "x" * 257):
        r = sched.submit(bad, None, 4, now=0.0)
        assert isinstance(r, Rejection) and r.reason == "bad-tenant"
    assert sched.coalescer.pending_rows == 0  # nothing half-admitted
    assert not isinstance(sched.submit("fine-1", None, 4, now=0.0),
                          Rejection)


def test_loadgen_post_counts_connection_errors():
    """_post_query must return a countable failure (not kill the worker
    thread) when the server is unreachable — a load tool that loses its
    failures under load flatters what it exists to expose (review
    regression)."""
    import numpy as np

    from mpi_knn_tpu.frontend.loadgen import _post_query

    status, rows = _post_query(
        "http://127.0.0.1:9",  # discard port: connection refused
        "t", np.zeros((1, 4), np.float32), timeout_s=2.0,
    )
    assert status == 0 and rows == 0


# ---------------------------------------------------------------------------
# ISSUE 39: a predicate rides with a request's rows


@pytest.mark.parametrize("through", ["coalescer", "scheduler"])
def test_filters_ride_with_the_rows_through_slices(through):
    """Requests of different tenants, with and without predicates, meet
    in one batch; ``slices`` hands each its own back with its rows."""
    if through == "coalescer":
        c = Coalescer(max_batch_rows=8, max_wait_s=1.0)
        admit = c.admit
    else:
        s = FrontendScheduler(SLOPolicy(max_batch_rows=8, max_wait_s=1.0,
                                        max_queue_rows=64))
        c, admit = s.coalescer, s.submit
    admit("a", "rows-a", 3, 0.0, filters="tags-a")
    admit("b", "rows-b", 2, 0.0)
    admit("c", "rows-c", 3, 0.0, filters="tags-c")
    batch = c.pop_ready(0.0)
    assert batch.rows == 8 and batch.reason == "fill"
    assert [(r.tenant, r.queries, r.filters, lo, hi)
            for r, lo, hi in batch.slices()] == [
        ("a", "rows-a", "tags-a", 0, 3), ("b", "rows-b", None, 3, 5),
        ("c", "rows-c", "tags-c", 5, 8)]
