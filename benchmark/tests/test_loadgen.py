"""The generator's arithmetic from a seed: schedule, size law, tenant
shares, lateness, and the windows' reductions."""

import json
import os

import numpy as np
import pytest

from benchmark import loadgen as L
from benchmark.harness import load_by_path

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = json.load(open(os.path.join(BENCH, "traffic", "small-steady.json")))
BULK = json.load(open(os.path.join(BENCH, "traffic", "bulk-saturated.json")))


def test_size_law_is_inverse_with_mean_13_5():
    rows = L.size_quantiles(SMALL["rows_per_request"], 7200)
    assert rows.min() == 1 and rows.max() == 64
    r = np.arange(1, 65)
    law = (1 / r) / (1 / r).sum()
    assert law @ r == pytest.approx(13.49, abs=0.01)
    assert rows.mean() == pytest.approx(law @ r, rel=0.01)
    share = np.bincount(rows, minlength=65)[1:] / len(rows)
    assert np.abs(share - law).max() < 2e-3


def test_tenant_shares_30_30_and_the_rest_evenly():
    counts = L.tenant_counts(SMALL["tenant_shares"], 720)
    assert counts.sum() == 720
    assert list(counts) == [216, 216, 48, 48, 48, 48, 48, 48]
    assert L.tenant_counts(SMALL["tenant_shares"], 7).sum() == 7


def test_every_seed_sends_the_same_cycle_of_requests_from_another_start():
    a = L.open_schedule(SMALL, 1, 30.0, 4096, 512)
    b = L.open_schedule(SMALL, 2**31 + 9, 30.0, 4096, 512)
    n = int(round(SMALL["rate_requests_per_s"] * 30))
    assert len(a["due_s"]) == len(b["due_s"]) == n
    assert list(a["rows"]) != list(b["rows"])
    # b is a turned by some number of places: rows, tenants and gaps alike
    ga, gb = np.diff(a["due_s"]), np.diff(b["due_s"])
    turns = [t for t in range(n)
             if (np.roll(a["rows"], -t) == b["rows"]).all()
             and (np.roll(a["tenant"], -t) == b["tenant"]).all()]
    assert len(turns) == 1
    # the gap before the first request of a window is the one left out
    assert np.allclose(np.roll(np.append(ga, 0), -turns[0])[:-1][:n - turns[0] - 1],
                       gb[:n - turns[0] - 1])
    assert np.bincount(a["tenant"]).tolist() == L.tenant_counts(
        SMALL["tenant_shares"], n).tolist()
    for s in (a, b):
        assert s["due_s"][0] == 0.0 and 0 < s["due_s"][-1] < 30.0
        assert (np.diff(s["due_s"]) > 0).all()
        g = np.diff(s["due_s"])  # exponential gaps: CV near 1
        assert g.std() / g.mean() == pytest.approx(1.0, abs=0.1)
        # the longest request is wholly inside the probe block
        assert s["offset"][int(np.argmax(s["rows"]))] == 512
    same = L.open_schedule(SMALL, 1, 30.0, 4096, 512)
    assert (same["due_s"] == a["due_s"]).all()
    assert (same["offset"] == a["offset"]).all()


def test_closed_clients_walk_the_pool_blocks():
    rows = BULK["rows_per_request"]["rows"]
    seen = {L.closed_request(BULK, c, j, 4096) for c in range(BULK["clients"])
            for j in range(16)}
    assert {r for r, _ in seen} == {rows}
    assert {o for _, o in seen} == set(range(0, 4096, rows))
    # so the probe block is asked for again and again by every client
    assert L.probe_block(5, 4096) % L.PROBE_BLOCK == 0
    assert rows % L.PROBE_BLOCK == 0


def fake_log(entries, probe_lo=0):
    log = L.Log(probe_lo, 4096, 10)
    for e in entries:
        log.requests.append(dict(
            {"status": 200, "rows": 8, "tenant": "tenant-0", "ok": True}, **e))
    return log


def test_open_reduction_counts_failures_and_lateness():
    entries = [{"due": float(i), "sent": i + 0.001 * i, "done": i + 0.1 + 0.01 * i}
               for i in range(100)]
    entries[7].update(status=429, ok=False)
    entries[8].update(status=0, ok=False)
    out = L.reduce_open(fake_log(entries),
                        {"t0": 0.0, "t_end": 100.0, "scheduled": 101,
                         "unfinished": 1})
    assert out["attempted"] == 101 and out["failed"] == 3
    assert out["refused"] == 1
    lat = np.array([0.1 + 0.01 * i for i in range(100) if i not in (7, 8)])
    assert out["request_p50_ms"] == pytest.approx(np.percentile(lat, 50) * 1e3)
    assert sorted(out["latency_s"]) == pytest.approx(sorted(lat))
    reader = load_by_path("layer_metrics", "request_tail_p95_ms")
    assert reader.read({"loadgen": out}) == pytest.approx(
        np.percentile(lat, 95) * 1e3)
    assert reader.read({"loadgen": None}) is None
    assert out["last_third_p50_ms"] > out["first_third_p50_ms"]
    assert max(out["late_s"]) == pytest.approx(0.099)


def test_closed_window_opens_and_closes_at_the_end_of_a_burst():
    # bursts of 4 completions every 2 s from t=1, each spread over 90 ms;
    # the window is nominally [5.02, 15.02]: both cuts fall inside a burst
    entries = [{"sent": t - 1.9, "due": t - 1.9, "done": t + 0.03 * c,
                "rows": 256}
               for t in range(1, 20, 2) for c in range(4)]
    done = np.sort([e["done"] for e in entries])
    assert np.allclose(L.burst_ends(done), np.arange(1, 20, 2) + 0.09)
    even = np.cumsum(1.2 + 0.01 * np.sin(np.arange(30)))  # one a batch
    assert len(L.burst_ends(even)) == 30
    out = L.reduce_closed(fake_log(entries),
                          {"t0": 5.02, "t_end": 15.02, "scheduled": None,
                           "unfinished": 0})
    a, b = out["window"]
    assert a == pytest.approx(3.09) and b == pytest.approx(15.09)
    assert out["bursts"] == 6  # those that ended at 5, 7, ... 15 (+0.09)
    assert out["rows_answered"] == 6 * 4 * 256
    assert out["rows_per_s"] == pytest.approx(6 * 1024 / 12.0)
    assert out["attempted"] == 24 and out["failed"] == 0
    assert out["window_s"] >= 10.0  # never less than the nominal window


def steady(t_from, t_to, step=1.2, rows=1024):
    """One good 1024-row completion every ``step`` seconds."""
    return [{"sent": t - 2 * step, "due": t - 2 * step, "done": t,
             "rows": rows} for t in np.arange(t_from, t_to, step)]


def refusals(t_from, t_to, step=0.05):
    return [{"sent": t - 0.001, "due": t - 0.001, "done": t, "rows": 1024,
             "status": 503, "ok": False} for t in np.arange(t_from, t_to, step)]


SPAN = {"t0": 4.0, "t_end": 34.0, "scheduled": None, "unfinished": 0}


def test_closed_window_holds_an_outage_in_its_tail():
    healthy = L.reduce_closed(fake_log(steady(0.3, 37.0)), SPAN)
    assert healthy["failed"] == 0
    assert healthy["window_s"] == pytest.approx(31.2)
    assert healthy["rows_per_s"] == pytest.approx(1024 / 1.2)
    # 503s from t=20 to past the window's end: the rate falls by the share
    # of the window that was lost, and every refusal is counted
    out = L.reduce_closed(
        fake_log(steady(0.3, 20.0) + refusals(20.0, 34.5)), SPAN)
    assert out["window"] == pytest.approx((3.9, 34.0))
    assert out["window_s"] >= 30.0
    assert out["rows_per_s"] == pytest.approx(13 * 1024 / 30.1, rel=1e-3)
    assert out["rows_per_s"] < 0.55 * healthy["rows_per_s"]
    assert out["failed"] == len(refusals(20.0, 34.0)) + 1  # due 34.0 too
    # a stall across the nominal end, then recovery: the window runs on to
    # the first completion after it, so the stall is inside it
    out = L.reduce_closed(
        fake_log(steady(0.3, 20.0) + steady(40.0, 45.0)), SPAN)
    assert out["window"][1] == pytest.approx(40.0)
    assert out["rows_per_s"] == pytest.approx(14 * 1024 / 36.1, rel=1e-3)
    # requests that never came back are failures
    hung = L.reduce_closed(fake_log(steady(0.3, 20.0)),
                           dict(SPAN, unfinished=6))
    assert hung["failed"] == 6 and hung["window_s"] >= 30.0
    # nothing answered at all: no rate, and the failures still count
    none = L.reduce_closed(fake_log(refusals(0.0, 40.0)), SPAN)
    assert none["rows_per_s"] is None and none["failed"] > 500


def test_answers_are_checked_for_shape_order_and_finiteness():
    good = {"ids": [[1, 2], [3, 4]], "dists": [[0.5, 1.0], [2.0, 2.0]]}
    assert L.check_answer(good, 2, 2) is not None
    assert L.check_answer(good, 3, 2) is None
    assert L.check_answer({"ids": [[1, 2]], "dists": [[2.0, 1.0]]}, 1, 2) is None
    assert L.check_answer({"ids": [[1, 2]], "dists": [[1.0, float("nan")]]},
                          1, 2) is None
    assert L.check_answer({}, 1, 2) is None


def test_probe_rows_of_an_answer_are_kept():
    log = L.Log(256, 4096, 2)
    doc = {"ids": [[i, i + 1] for i in range(8)],
           "dists": [[0.0, 1.0]] * 8}
    log.record(due=0, sent=0, done=1, status=200, rows=8, offset=252,
               tenant="t", doc=doc)
    assert [p[0] for p in log.probe] == [256, 257, 258, 259]
    assert log.probe[0][1].tolist() == [4, 5]


def test_metrics_parse_and_delta():
    text = ('# HELP x y\n# TYPE x counter\nx 3.0\n'
            'h_sum 1.5\nh_count 2\nr{tenant="a",reason="b"} 1.0\n')
    m = L.parse_metrics(text)
    assert m["x"] == 3.0 and m['r{tenant="a",reason="b"}'] == 1.0
    assert L.metrics_delta({"x": 1.0}, m)["x"] == 2.0
    assert L.metrics_delta({}, m)["h_count"] == 2.0
