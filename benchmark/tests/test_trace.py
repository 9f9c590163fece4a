"""The trace reduction: busy union, idle share, own times, gap attribution;
on hand-made events and on a small trace recorded on the v5e."""

import os

import pytest

from benchmark import trace as T

RECORDED = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


def test_union_merges_nested_touching_and_disjoint():
    assert T.union([(3, 4), (0, 2), (1, 1.5), (2, 2.5), (5, 5)]) == [
        (0, 2.5), (3, 4)]


def test_gaps_are_the_complement_inside_the_window():
    assert T.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert T.gaps([], 0, 1) == [(0, 1)]
    assert T.gaps([(0, 1)], 0, 1) == []


def test_self_time_takes_nested_events_out_of_their_parent():
    own = T.self_times([("while", 0.0, 10.0), ("fusion", 1.0, 2.0),
                        ("sort", 4.0, 5.0), ("fusion", 20.0, 1.0)])
    assert own["while"] == pytest.approx(3.0)
    assert own["fusion"] == pytest.approx(3.0)
    assert own["sort"] == pytest.approx(5.0)


def test_gap_goes_to_our_annotation_and_the_most_overlapping_host_event():
    host = [("bench:call", 0.0, 10.0), ("PjitFunction(f)", 1.0, 1.0),
            ("TpuExecute", 1.2, 0.2), (T.WINDOW_ANNOTATION, 0.0, 100.0)]
    activity = T.HostActivity(host)
    assert activity.during((1.0, 2.0)) == "bench:call / PjitFunction(f)"
    assert activity.during((50.0, 51.0)) == T.NO_HOST
    assert T.HostActivity([("a", 0, 5), ("b", 1, 1)]).during((1.2, 1.4)) == "b"


def test_summary_on_hand_made_events():
    ev = {"device": {"/device:TPU:0": [
              ("while", 1.0, 2.0), ("fusion.1", 1.1, 0.5), ("sort", 1.7, 1.0),
              ("fusion.1", 3.5, 0.5), ("before", 0.0, 0.25)]},
          "host": [(T.WINDOW_ANNOTATION, 0.5, 4.0), ("bench:call", 0.5, 2.6),
                   ("bench:sleep", 3.0, 0.5)]}
    s = T.summarize(ev)
    assert s["window_s"] == pytest.approx(4.0)
    assert s["busy_s"] == pytest.approx(2.5)  # the event before 0.5 is out
    assert T.idle_pct(s) == pytest.approx(37.5)
    assert dict(map(tuple, s["device_ops"]))["sort"] == pytest.approx(1.0)
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert gaps["bench:call"] == pytest.approx(0.5)
    assert gaps["bench:sleep"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(1.5)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.summarize({"device": {}, "host": []})
    assert T.summarize_file(None, allow_empty=True) is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_v5e_trace():
    """Three 2048^3 bf16 matmuls with a 20 ms host sleep after each,
    recorded by .chipwork/tiny_trace.py on one TPU v5e (PR 25)."""
    s = T.summarize_file(RECORDED)
    assert s["chips"] == 1 and s["device_events"] == 6
    assert s["window_s"] == pytest.approx(0.0646414, rel=1e-5)
    # each matmul fusion runs 90.9 us on the device (17.2 GFLOP at 189
    # TFLOP/s). The device's clock leads the host's by some 0.1 ms in this
    # trace, so the first matmul ends before the host's annotation opens
    # and two of the three are inside the window.
    assert s["busy_s"] == pytest.approx(2 * 90.94e-6, rel=1e-3)
    assert T.idle_pct(s) == pytest.approx(99.72, abs=0.01)
    assert s["device_ops"][0][0] == (
        "%convolution_multiply_fusion fusion bf16[2048,2048]")
    name, seconds = s["idle_gaps"][0]
    assert name == "bench:sleep / PjitFunction(<lambda>)"
    assert seconds == pytest.approx(0.06446, rel=1e-3)


def test_hlo_lines_are_cut_to_name_kind_and_shape():
    assert T.short_name(
        "%fusion.35 = f32[1024,8192]{1,0:T(8,128)S(1)} fusion(f32[1024,128]"
        "{1,0:T(8,128)} %a, f32[8192]{0:T(1024)S(1)} %b), kind=kOutput, "
        "calls=%fused_computation.6.clone") == "%fusion.35 fusion f32[1024,8192]"
    assert T.short_name(
        "%sort.6 = (f32[64,2,8192]{0,2,1:T(8,128)}, s32[64,2,8192]{0,2,1}) "
        "sort(f32[64,2,8192]{0,2,1} %x), dimensions={2}"
    ) == "%sort.6 sort (f32[64,2,8192], s32[64,2,8192])"
    assert T.short_name("jit_f(123)") == "jit_f(123)"
