"""The reduction from a profiler trace to the device metrics, on a small
trace recorded on the v5e (`data/small.xplane.pb`: three rounds of a jitted
matmul-and-sum and a jitted elementwise pass over f32[1024,1024], with
sleeps between, after one `bench:marker` annotation)."""

import os

import pytest

from harness import xplane

PB = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    return xplane.load(PB)


def test_merge_and_gaps():
    busy = xplane.merge([(5, 7, "a"), (1, 3, "b"), (2, 4, "c"), (9, 20, "d")],
                        0, 10)
    assert busy == [(1, 4), (5, 7), (9, 10)]
    assert xplane.gaps(busy, 0, 10) == [(0, 1), (4, 5), (7, 9)]
    assert xplane.gaps([], 2, 5) == [(2, 5)]


def test_self_segments_give_each_instant_to_the_innermost():
    segs = xplane.self_segments(
        [(0, 10, "job"), (2, 6, "stage"), (3, 4, "dispatch"),
         (12, 14, "late")], 0, 15)
    assert segs == [(0, 2, "job"), (2, 3, "stage"), (3, 4, "dispatch"),
                    (4, 6, "stage"), (6, 10, "job"),
                    (10, 12, xplane.NO_SPAN), (12, 14, "late"),
                    (14, 15, xplane.NO_SPAN)]
    by = xplane.overlap_by_name([(1, 5), (11, 13)], segs)
    assert by == {"job": 1, "stage": 2, "dispatch": 1, xplane.NO_SPAN: 1,
                  "late": 1}


def test_names_are_cut_to_what_a_reader_needs():
    assert xplane.short_op_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") == "fusion.3"
    assert xplane.short_module_name("jit_stage(12345)") == "jit_stage"


def test_recorded_trace_has_one_tpu_and_the_marker(profile):
    planes = xplane.device_planes(profile)
    assert [i for i, _ in planes] == [0]
    assert xplane.find_marker(profile, "bench:marker") == \
        pytest.approx(39544248.0)
    assert xplane.find_marker(profile, "no such marker") is None


def test_reduction_of_the_recorded_trace(profile):
    # the host's spans, on a clock of their own (microseconds) on which
    # the marker fell at 1000: a job over the whole window and a stage in
    # its first 100 ms; a pool thread's short span must not take the gaps
    spans = [{"name": "job", "ts": 1000.0, "dur": 250000.0, "tid": 1},
             {"name": "stage:execute", "ts": 2000.0, "dur": 100000.0,
              "tid": 1},
             {"name": "compile:aot-load", "ts": 5000.0, "dur": 100.0,
              "tid": 2}]
    r = xplane.reduce_trace(profile, "bench:marker", 1000.0,
                            (1000.0, 241000.0), spans)
    assert r["window_s"] == pytest.approx(0.24)
    # two of the three rounds fall wholly inside the window (the device's
    # clock reads ~1 ms ahead, so round one started "before" the marker):
    # 2 x (copy 6.1 us + matmul 11.8 us) + 3 x 12.6 us elementwise
    assert r["busy_s"] == pytest.approx(73.55e-6, rel=0.01)
    idle_pct = 100.0 * (1 - r["busy_s"] / r["window_s"])
    assert 99.9 < idle_pct < 100.0
    ops = dict(r["device_ops"])
    assert ops["jit_probe_elementwise/multiply_add_fusion"] == \
        pytest.approx(37.76e-6, rel=0.01)
    assert ops["jit_probe_matmul/convolution_reduce_fusion"] == \
        pytest.approx(23.61e-6, rel=0.01)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"job", "stage:execute"}
    assert gaps["stage:execute"] == pytest.approx(0.1, rel=0.01)
    assert gaps["job"] + gaps["stage:execute"] == \
        pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_a_trace_without_the_marker_gives_nothing(profile):
    assert xplane.reduce_trace(profile, "absent", 0.0, (0.0, 1.0), []) is None
