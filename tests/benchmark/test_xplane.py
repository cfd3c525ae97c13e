"""The reduction from a profiler capture to numbers, on a small capture
recorded on the chip (six steps of ppi_device_train, TPU v5 lite, PR 26,
cut from a 100-step capture: device ops and the eg_align stamp only) and
on intervals worked by hand."""

import os

import pytest

from benchmark import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(
    ROOT, "benchmark", "data", "ppi_device_train_6steps.xplane.pb")


@pytest.fixture(scope="module")
def capture():
    return xplane.read_capture(RECORDED)


def test_recorded_capture_has_one_chip_and_its_ops(capture):
    assert len(capture.lanes) == 1
    assert len(capture.fullest().events) == 816  # 136 ops a step x 6


def test_recorded_window_and_busy(capture):
    assert capture.window_s == pytest.approx(0.032962878, rel=1e-6)
    assert capture.busy_s == pytest.approx(0.02165704, rel=1e-6)
    lane = capture.fullest()
    idle = sum(b - a for a, b in lane.gaps()) * 1e-9
    assert idle == pytest.approx(capture.window_s - capture.busy_s, rel=1e-6)
    assert 0 < capture.busy_s < capture.window_s


def test_recorded_draw_kernel_is_found_by_its_call_target(capture):
    ops = capture.fullest().op_seconds(xplane.DRAW_KERNEL)
    assert len(ops) == 1
    (name, secs), = ops.items()
    assert "tpu_custom_call" in name and "_hops" in name
    assert secs == pytest.approx(0.001208962, rel=1e-6)  # 0.2 ms a step


def test_recorded_top_op_is_the_feature_gather(capture):
    top = capture.top_ops(3)
    assert top[0][0].startswith("%fusion.3 = f32[51200,50]")
    assert top[0][1] == pytest.approx(0.017847237, rel=1e-6)
    assert [r[1] for r in top] == sorted((r[1] for r in top), reverse=True)


def test_recorded_one_chip_capture_has_no_collective(capture):
    assert capture.fullest().op_seconds(xplane.COLLECTIVE) == {}


def test_recorded_align_stamp_is_read(capture):
    # monotonic ns minus profiler ns, from the eg_align:<us> annotation
    assert capture.align_offset_ns == pytest.approx(368599101754.0)


def test_union_and_gaps_by_hand():
    lane = xplane.DeviceLane(0, [
        ("a", 0.0, 10.0), ("b", 5.0, 12.0),      # overlap: busy 0..12
        ("c", 20.0, 30.0),                        # gap 12..20
        ("d", 30.0, 31.0),                        # abuts: no gap
        ("a", 40.0, 45.0),                        # gap 31..40
    ])
    assert lane.busy == [[0.0, 12.0], [20.0, 31.0], [40.0, 45.0]]
    assert lane.busy_ns() == 28.0
    assert lane.gaps() == [(12.0, 20.0), (31.0, 40.0)]
    assert lane.op_seconds()["a"] == pytest.approx(15e-9)


def test_idle_gaps_are_shared_out_among_the_host_spans_over_them():
    lane = xplane.DeviceLane(0, [
        ("op", 1000.0, 2000.0), ("op", 5000.0, 6000.0),
        ("op", 9000.0, 9500.0)])
    cap = xplane.Capture([lane], align_offset_ns=1_000_000.0)
    # profiler ns = monotonic ns - 1e6; phases in monotonic microseconds
    phases = [
        ("host", 1003, 1, 0, "MainThread"),      # 3000..4000 of gap 2000..5000
        ("device", 1004, 3, 1, "MainThread"),    # 4000..7000: 1000 of gap 1,
                                                 # 1000 of gap 6000..9000
        ("step", 1000, 10, 0, "MainThread"),     # covers all: ignored
        ("sample", 1006, 3, 1, "prefetch-0"),    # a worker: ignored
    ]
    out = dict(cap.idle_by_host_phase(phases))
    assert out == {"host_in_host": pytest.approx(1e-6),
                   "host_in_device": pytest.approx(2e-6),
                   "host_between_spans": pytest.approx(3e-6)}
    assert sum(out.values()) == pytest.approx(6e-6)  # all the idle time


def test_capture_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        xplane.Capture([], None)
