"""The reduction from a profiler trace to busy time, operation times and
labelled idle gaps: on events made by hand, and on a small trace
recorded on a TPU v5e (``data/small_trace.xplane.pb``)."""
import os

import pytest

from harness import spec
from harness import trace as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_trace.xplane.pb")


def ev(plane, line, name, a, b, meta=""):
    return tr.Event(plane, line, name, a, b - a, meta)


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_gap_labels_by_hand():
    events = [
        ev(HOST, "python", "bench:window", 0, 100),
        ev(HOST, "python", "bench:a", 0, 40),
        ev(HOST, "python", "bench:b", 45, 100),
        ev(DEV, "XLA Ops", "fusion.1", 10, 20),
        ev(DEV, "XLA Ops", "fusion.1", 15, 30),
        ev(DEV, "XLA Ops", "custom-call.2", 50, 60, "_dest_kernel"),
        ev(DEV, "XLA Ops", "fusion.1", 120, 130),  # after the window
        ev(DEV, "XLA Modules", "jit_f", 10, 60),  # not an operation line
    ]
    r = tr.reduce_events(events)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(30e-9)
    assert r.idle_share == pytest.approx(0.7)
    assert r.devices == 1
    assert r.op_times() == pytest.approx({"fusion.1": 25e-9,
                                          "custom-call.2": 10e-9})
    assert [e.name for e in r.matching("_dest_kernel")] == ["custom-call.2"]
    assert sorted(r.gaps) == sorted([("a", 10e-9), ("a", 20e-9),
                                     ("b", 40e-9)])
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(25e-9)]
    assert b["idle_gaps"][0] == ["b", pytest.approx(40e-9)]


def test_busy_is_averaged_over_devices():
    events = [ev(HOST, "python", "bench:window", 0, 10),
              ev(DEV, "XLA Ops", "f", 0, 4),
              ev("/device:TPU:1", "XLA Ops", "f", 0, 8)]
    r = tr.reduce_events(events)
    assert r.devices == 2 and r.busy_s == pytest.approx(6e-9)


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_events([ev(DEV, "XLA Ops", "f", 0, 4)])


def test_short_names_keep_name_and_result_type():
    assert tr.short_name("%fusion.4 = f32[32,1024]{1,0:T(8,128)} fusion(f32[1] "
                         "%p), kind=kLoop") == "%fusion.4 = f32[32,1024]"
    assert tr.short_name("%while.1 = (s32[]{:T(128)}, f32[2]{0}) while(") == \
        "%while.1 = (s32[]"


def test_nested_operations_are_charged_self_time():
    events = [ev(HOST, "python", "bench:window", 0, 100),
              ev(DEV, "XLA Ops", "%while.1 = (s32[]", 0, 50),
              ev(DEV, "XLA Ops", "%fusion.2 = f32[2]", 10, 30)]
    r = tr.reduce_events(events)
    assert r.busy_s == pytest.approx(50e-9)
    assert r.op_times() == pytest.approx({"%while.1 = (s32[]": 30e-9,
                                          "%fusion.2 = f32[2]": 20e-9})


def test_recorded_chip_trace():
    """Three matmul steps, three 2 ms host sleeps and three decide-kernel
    calls inside one window, recorded on one TPU v5e."""
    r = tr.reduce_events(tr.load_events(RECORDED))
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s
    decide = spec.reader("decide_kernel_roofline")
    assert len([e for e in r.ops if decide.is_decide_kernel(e)]) == 3
    labels = {name for name, _ in r.gaps}
    assert "host_sleep" in labels
    sleep = sum(s for name, s in r.gaps if name == "host_sleep")
    assert sleep >= 3 * 0.002 * 0.9
