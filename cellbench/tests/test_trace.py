"""The reduction from device and host events to busy time, idle share,
top operations and gap attribution: on hand-made events, and on the
small trace recorded on the chip in `cellbench/testdata/`."""
import os

import pytest

from cellbench import run
from cellbench.readers import trace

MS = 1e6     # nanoseconds


def _events():
    dev = {"/device:TPU:0": [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0 * MS, 10 * MS),
        ("%copy.3 = bf16[8]{0} copy(%q)", 5 * MS, 10 * MS),  # overlaps
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 40 * MS, 10 * MS),
        ("fusion.2", 90 * MS, 10 * MS)]}
    host = [("PjitFunction(scatter)", 14 * MS, 28 * MS),
            ("ReadSyncFlag", 20 * MS, 2 * MS),     # runtime: ignored
            ("cellbench.outer", 10 * MS, 85 * MS),
            ("PjitFunction(late)", 200 * MS, 5 * MS)]
    return dev, host


def test_busy_is_the_union_of_intervals():
    dev, host = _events()
    assert trace.union(dev["/device:TPU:0"]) == [
        [0, 15 * MS], [40 * MS, 50 * MS], [90 * MS, 100 * MS]]
    got = trace.reduce_events(dev, host)
    assert got["busy_s"] == pytest.approx(0.035)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["idle_share"] == pytest.approx(0.65)
    # a traced window longer than the span of device events counts too
    assert trace.reduce_events(dev, host, 0.2)["idle_share"] == \
        pytest.approx(1 - 0.035 / 0.2)


def test_top_operations_sum_by_name():
    got = trace.reduce_events(*_events())
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    assert dict(map(tuple, got["device_ops"]))["copy.3"] == \
        pytest.approx(0.010)


def test_gaps_go_to_the_shortest_host_event_over_them():
    got = trace.reduce_events(*_events())
    gaps = dict(map(tuple, got["idle_gaps"]))
    # 15..40 ms lies under the scatter dispatch, 50..90 ms only under
    # the outer span
    assert gaps["PjitFunction_scatter_"] == pytest.approx(0.025)
    assert gaps["cellbench.outer"] == pytest.approx(0.040)
    dev, _ = _events()
    none = trace.reduce_events(dev, [])
    assert none["idle_gaps"] == [[trace.NO_SPAN, pytest.approx(0.065)]]


def test_two_devices_average_busy_and_report_the_worst():
    dev, host = _events()
    dev["/device:TPU:1"] = [("fusion.1", 0, 100 * MS)]
    got = trace.reduce_events(dev, host)
    assert got["busy_s"] == pytest.approx((0.035 + 0.100) / 2)
    assert got["idle_share"] == pytest.approx(0.65)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events({}, [])


def test_recorded_trace_reduces_to_its_known_numbers():
    path = os.path.join(run.HERE, "testdata", "small.xplane.pb")
    want = run.load_json(run.HERE, "testdata", "small.expected.json")
    devices, host = trace.load(path)
    assert sorted(devices) == want["device_planes"]
    assert sum(map(len, devices.values())) == want["device_events"]
    got = trace.reduce_events(devices, host)
    # an independent count: sweep the time line in fixed steps
    events = devices[want["device_planes"][0]]
    lo = min(s for _n, s, _d in events)
    hi = max(s + d for _n, s, d in events)
    step = (hi - lo) / 20000.0
    busy = sum(any(s <= lo + (k + 0.5) * step < s + d
                   for _n, s, d in events) for k in range(20000)) * step
    assert got["busy_s"] == pytest.approx(busy * 1e-9, rel=0.02)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert got["idle_share"] == pytest.approx(want["idle_share"],
                                              rel=1e-6)
    assert got["device_ops"][0][0] == want["top_op"]
    assert got["idle_gaps"][0][0] == want["top_gap"]
